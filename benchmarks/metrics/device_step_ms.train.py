"""Device time of one optimizer step: the durations of the train programs'
events on the trace's ``XLA Modules`` line, a ``train_superstep`` divided by
the K of its dispatch (the StepClock row's ``k``, in dispatch order), median
over the traced epoch's optimizer steps. No fence: the device's own clock."""

import statistics

from benchmarks import harness, scopes


def compute(run):
    s = scopes.of_run(run)
    if s is None:
        return None
    modules = [
        (b - a) / 1e6 for program, a, b in s["reduced"]["modules"]
        if program in scopes.TRAIN_PROGRAMS
    ]
    ks = [int(r["k"]) for r in scopes.traced_train_rows(run)]
    if not modules or len(modules) != len(ks):
        harness.log(
            f"device_step_ms: {len(modules)} train program events against "
            f"{len(ks)} dispatches in the traced epoch's rows: not read"
        )
        return None
    per_step = [ms / k for ms, k in zip(modules, ks) for _ in range(k)]
    busy = run.trace()
    everything = sum((b - a) for _, a, b in s["reduced"]["modules"]) / 1e9
    harness.log(
        f"device_step_ms: {len(per_step)} optimizer steps in {len(modules)} "
        f"dispatches, train programs {sum(modules) / 1e3:.3f}s, all programs "
        f"{everything:.3f}s" + (f" (busy {busy['busy_s']:.3f}s)" if busy else "")
    )
    return statistics.median(per_step)
