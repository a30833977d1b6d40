"""1 minus the union of device-op intervals over the traced span, from the
.xplane.pb the program's Profiler wrote (benchmarks/trace.py)."""


def compute(run):
    t = run.trace()
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
