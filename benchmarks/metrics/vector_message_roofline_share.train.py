"""The ``vector_message`` blocks' share of their roofline in the traced
epoch: the least time the chip could take for the block and its transpose,
summed over the epoch's train steps and the model's blocks at each step's
padded shapes (counts/painn_vector_message.py: the larger of bytes over the
memory's peak and operations over the chip's, from peaks.json; the
StepClock rows' ``nodes_pad`` and ``edges_pad``), over the device self time
the train programs spent under the scope. The numerator is a lower bound by
construction, so the share cannot pass 100%."""

from benchmarks import harness, named_scopes, scopes, spec


def compute(run):
    if run.peaks is None:
        return None
    s = named_scopes.of_run(run, ("vector_message",))
    steps = scopes.traced_train_rows(run)
    if s is None or s["vector_message"] <= 0 or not steps:
        return None
    arch = spec.architecture(run.cell["config"])
    counts = spec.load_module("counts", "painn_vector_message")
    least, binds = 0.0, set()
    for row in steps:
        seconds, which = counts.step_least_seconds(
            arch, row["nodes_pad"], row["edges_pad"], run.peaks
        )
        least += row["k"] * seconds
        binds.add(which)
    harness.log(
        f"vector message roofline: least {least:.4f}s over "
        f"{sum(r['k'] for r in steps)} train steps "
        f"({'/'.join(sorted(binds))} binds) against "
        f"{s['vector_message']:.4f}s under vector_message"
    )
    return 100.0 * least / s["vector_message"]
