"""The ``triplet`` blocks' share of their roofline in the traced epoch: the
least time the chip could take for the block and its transpose, summed over
the epoch's train steps and the model's blocks at each step's padded shapes
(counts/dimenet_triplet.py: the larger of bytes over the memory's peak and
operations over the chip's, from peaks.json; the StepClock rows'
``triplets_pad`` and ``edges_pad``), over the device self time the train
programs spent under the scope ``triplet``. The numerator is a lower bound
by construction, so the share cannot pass 100%."""

from benchmarks import harness, scopes, spec, triplet_scopes


def compute(run):
    if run.peaks is None:
        return None
    s = triplet_scopes.of_run(run)
    steps = [r for r in scopes.traced_train_rows(run) if "triplets_pad" in r]
    if s is None or s["triplet"] <= 0 or not steps:
        return None
    arch = spec.architecture(run.cell["config"])
    counts = spec.load_module("counts", "dimenet_triplet")
    least, binds = 0.0, set()
    for row in steps:
        seconds, which = counts.step_least_seconds(
            arch, row["triplets_pad"], row["edges_pad"], run.peaks
        )
        least += row["k"] * seconds
        binds.add(which)
    harness.log(
        f"triplet roofline: least {least:.4f}s over "
        f"{sum(r['k'] for r in steps)} train steps "
        f"({'/'.join(sorted(binds))} binds) against {s['triplet']:.4f}s "
        "under triplet"
    )
    return 100.0 * least / s["triplet"]
