"""The ``edge_aggregate`` block's share of its roofline in the traced epoch:
the least time the chip could take for the block and its transpose, summed
over the epoch's train steps and the model's layers at each step's padded
shapes (counts/<mpnn_type>_edge_aggregate.py: the larger of bytes over the
memory's peak and operations over the chip's, from peaks.json), over the
device self time the train programs spent under the scope, copies and
layout changes included. The numerator is a lower bound by construction, so
the share cannot pass 100%."""

from benchmarks import harness, scopes, spec


def compute(run):
    s = scopes.of_run(run)
    if s is None or run.peaks is None:
        return None
    spent = scopes.under(s["table"], "edge_aggregate")
    steps = scopes.traced_train_rows(run)
    if spent <= 0 or not steps:
        return None
    arch = spec.architecture(run.cell["config"])
    counts = spec.load_module(
        "counts", arch["mpnn_type"].lower() + "_edge_aggregate"
    )
    least, binds = 0.0, set()
    for row in steps:
        seconds, which = counts.step_least_seconds(
            arch, row["nodes_pad"], row["edges_pad"], run.peaks
        )
        least += row["k"] * seconds
        binds.add(which)
    harness.log(
        f"segment roofline: least {least:.4f}s over {sum(r['k'] for r in steps)} "
        f"train steps ({'/'.join(sorted(binds))} binds) against {spent:.4f}s "
        "under edge_aggregate"
    )
    return 100.0 * least / spent
