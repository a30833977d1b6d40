"""Device self time under the scope ``edge_aggregate`` (and what nests in
it), forward and transpose, over the train programs' device time in the
traced epoch (benchmarks/scopes.py; the scope is put by models/schnet.py
and ops/segment.py)."""

from benchmarks import scopes


def compute(run):
    s = scopes.of_run(run)
    if s is None:
        return None
    return 100.0 * scopes.under(s["table"], "edge_aggregate") / s["total_s"]
