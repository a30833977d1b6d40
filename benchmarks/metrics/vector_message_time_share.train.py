"""Device self time under the scope ``vector_message`` (the edge work of
every PaiNN message block: the radial filter, both sender gathers, the
products and both receiver sums; put by models/equivariant.py), forward
and transpose, over the train programs' device time in the traced epoch
(benchmarks/named_scopes.py)."""

from benchmarks import named_scopes


def compute(run):
    s = named_scopes.of_run(run, ("vector_message",))
    if s is None:
        return None
    return 100.0 * s["vector_message"] / s["total_s"]
