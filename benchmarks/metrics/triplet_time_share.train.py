"""Device self time under the scopes ``triplet`` and ``triplet_basis`` (the
triplet exchange of every DimeNet++ block and the spherical basis; put by
models/dimenet.py), forward and transpose, over the train programs' device
time in the traced epoch (benchmarks/triplet_scopes.py)."""

from benchmarks import triplet_scopes


def compute(run):
    s = triplet_scopes.of_run(run)
    if s is None:
        return None
    return 100.0 * (s["triplet"] + s["triplet_basis"]) / s["total_s"]
