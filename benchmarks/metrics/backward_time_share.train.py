"""Self time of the ops traced under ``transpose(`` (JAX's own mark of the
backward pass in an op's path) over the train programs' device time in the
traced epoch (benchmarks/scopes.py)."""

from benchmarks import scopes


def compute(run):
    s = scopes.of_run(run)
    if s is None:
        return None
    return 100.0 * sum(r["bwd"] for r in s["table"].values()) / s["total_s"]
