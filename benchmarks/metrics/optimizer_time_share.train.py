"""Device self time under the scope ``optimizer`` (train/state.py:
``tx.update`` and ``optax.apply_updates``) over the train programs' device
time in the traced epoch (benchmarks/scopes.py)."""

from benchmarks import scopes


def compute(run):
    s = scopes.of_run(run)
    if s is None:
        return None
    return 100.0 * scopes.under(s["table"], "optimizer") / s["total_s"]
