"""All train graphs of the epochs inside the window over the window's whole
wall time, evaluation passes and epoch boundaries included (host clock)."""


def compute(run):
    f = run.facts
    return f["epochs"] * f["train_graphs"] / f["window_s"]
