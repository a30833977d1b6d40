"""The whole step's share of the chip's peak: the benchmark's own count of
forward+backward operations per real graph (mean real atoms and edges of
the train split, no padding, no recomputation) times the rate of train
graphs, over chips x the peak of the device kind.

The rate is taken over the window's epochs after the traced one (epoch 1
carries the profiler's start and stop, 5 s of a 21 s epoch in my chip run
2, PR 26; the driver lets a traced run go on for --seconds after it),
evaluation passes and epoch boundaries included; where the window holds
only one epoch, over the whole window."""

from benchmarks import spec


def compute(run):
    if run.peaks is None:
        return None
    f = run.facts
    config = run.cell["config"]
    arch = spec.architecture(config)
    counts = spec.load_module("counts", arch["mpnn_type"].lower())
    train = f["splits"]["train"]
    n = sum(len(r["z"]) for r in train) / len(train)
    e = sum(len(r["senders"]) for r in train) / len(train)
    flops = counts.train_flops_per_graph(arch, config["heads"], n, e)
    starts = f["epoch_starts"]  # seconds after the window opened
    if f.get("trace_dir") and len(starts) >= 2:
        rate = (f["epochs"] - 1) * f["train_graphs"] / (f["window_s"] - starts[1])
    else:
        rate = f["epochs"] * f["train_graphs"] / f["window_s"]
    peak = run.peaks["flops_per_s"] * run.device["count"]
    return 100.0 * flops * rate / peak
