#!/usr/bin/env python3
"""The yardstick checked against known answers, on the CPU, by hand:

    JAX_PLATFORMS=cpu python3 benchmarks/selfcheck.py

* the trace reduction on a hand-made set of planes (exact numbers) and on
  the small recorded TPU trace under fixtures/ (against the numbers a
  second method gave when it was recorded);
* the counts function against a hand-worked example with
  ``hidden_dim != num_filters``;
* the plain reference against the program at a tiny size: one forward
  pass and one loss through the program's own model and loss;
* the generator's determinism in ``--seed`` and the sameness of the work
  across seeds.

Prints one line per check and exits non-zero on the first that fails. It is
not a tier-1 test and not part of a benchmark run.
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
HERE = os.path.dirname(os.path.abspath(__file__))


def close(a, b, tol):
    return abs(a - b) <= tol * max(abs(a), abs(b), 1e-30)


def check_trace_synthetic():
    from benchmarks import trace

    u = 1000.0  # one unit is a microsecond, in the trace's nanoseconds
    planes = {
        "/device:TPU:0": {
            "XLA Ops": [
                ("%while.9 = (s32[]{:T(128)}, f32[8]{0}) while((s32[], f32[8]) %t), body=%b",
                 100 * u, 300 * u),
                ("fusion.1", 100 * u, 200 * u), ("fusion.2", 200 * u, 290 * u),
                ("copy.3", 500 * u, 600 * u), ("fusion.1", 900 * u, 1000 * u),
                ("fusion.1", 1000 * u + 1, 1000 * u + 2),
            ],
            "Steps": [("0", 100 * u, 1000 * u)],
        },
        "/host:CPU": {
            "main": [
                ("train_step", 0.0, 320 * u), ("eval_step", 450 * u, 650 * u),
                ("PjitFunction(step)", 10 * u, 300 * u),
                ("stop_trace", 1000 * u, 5000 * u),
            ],
            "other": [("noise", 0.0, 5 * u)],
        },
    }
    r = trace.reduce(planes)
    us = 1e-6
    # ops cover [100,300] + [500,600] + [900,1000] us and 1 ns more; the
    # window runs from the first step annotation (0) to the last op, and the
    # profiler's own stop after it is outside
    assert close(r["busy_s"], 400 * us + 1e-9, 1e-12), r
    assert close(r["window_s"], 1000 * us + 2e-9, 1e-12), r
    ops = dict(map(tuple, r["device_ops"]))
    # self times: the while keeps only the 10 us its body does not cover
    assert r["device_ops"][0][0] == "fusion.1", r
    assert close(ops["fusion.1"], 200 * us + 1e-9, 1e-12), r
    assert close(ops["fusion.2"], 90 * us, 1e-12), r
    assert close(ops["copy.3"], 100 * us, 1e-12), r
    assert close(ops["while tuple <- s32[], f32[8]"], 10 * us, 1e-9), r
    gaps = dict(map(tuple, r["idle_gaps"]))
    # gaps: [0,100] under PjitFunction (innermost), [300,500] and [600,900]
    # under nothing but eval_step's edge -> midpoints 400 and 750: none;
    # the 1 ns between the last two ops is the device's own hand-over
    assert close(gaps["PjitFunction(step)"], 100 * us, 1e-12), gaps
    assert close(gaps["no host span on the loop's thread"], 500 * us, 1e-12), gaps
    assert close(gaps[trace.BETWEEN_OPS], 1e-9, 1e-6), gaps
    assert r["annotated_thread"] == "main"
    assert trace.reduce({"/host:CPU": {"main": []}}) is None
    return "trace reduction, hand-made planes: busy 400 us of 1000 us"


def check_trace_recorded():
    from benchmarks import trace

    path = os.path.join(HERE, "fixtures", "small_trace.xplane.pb")
    with open(os.path.join(HERE, "fixtures", "small_trace.expected.json")) as fh:
        want = json.load(fh)
    r = trace.reduce(trace.planes_of(trace.load(path)))
    assert r is not None and r["devices"] == 1, r
    assert close(r["busy_s"], want["busy_s"], 1e-9), (r["busy_s"], want)
    assert 0 < r["busy_s"] <= r["window_s"], r
    for name, seconds in r["device_ops"]:
        assert close(seconds, want["op_totals_s"][name], 1e-9), name
    assert close(r["window_s"], want["window_s"], 1e-9), (r["window_s"], want)
    return (f"trace reduction, recorded TPU trace: busy {r['busy_s']:.3e}s "
            f"of {r['window_s']:.3e}s, {want['n_op_events']} op events")


def check_counts():
    from benchmarks import spec

    counts = spec.load_module("counts", "schnet")
    arch = {
        "hidden_dim": 8, "num_filters": 4, "num_gaussians": 3,
        "num_conv_layers": 2, "input_dim": 1,
        "output_heads": {
            "graph": {"num_sharedlayers": 1, "dim_sharedlayers": 5,
                      "num_headlayers": 1, "dim_headlayers": [6]},
            "node": {"num_headlayers": 1, "dim_headlayers": [7]},
        },
    }
    heads = [{"type": "graph", "dim": 1}, {"type": "node", "dim": 3}]
    n, e = 10, 40
    # by hand, multiply-adds: per edge and layer 3*4 + 4*4 (filter MLP) + 3*4
    # (envelope, product, sum) = 40 -> 40 edges * 40 = 1600 a layer;
    # lin1: layer 0 is 1x4, layer 1 is 8x4 -> 10 * (4 + 32) = 360;
    # lin2: 4x8 twice -> 10 * 32 * 2 = 640;
    # graph head 8x5 + 5x6 + 6x1 = 76; node head 10 * (8x7 + 7x3) = 770
    want = 2 * 1600 + 360 + 640 + 76 + 770
    got = counts.forward_macs(arch, heads, n, e)
    assert got == want, (got, want)
    assert counts.train_flops_per_graph(arch, heads, n, e) == 6 * want
    return f"counts, hand-worked (hidden 8 != filters 4): {want} forward MACs"


def check_reference_against_program():
    import jax
    import numpy as np

    from benchmarks import spec
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.models.create import create_model_config, init_params
    from hydragnn_tpu.train.losses import multihead_loss

    worst = 0.0
    for name in [w["name"] for w in spec.benchmark()["workloads"]]:
        cell = spec.cell(name, rehearse=True)
        driver = spec.load_module("drivers", cell["traffic"]["driver"])
        gen = spec.load_module("generators", cell["traffic"]["generator"])
        params_ = dict(cell["traffic"]["params"], n_graphs=40)
        records = gen.make(7, **params_)["train"][:12]
        samples = driver.to_samples(records)
        config = update_config(driver.build_config(cell, "/tmp/unused", False), samples)
        model, cfg = create_model_config(config)
        batch = next(iter(GraphLoader(samples, 12, fixed_pad=True)))
        params, stats = init_params(model, batch, seed=3)
        outs = model.apply({"params": params, "batch_stats": stats}, batch, train=True)
        tot, tasks = multihead_loss(outs, batch, cfg)
        arch = dict(cell["config"]["hydragnn"]["NeuralNetwork"]["Architecture"])
        heads = cell["config"]["heads"]
        ref = spec.load_module("references", arch["mpnn_type"].lower())
        n_pad = sum(len(r["z"]) for r in records) + 3
        e_pad = sum(len(r["senders"]) for r in records) + 5
        plain = ref.collate(records, (n_pad, e_pad, 13), len(heads) > 1)
        with jax.default_matmul_precision("highest"):
            rtot, rtasks = ref.loss_fn(jax.device_get(params), plain, arch, heads)
        gap = abs(float(tot) - float(rtot)) / abs(float(rtot))
        assert gap < 1e-5, (name, float(tot), float(rtot))
        assert np.allclose(np.asarray(tasks), np.asarray(rtasks), rtol=1e-5)
        worst = max(worst, gap)
    return f"reference against the program's model and loss, tiny, CPU: gap {worst:.1e}"


def check_generator():
    import numpy as np

    from benchmarks import spec

    gen = spec.load_module("generators", "clusters")
    p = dict(
        n_graphs=200, atoms={"edges": [9, 30], "weights": [1.0]},
        volume_per_atom=10.6, species=5, cutoff=6.0, max_neighbours=12,
        forces=True, structure_seed=4,
    )
    a, b, c = gen.make(11, **p), gen.make(11, **p), gen.make(2**31 + 12, **p)
    for split in gen.SPLITS:
        assert len(a[split]) == len(b[split]) == len(c[split])
        for ra, rb in zip(a[split], b[split]):
            assert all(np.array_equal(ra[k], rb[k]) for k in ra)
        size = lambda recs: sorted((len(r["z"]), len(r["senders"])) for r in recs)
        assert size(a[split]) == size(c[split]), "the seed changed the work"
    assert not np.array_equal(a["train"][0]["z"], c["train"][0]["z"]) or \
        not np.array_equal(a["train"][0]["pos"], c["train"][0]["pos"])
    deg = np.bincount(a["train"][0]["receivers"])
    assert deg.max() <= 12
    return "generator: same seed same data; another seed same sizes, other data"


def main() -> int:
    checks = [check_trace_synthetic, check_trace_recorded, check_counts,
              check_generator, check_reference_against_program]
    for fn in checks:
        print(f"ok: {fn()}", flush=True)
    print("selfcheck passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
