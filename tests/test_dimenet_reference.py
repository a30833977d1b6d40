"""DimeNet++ (models/dimenet.py) against the benchmark's plain reference
(benchmarks/references/dimenet.py) on seeded random weights, at small
widths on the CPU: the model built as ``run_training`` builds it from the
cell ``dimenet_pp_qm9.train``'s configuration (its rehearsal widths), fed
the program's loader's own batch; the reference builds its triplets and
its basis from the generator's records alone.

Tolerances. Both sides compute in float32 on the CPU; they differ in the
order of their sums and in the basis: the program evaluates the radial
Bessel functions through a 64-term Chebyshev fit, the reference in float64
(4e-7 of the basis's size, root mean square, on the cell's own batches;
PERF.md). Through two blocks that carries to a few 1e-6 of the outputs
and the loss; the limits below are ten times and more what these tests
read.
"""

import numpy as np
import pytest

import tests._cpu  # noqa: F401

import jax
import jax.numpy as jnp

from benchmarks import spec

CELL = "dimenet_pp_qm9.train"
N_GRAPHS = 10


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.models.create import create_model_config, init_params
    from hydragnn_tpu.train.optimizer import select_optimizer

    cell = spec.cell(CELL, rehearse=True)
    driver = spec.load_module("drivers", "train")
    gen = spec.load_module("generators", "clusters")
    params_ = dict(cell["traffic"]["params"], n_graphs=40)
    records = gen.make(2147483905, **params_)["train"][:N_GRAPHS]
    samples = driver.to_samples(records)
    work = str(tmp_path_factory.mktemp("dimenet"))
    config = update_config(driver.build_config(cell, work, False), samples)
    model, cfg = create_model_config(config)
    batch = next(iter(GraphLoader(samples, N_GRAPHS, with_triplets=True)))
    params, stats = init_params(model, batch, seed=5)
    ref = spec.load_module("references", "dimenet")
    shape = tuple(
        int(m.shape[-1])
        for m in (batch.node_mask, batch.edge_mask, batch.graph_mask)
    )
    return {
        "cell": cell, "records": records, "model": model, "cfg": cfg,
        "batch": batch, "params": params, "stats": stats, "ref": ref,
        "plain": ref.collate(records, shape, False),
        "arch": spec.architecture(cell["config"]),
        "heads": cell["config"]["heads"], "opt": cell["config"]["optimizer"],
        "tx": select_optimizer(config["NeuralNetwork"]["Training"]),
    }


def _gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _leaves(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], f"{prefix}/{k}" if prefix else k)
    else:
        yield prefix, np.asarray(tree, np.float64)


def test_triplets_are_the_loaders(setup):
    """The same angular triplets (k, j, i), k != i, on both sides, as sets
    of node ids: the loader's from data/graph.py's plan arithmetic, the
    reference's from a plain pairing of every edge k->j with every j->i."""
    b, plain = setup["batch"], setup["plain"]
    snd, rcv = np.asarray(b.senders), np.asarray(b.receivers)
    m = np.asarray(b.triplet_mask)
    kj, ji = np.asarray(b.t_kj)[m], np.asarray(b.t_ji)[m]
    assert np.all(rcv[kj] == snd[ji])
    program = set(zip(snd[kj].tolist(), rcv[kj].tolist(), rcv[ji].tolist()))
    r_snd, r_rcv = plain["snd"], plain["rcv"]
    rkj, rji = plain["t_kj"], plain["t_ji"]
    reference = set(
        zip(r_snd[rkj].tolist(), r_rcv[rkj].tolist(), r_rcv[rji].tolist())
    )
    assert len(program) == len(kj) == len(rkj)
    assert program == reference
    assert all(k != i for k, _, i in program)


def test_head_outputs_and_loss(setup):
    from hydragnn_tpu.train.losses import multihead_loss

    s = setup
    outs = s["model"].apply(
        {"params": s["params"], "batch_stats": s["stats"]}, s["batch"],
        train=True,
    )
    tot, _ = multihead_loss(outs, s["batch"], s["cfg"])
    params = jax.device_get(s["params"])
    with jax.default_matmul_precision("highest"):
        (want,) = s["ref"].forward(params, s["plain"], s["arch"], s["heads"])
        rtot, _ = s["ref"].loss_fn(params, s["plain"], s["arch"], s["heads"])
    got = np.asarray(outs[0])[:N_GRAPHS]  # read: 1.3e-6, the loss 1.5e-6
    assert got.shape == (N_GRAPHS, 1)
    assert _gap(got, np.asarray(want)[:N_GRAPHS]) < 2e-5
    assert abs(float(tot) - float(rtot)) / abs(float(rtot)) < 2e-5


def _with_moments(opt_state, mu, nu, count):
    """The optax state with Adam's moments and step count replaced."""
    import optax

    def put(node):
        if isinstance(node, optax.ScaleByAdamState):
            return node._replace(
                count=jnp.asarray(count, node.count.dtype), mu=mu, nu=nu
            )
        return node

    return jax.tree_util.tree_map(
        put, opt_state,
        is_leaf=lambda n: isinstance(n, optax.ScaleByAdamState),
    )


def test_gradient_and_one_adamw_step(setup):
    """The whole gradient and one AdamW update from a warm state (seeded
    moments at Adam step 10: from zero moments the first update is
    lr * sign(g), which turns round-off in a near-zero gradient into a
    whole step), through the program's own jitted train step."""
    from hydragnn_tpu.train import loop
    from hydragnn_tpu.train.state import create_train_state

    s = setup
    rng = np.random.default_rng(11)
    mu = jax.tree_util.tree_map(
        lambda p: jnp.asarray(1e-3 * rng.standard_normal(p.shape), p.dtype),
        s["params"],
    )
    nu = jax.tree_util.tree_map(
        lambda p: jnp.asarray(1e-6 * (1 + rng.random(p.shape)), p.dtype),
        s["params"],
    )
    state = create_train_state(s["params"], s["tx"], s["stats"])
    state = state.replace(opt_state=_with_moments(state.opt_state, mu, nu, 10))
    step = loop.make_train_step(s["model"], s["tx"], s["cfg"], donate=False)
    new_state, loss, _ = step(state, s["batch"])
    grads = jax.grad(
        lambda p: loop.make_loss_fn(s["model"], s["cfg"])(
            p, s["stats"], s["batch"]
        )[0]
    )(s["params"])
    ref = s["ref"].follow(
        jax.device_get(s["params"]), [s["plain"]], s["arch"], s["heads"],
        s["opt"], mu=jax.device_get(mu), nu=jax.device_get(nu), t0=10,
    )
    # read: loss 4.7e-7, gradient 2.7e-6 whole, its worst leaf 6.7e-6
    assert abs(float(loss) - ref["loss"][0]) / ref["loss"][0] < 2e-5
    got = dict(_leaves(jax.device_get(grads)))
    want = dict(_leaves(ref["grad_first"]))
    assert set(got) == set(want)
    median = np.median([np.linalg.norm(v) for v in want.values()])
    for name, g in want.items():
        gap = np.linalg.norm(got[name] - g) / max(np.linalg.norm(g), median)
        assert gap < 1e-4, (name, gap)
    assert _gap(np.concatenate([v.ravel() for _, v in sorted(got.items())]),
                np.concatenate([v.ravel() for _, v in sorted(want.items())])) < 3e-5
    # Adam divides the first moment by the root of the second: where an
    # element's gradient is near nought its second moment is small, and
    # the gradient's round-off, which the leaf's largest elements set,
    # moves that element's update whole. The worst leaf,
    # stack/inter_1/after_skip_0/lin1/kernel, reads 3.2e-4 against a
    # gradient gap of 3.6e-6; Adam's formula in float64 on the two
    # gradients alone gives 2.0e-4 of it. Read: whole 6.3e-5, median
    # leaf 1.5e-5, worst leaf 3.2e-4
    before = dict(_leaves(jax.device_get(s["params"])))
    after = dict(_leaves(jax.device_get(new_state.params)))
    moved = dict(_leaves(ref["params"]))
    names = sorted(moved)
    gaps = [_gap(after[k] - before[k], moved[k] - before[k]) for k in names]
    assert np.median(gaps) < 1.5e-4, dict(zip(names, gaps))
    assert max(gaps) < 3e-3, dict(zip(names, gaps))
    whole = [np.concatenate([(t[k] - before[k]).ravel() for k in names])
             for t in (after, moved)]
    assert _gap(*whole) < 6e-4


def test_chebyshev_contraction_is_pinned():
    """ops/sbf.py evaluates the radial basis as ``feats @ coeffs``; at the
    default precision a TPU rounds both operands to bfloat16, so the
    contraction asks for HIGHEST whatever the caller's default is."""
    from hydragnn_tpu.ops.sbf import chebyshev_eval

    t = jnp.linspace(-1.0, 1.0, 16)
    coeffs = jnp.ones((64, 42), jnp.float32)
    with jax.default_matmul_precision("default"):
        text = jax.jit(chebyshev_eval).lower(t, coeffs).as_text()
    (dot,) = [line for line in text.splitlines() if "dot_general" in line]
    assert dot.count("HIGHEST") == 2, dot


def test_step_rows_count_triplets(tmp_path):
    """A triplet-bearing loader's StepClock rows carry the real triplets
    (``count_triplets`` of the step's samples) and the padded slots; the
    rows of a loader without triplets carry neither."""
    import json
    import time

    from hydragnn_tpu.data.graph import count_triplets
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.utils import telemetry

    driver = spec.load_module("drivers", "train")
    gen = spec.load_module("generators", "clusters")
    params_ = dict(spec.cell(CELL, rehearse=True)["traffic"]["params"], n_graphs=20)
    samples = driver.to_samples(gen.make(3, **params_)["train"][:12])
    path = str(tmp_path / "t.jsonl")
    stream = telemetry.TelemetryStream(path)
    telemetry.install(stream)
    try:
        for trips in (True, False):
            loader = GraphLoader(samples, 4, with_triplets=trips)
            clock = telemetry.epoch_clock(loader, "train")
            for step, batch in enumerate(loader, start=1):
                t = time.perf_counter()
                clock.record(step=step, k=1, batch=batch, is_macro=False,
                             t_fetch_start=t, t_fetch_end=t,
                             t_dispatch_start=t, t_dispatch_end=t)
            clock.finish()
    finally:
        telemetry.close_run(stream)
    rows = [json.loads(line) for line in open(path)]
    rows = [r for r in rows if r.get("t") == "step"]
    assert len(rows) == 6
    with_t, without = rows[:3], rows[3:]
    for i, row in enumerate(with_t):
        assert row["triplets"] == sum(
            count_triplets(x) for x in samples[4 * i:4 * i + 4]
        )
        assert row["triplets"] <= row["triplets_pad"]
    assert len({r["triplets_pad"] for r in with_t}) == 1
    assert not any("triplets" in r or "triplets_pad" in r for r in without)
