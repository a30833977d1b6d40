"""PaiNN (models/equivariant.py) against the benchmark's plain reference
(benchmarks/references/painn.py) on seeded random weights, at small widths
on the CPU: the model built as ``run_training`` builds it from the cell
``painn_qm9.train``'s configuration (its rehearsal widths, the atom-type
embedding on), fed the program's loader's own receiver-sorted batch; the
reference collates the generator's records itself and sums the ``[E, 3,
F]`` vector message with ``jax.ops.segment_sum``.

Tolerances. Both sides compute in float32 on the CPU from the same
parameters; they differ only in how the program lays out its sums (the
vector message folded to ``[E, 3F]``, the receiver sum on the sorted
scatter) and in the order of a few products, which moves single roundings.
Read: the outputs and the loss 0, the gradient 1.8e-7 whole (its worst
leaf 2.7e-6), the update 1.0e-6 whole (median leaf 3.3e-8, worst 6.6e-6),
a rigid motion 7.4e-7; the limits below are ten times and more that.
"""

import hashlib

import numpy as np
import pytest

import tests._cpu  # noqa: F401

import jax
import jax.numpy as jnp

from benchmarks import spec

CELL = "painn_qm9.train"
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
    records = gen.make(2147484905, **params_)["train"][:N_GRAPHS]
    samples = driver.to_samples(records)
    work = str(tmp_path_factory.mktemp("painn"))
    config = update_config(driver.build_config(cell, work, False), samples)
    model, cfg = create_model_config(config)
    batch = next(iter(GraphLoader(samples, N_GRAPHS, sort_receivers=True)))
    params, stats = init_params(model, batch, seed=5)
    ref = spec.load_module("references", "painn")
    shape = tuple(
        int(m.shape[-1])
        for m in (batch.node_mask, batch.edge_mask, batch.graph_mask)
    )
    return {
        "records": records, "model": model, "cfg": cfg, "batch": batch,
        "params": params, "stats": stats, "ref": ref,
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


def _program(s, batch):
    return np.asarray(
        s["model"].apply(
            {"params": s["params"], "batch_stats": s["stats"]}, batch,
            train=True,
        )[0]
    )[:N_GRAPHS]


def _reference(s, plain):
    with jax.default_matmul_precision("highest"):
        (out,) = s["ref"].forward(
            jax.device_get(s["params"]), plain, s["arch"], s["heads"]
        )
    return np.asarray(out)[:N_GRAPHS]


def test_every_block_runs_at_the_hidden_width(setup):
    """The atom type is embedded into F channels before block 1, so no
    block runs at the input width (one column, the atomic number)."""
    f = setup["cfg"].hidden_dim
    stack = setup["params"]["stack"]
    assert setup["cfg"].input_dim == 1 and f > 1
    assert stack["species_embed"]["kernel"].shape == (118, f)
    for i in range(setup["cfg"].num_conv_layers):
        assert stack[f"message_{i}"]["scalar_message_mlp"]["dense_0"][
            "kernel"].shape == (f, f)
        assert stack[f"update_{i}"]["update_U"]["kernel"].shape == (f, f)


def test_head_outputs_and_loss(setup):
    from hydragnn_tpu.train.losses import multihead_loss

    s = setup
    outs = s["model"].apply(
        {"params": s["params"], "batch_stats": s["stats"]}, s["batch"],
        train=True,
    )
    tot, _ = multihead_loss(outs, s["batch"], s["cfg"])
    with jax.default_matmul_precision("highest"):
        rtot, _ = s["ref"].loss_fn(
            jax.device_get(s["params"]), s["plain"], s["arch"], s["heads"]
        )
    got = np.asarray(outs[0])[:N_GRAPHS]
    assert got.shape == (N_GRAPHS, 1)
    assert _gap(got, _reference(s, s["plain"])) < 1e-5
    assert abs(float(tot) - float(rtot)) / abs(float(rtot)) < 1e-5


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
    assert abs(float(loss) - ref["loss"][0]) / ref["loss"][0] < 1e-5
    got = dict(_leaves(jax.device_get(grads)))
    want = dict(_leaves(ref["grad_first"]))
    assert set(got) == set(want)
    # every leaf, the atom-type embedding's rows of absent elements too
    # (their gradient is exactly nought on both sides), against the larger
    # of its own norm and the median leaf's
    median = np.median([np.linalg.norm(v) for v in want.values()])
    for name, g in want.items():
        gap = np.linalg.norm(got[name] - g) / max(np.linalg.norm(g), median)
        assert gap < 1e-4, (name, gap)
    assert _gap(np.concatenate([v.ravel() for _, v in sorted(got.items())]),
                np.concatenate([v.ravel() for _, v in sorted(want.items())])) < 1e-5
    # the update: Adam divides by the root of the second moment, which
    # carries a gradient's round-off into the update's elements
    before = dict(_leaves(jax.device_get(s["params"])))
    after = dict(_leaves(jax.device_get(new_state.params)))
    moved = dict(_leaves(ref["params"]))
    names = sorted(moved)
    gaps = [_gap(after[k] - before[k], moved[k] - before[k]) for k in names]
    assert np.median(gaps) < 1e-6, dict(zip(names, gaps))
    assert max(gaps) < 1e-4, dict(zip(names, gaps))
    whole = [np.concatenate([(t[k] - before[k]).ravel() for k in names])
             for t in (after, moved)]
    assert _gap(*whole) < 1e-5


def _rotated(pos, seed=7):
    """A random rotation and translation of ``pos`` [N, 3]."""
    rng = np.random.default_rng(seed)
    q, r = np.linalg.qr(rng.standard_normal((3, 3)))
    q = q * np.sign(np.diag(r))
    if np.linalg.det(q) < 0:
        q[:, 0] = -q[:, 0]
    shift = rng.uniform(-5.0, 5.0, 3)
    return (np.asarray(pos, np.float64) @ q.T + shift).astype(np.float32)


@pytest.mark.parametrize("side", ["program", "reference"])
def test_energy_is_invariant_under_rotation_and_translation(setup, side):
    """The graph head reads the same energy after a random rigid motion of
    every position; float32 round-off of the moved positions (lengths to
    ~1e-7 of their size) bounds the gap."""
    s = setup
    if side == "program":
        b = s["batch"]
        moved = b.replace(pos=jnp.asarray(_rotated(b.pos)))
        before, after = _program(s, b), _program(s, moved)
    else:
        moved = dict(s["plain"], pos=_rotated(s["plain"]["pos"]))
        before, after = _reference(s, s["plain"]), _reference(s, moved)
    assert np.std(before) > 0  # the graphs' energies differ
    assert _gap(after, before) < 1e-5


# sha256 over the head outputs and the sorted parameter leaves of the
# tests/test_equivariance.py models (PAINN, PNAEq), jitted init and apply
# on the CPU, as the stack computed them before the atom-type embedding was
# added: with the key off it is the same stack, bit for bit
TODAY = {
    "PAINN": "a0e39b1e9bbf783fce30046c052fa30d0f43bb3b7bc6d37364ed153e8d18d006",
    "PNAEq": "6b1a38f5b230f782936603b90da3cc2e3e5df57b899bb939e5d1878ca7e9cdae",
}


@pytest.mark.parametrize("mpnn_type", sorted(TODAY))
def test_species_embedding_off_is_todays_stack(mpnn_type):
    from hydragnn_tpu.data.graph import collate
    from hydragnn_tpu.models.create import create_model
    from tests.test_equivariance import _config, _samples

    cfg = _config(mpnn_type)
    assert cfg.species_embedding is False
    model = create_model(cfg)
    batch = collate(_samples())
    variables = jax.jit(
        lambda b: model.init(jax.random.PRNGKey(0), b, train=False)
    )(batch)
    outs = jax.jit(model.apply)(variables, batch)
    digest = hashlib.sha256()
    for out in outs:
        digest.update(np.asarray(out).tobytes())
    for path, leaf in sorted(
        jax.tree_util.tree_flatten_with_path(variables["params"])[0],
        key=lambda kv: str(kv[0]),
    ):
        digest.update(str(path).encode())
        digest.update(np.asarray(leaf).tobytes())
    assert digest.hexdigest() == TODAY[mpnn_type]


@pytest.mark.parametrize(
    "kw",
    [
        {"mpnn_type": "SchNet"},
        {"mpnn_type": "MACE"},
        {"mpnn_type": "PAINN", "global_attn_engine": "GPS"},
    ],
    ids=["SchNet", "MACE", "PAINN-GPS"],
)
def test_species_embedding_where_it_is_not_honoured_raises(kw):
    from hydragnn_tpu.models.spec import ModelConfig

    with pytest.raises(ValueError, match="species_embedding"):
        ModelConfig(species_embedding=True, **kw)


@pytest.mark.parametrize("precision", [None, "highest"])
def test_stated_matmul_precision_reaches_every_product(setup, precision):
    """``Training.matmul_precision`` puts every matrix product of the
    model at that precision, forward and backward (the cell states
    ``highest``); unset, no product names one."""
    import dataclasses

    from hydragnn_tpu.models.create import create_model
    from hydragnn_tpu.train import loop

    cfg = dataclasses.replace(setup["cfg"], matmul_precision=precision)
    loss = loop.make_loss_fn(create_model(cfg), cfg)
    text = jax.jit(
        jax.grad(lambda p: loss(p, setup["stats"], setup["batch"])[0])
    ).lower(setup["params"]).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert len(dots) > 20  # forward and transposed products of every block
    stated = [line for line in dots if "precision = [HIGHEST, HIGHEST]" in line]
    assert len(stated) == (len(dots) if precision else 0)


def test_matmul_precision_must_be_known():
    from hydragnn_tpu.models.spec import ModelConfig

    with pytest.raises(ValueError, match="matmul_precision"):
        ModelConfig(matmul_precision="float32")
