"""DimeNet's triplet reduce on XLA's sorted scatter (ops/segment.py:
``sum_over_triplets``) and the promise batch forming carries for it:
``t_ji`` is nondecreasing in every batch ``fill_triplets`` fills, padding
triplets included, and the batch says so (``GraphBatch.triplets_sorted``).

The same mechanism as the receiver sum's (tests/test_segment_runs.py): the
promise changes no shape and no order of additions, so values and
gradients are checked bit for bit against a twin batch that promises
nothing. All on the CPU at toy sizes.
"""

import re

import numpy as np
import pytest

import tests._cpu  # noqa: F401

import jax
import jax.numpy as jnp

from hydragnn_tpu.data.graph import PadSpec, collate, count_triplets
from hydragnn_tpu.ops import segment as seg
from tests.test_superstep import _config, _mols

SORTED_SCATTER = r'"stablehlo.scatter"\([^\n]*indices_are_sorted = true'


def _samples(n=6, seed=2):
    return _mols(n, seed=seed)


def _full_edges_spec(samples):
    """Edges padded to exactly the real count (e_real == E), triplets with
    room to spare: the padding triplets then share the last real edge's
    index."""
    n = sum(s.num_nodes for s in samples)
    e = sum(s.num_edges for s in samples)
    t = sum(count_triplets(s) for s in samples)
    return PadSpec(
        num_nodes=n + 1, num_edges=e, num_graphs=len(samples) + 1,
        num_triplets=t + 7,
    )


def _check_sorted(batch, e_real=None):
    t_ji = np.asarray(batch.t_ji)
    mask = np.asarray(batch.triplet_mask)
    assert batch.triplets_sorted is True
    assert np.all(np.diff(t_ji) >= 0)  # padding triplets included
    assert (~mask).any() and not mask[int(mask.sum()):].any()
    assert np.all(t_ji[~mask] == batch.num_edges - 1)
    if e_real is not None:
        assert t_ji[mask].max() == e_real - 1


def _collated(collator, samples, spec):
    from hydragnn_tpu.data.pipeline import PackedStore, collate_packed

    if collator == "collate":
        return collate(samples, spec, as_numpy=True)
    if collator == "collate_packed":
        return collate_packed(samples, spec)
    return PackedStore.build(samples).assemble(np.arange(len(samples)), spec)


@pytest.mark.parametrize("full_edges", [False, True])
@pytest.mark.parametrize("collator", ["collate", "collate_packed", "store"])
def test_collators_promise_sorted_triplets(collator, full_edges):
    samples = _samples()
    spec = (
        _full_edges_spec(samples)
        if full_edges
        else PadSpec.for_samples(samples, with_triplets=True)
    )
    batch = _collated(collator, samples, spec)
    e_real = int(np.asarray(batch.edge_mask).sum())
    assert (e_real == batch.num_edges) is full_edges
    _check_sorted(batch, e_real)
    want = collate(samples, spec, as_numpy=True)
    for name in ("t_kj", "t_ji", "triplet_mask"):
        assert np.array_equal(getattr(batch, name), getattr(want, name))


@pytest.mark.parametrize("fixed_pad", [True, False])
def test_loader_batches_promise_sorted_triplets(fixed_pad):
    """The worst-case pad and the triplet ladder (``fixed_pad=False``),
    with receiver-sorted edges as ``run_training`` asks on one chip."""
    from hydragnn_tpu.data.loader import GraphLoader

    loader = GraphLoader(
        _samples(12, seed=3), 4, shuffle=True, seed=5, with_triplets=True,
        fixed_pad=fixed_pad, sort_receivers=True,
    )
    batches = list(loader)
    assert len(batches) == 3
    for batch in batches:
        _check_sorted(batch)
        assert batch.receivers_sorted is True


@pytest.mark.parametrize("collator", ["collate", "collate_packed", "store"])
def test_batches_without_triplets_promise_nothing(collator):
    from hydragnn_tpu.data.loader import GraphLoader

    samples = _samples()
    batch = _collated(collator, samples, PadSpec.for_samples(samples))
    assert batch.t_ji is None and batch.triplets_sorted is False
    assert next(iter(GraphLoader(samples, 3))).triplets_sorted is False


def test_stacked_groups_keep_the_promise_and_refuse_a_mix():
    from hydragnn_tpu.data.graph import stack_batches
    from hydragnn_tpu.data.pipeline import _stack_group
    from hydragnn_tpu.parallel import mesh

    samples = _samples()
    spec = PadSpec.for_samples(samples, with_triplets=True)
    group = [collate(samples, spec, as_numpy=True) for _ in range(3)]
    for stacked in (
        stack_batches(group).batch, _stack_group(group, {}).batch,
        mesh.stack_batches(group),
    ):
        assert stacked.triplets_sorted is True
        one = jax.tree_util.tree_map(lambda x: x[2], stacked)
        _check_sorted(one)
        assert np.array_equal(one.t_ji, group[2].t_ji)
    mixed = group[:2] + [group[2].replace(triplets_sorted=False)]
    with pytest.raises(ValueError, match="triplets_sorted"):
        _stack_group(mixed, {})
    for stack in (stack_batches, mesh.stack_batches):
        with pytest.raises(ValueError):
            stack(mixed)


@pytest.fixture(scope="module")
def block():
    """A tiny ``InteractionPPBlock`` with its operands on a collated
    batch that promises sorted triplets, and the twin that does not."""
    from hydragnn_tpu.models.dimenet import InteractionPPBlock

    samples = _samples(5, seed=4)
    promised = collate(
        samples, PadSpec.for_samples(samples, with_triplets=True)
    )
    twin = promised.replace(triplets_sorted=False)
    rng = np.random.default_rng(0)
    E, T = promised.num_edges, promised.t_ji.shape[0]
    m = jnp.asarray(rng.normal(size=(E, 8)), jnp.float32)
    rbf = jnp.asarray(rng.normal(size=(E, 3)), jnp.float32)
    sbf = jnp.asarray(rng.normal(size=(T, 6)), jnp.float32)
    mod = InteractionPPBlock(
        hidden_dim=8, int_emb_size=4, basis_emb_size=2, num_before_skip=1,
        num_after_skip=1,
    )
    params = mod.init(jax.random.PRNGKey(0), m, rbf, sbf, promised)

    def apply(params, m, batch):
        return mod.apply(params, m, rbf, sbf, batch)

    return apply, params, m, promised, twin


@pytest.mark.parametrize("promise", [True, False])
def test_lowered_block_sorts_exactly_when_promised(block, promise):
    """Forward: the triplet reduce is the block's one scatter. Backward:
    the ``x_kj[t_kj]`` gather's transpose is a second, over unsorted
    ``t_kj``, which never carries the flag."""
    apply, params, m, promised, twin = block
    batch = promised if promise else twin
    fwd = jax.jit(apply).lower(params, m, batch).as_text()
    assert fwd.count('"stablehlo.scatter"') == 1
    assert len(re.findall(SORTED_SCATTER, fwd)) == int(promise)
    loss = lambda p, m, b: jnp.sum(jnp.sin(apply(p, m, b)))  # noqa: E731
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, m, batch
    ).as_text()
    assert bwd.count('"stablehlo.scatter"') == 2
    assert len(re.findall(SORTED_SCATTER, bwd)) == int(promise)


def test_block_is_its_unsorted_twin_bit_for_bit(block):
    apply, params, m, promised, twin = block
    before = dict(seg._DISPATCH)
    got = apply(params, m, promised)  # eager: each call traces anew
    want = apply(params, m, twin)
    assert seg._DISPATCH["triplet_sorted_scatter"] == (
        before["triplet_sorted_scatter"] + 1
    )
    assert seg._DISPATCH["triplet_scatter"] == before["triplet_scatter"] + 1
    assert np.array_equal(got, want)
    loss = lambda b: lambda p, m: jnp.sum(jnp.sin(apply(p, m, b)))  # noqa: E731
    g1 = jax.jit(jax.grad(loss(promised), argnums=(0, 1)))(params, m)
    g0 = jax.jit(jax.grad(loss(twin), argnums=(0, 1)))(params, m)
    leaves1, leaves0 = jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g0)
    assert len(leaves1) == len(leaves0) > 2
    assert all(np.array_equal(a, b) for a, b in zip(leaves1, leaves0))


def test_sum_over_triplets_is_the_masked_segment_sum():
    samples = _samples(4, seed=6)
    batch = collate(samples, PadSpec.for_samples(samples, with_triplets=True))
    trip = jnp.asarray(
        np.random.default_rng(1).normal(size=(batch.t_ji.shape[0], 5)),
        jnp.float32,
    )
    got = seg.sum_over_triplets(trip, batch)
    masked = jnp.where(batch.triplet_mask[:, None], trip, 0)
    want = jax.ops.segment_sum(masked, batch.t_ji, num_segments=batch.num_edges)
    assert got.shape == (batch.num_edges, 5)
    assert np.array_equal(got, want)

    class Plain:  # a batch-like that says nothing promises nothing
        t_ji, triplet_mask, num_edges = batch.t_ji, batch.triplet_mask, batch.num_edges

    text = jax.jit(lambda x: seg.sum_over_triplets(x, Plain)).lower(trip).as_text()
    assert "indices_are_sorted = true" not in text


@pytest.fixture(scope="module")
def dimenet():
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.models.create import create_model_config, init_params
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.state import create_train_state

    samples = _mols(8, seed=4)
    config = _config()
    config["NeuralNetwork"]["Architecture"].update(
        mpnn_type="DimeNet", num_radial=3, num_spherical=2,
        envelope_exponent=5, int_emb_size=4, basis_emb_size=2,
        out_emb_size=8,
    )
    cfgd = update_config(config, samples)
    model, cfg = create_model_config(cfgd)
    batch = next(iter(
        GraphLoader(samples, 4, with_triplets=True, sort_receivers=True)
    ))
    params, bs = init_params(model, batch)
    tx = select_optimizer(cfgd["NeuralNetwork"]["Training"])
    return model, cfg, tx, create_train_state(params, tx, bs), batch


@pytest.mark.parametrize(
    "program", ["train_step", "eval_step", "train_superstep"]
)
def test_traced_dimenet_program_writes_its_dispatch_row(
    tmp_path, dimenet, program
):
    """One triplet reduce and one output-block receiver sum a block, each
    on the sorted scatter: the row the DimeNet cell's programs write."""
    import json

    from hydragnn_tpu.train import loop
    from hydragnn_tpu.utils import telemetry

    model, cfg, tx, state, batch = dimenet
    assert batch.triplets_sorted is True and batch.receivers_sorted is True
    path = str(tmp_path / "t.jsonl")
    stream = telemetry.TelemetryStream(path)
    telemetry.install(stream)
    try:
        if program == "train_step":
            fn = loop.make_train_step(model, tx, cfg, donate=False)
            lowered = fn.lower(state, batch)
        elif program == "eval_step":
            lowered = loop.make_eval_step(model, cfg).lower(state, batch)
        else:
            fn = loop.make_superstep_fn(
                model, tx, cfg, train=True, donate=False
            )
            stacked = jax.tree_util.tree_map(
                lambda x: jnp.stack([x, x]), batch
            )
            acc = (jnp.zeros(()), jnp.zeros((1,)), jnp.zeros(()))
            lowered = fn.lower(state, acc, stacked)
        text = lowered.as_text()
    finally:
        telemetry.close_run(stream)
    (row,) = [
        r for r in map(json.loads, open(path))
        if r.get("phase") == "segment_dispatch"
    ]
    blocks = cfg.num_conv_layers
    assert row["program"] == f"jit_{program}"
    assert {k: row[k] for k in seg._DISPATCH} == {
        "sorted_scatter": blocks, "scatter": 0,
        "triplet_sorted_scatter": blocks, "triplet_scatter": 0,
    }
    assert len(re.findall(SORTED_SCATTER, text)) == 2 * blocks
