"""The receiver aggregation on XLA's sorted scatter (ISSUE 34,
ops/segment.py) and the promise batch forming carries for it: collation
keeps the edges in receiver order and the batch says so
(data/graph.py, data/loader.py, data/pipeline.py).

The PR was asked to take the scatter-add out of the aggregation by summing
each node's run of edges with gathers; on the chip every sum in another
order than the scatter's failed the benchmark's ``correct`` (PERF.md
section 6, PR 34), so what shipped keeps the scatter and its order and
tells XLA what collation knows. All on the CPU at toy sizes: values and
gradients, bit for bit against the plain scatter; the collators, bit for
bit against each other; no shape that depends on seed or shuffle.
"""

import dataclasses

import numpy as np
import pytest

import tests._cpu  # noqa: F401

import jax
import jax.numpy as jnp

from hydragnn_tpu.data.graph import (
    GraphSample,
    PadSpec,
    collate,
    sort_edges_by_receiver,
)
from hydragnn_tpu.ops import segment as seg


def _mols(n_graphs, seed=0, lo=3, hi=9, shuffled=False):
    """Small directed graphs with an isolated last atom each (an empty
    node); receiver-sorted unless ``shuffled``."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_graphs):
        n = int(rng.integers(lo, hi))
        pairs = [
            (i, j)
            for j in range(n - 1)
            for i in range(n - 1)
            if i != j and rng.random() < 0.7
        ]
        ei = np.asarray(pairs, np.int64).T.reshape(2, -1)
        if shuffled:
            ei = ei[:, rng.permutation(ei.shape[1])]
        out.append(
            GraphSample(
                x=rng.normal(size=(n, 2)).astype(np.float32),
                pos=rng.normal(size=(n, 3)).astype(np.float32),
                edge_index=ei,
                edge_attr=rng.normal(size=(ei.shape[1], 2)).astype(
                    np.float32
                ),
                y_graph=np.array([rng.normal()], np.float32),
            )
        )
    return out


def _spec(samples, sorted_receivers=True):
    return dataclasses.replace(
        PadSpec.for_samples(samples), sorted_receivers=sorted_receivers
    )


@pytest.fixture(scope="module")
def batches():
    """One set of receiver-sorted graphs collated without and with the
    promise: the same arrays, one static field apart."""
    samples = _mols(6, seed=1)
    return (
        samples,
        collate(samples, _spec(samples, False)),
        collate(samples, _spec(samples)),
    )


def _reference(h, w, batch):
    """The block on the parent's ops: gather, multiply, plain scatter."""
    msg = h[batch.senders] * w
    msg = jnp.where(batch.edge_mask[:, None], msg, 0)
    return jax.ops.segment_sum(
        msg, batch.receivers, num_segments=batch.num_nodes
    )


def _block(h, w, batch):
    return seg.aggregate_receivers_product(h[batch.senders], w, batch)


def _operands(batch, f, seed=0):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(batch.num_nodes, f)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(batch.num_edges, f)), jnp.float32)
    return h, w


def test_fixture_holds_the_hard_cases(batches):
    """Padding edges on the padding node, after every real edge; empty
    nodes; the promise on one batch and not on the other."""
    samples, plain, promised = batches
    n_real = sum(s.num_nodes for s in samples)
    e_real = sum(s.num_edges for s in samples)
    assert plain.receivers_sorted is False and promised.receivers_sorted is True
    for batch in (plain, promised):
        mask = np.asarray(batch.edge_mask)
        assert 0 < e_real < batch.num_edges and not mask[e_real:].any()
        rcv = np.asarray(batch.receivers)
        assert np.all(rcv[~mask] == n_real)
        assert np.all(np.diff(rcv) >= 0)  # padding edges included
        degree = np.bincount(rcv[mask], minlength=n_real)
        assert (degree[:n_real] == 0).any()
    assert all(
        np.array_equal(a, b)
        for a, b in zip(
            jax.tree_util.tree_leaves(plain),
            jax.tree_util.tree_leaves(promised),
        )
    )


@pytest.mark.parametrize("f", [3, 128, 256])
def test_sorted_scatter_is_the_scatter_bit_for_bit(batches, f):
    """Value and gradient of the block with the promise against the
    parent's ops, and the lowered scatter carries the flag."""
    _, _, promised = batches
    h, w = _operands(promised, f)
    before = dict(seg._DISPATCH)
    got = _block(h, w, promised)
    assert seg._DISPATCH["sorted_scatter"] == before["sorted_scatter"] + 1
    assert seg._DISPATCH["scatter"] == before["scatter"]
    assert np.array_equal(got, _reference(h, w, promised))
    loss = lambda fn: lambda h, w: jnp.sum(jnp.sin(fn(h, w, promised)))  # noqa: E731
    got = jax.grad(loss(_block), argnums=(0, 1))(h, w)
    want = jax.grad(loss(_reference), argnums=(0, 1))(h, w)
    assert all(np.array_equal(g, r) for g, r in zip(got, want))
    text = jax.jit(_block).lower(h, w, promised).as_text()
    assert "indices_are_sorted = true" in text


@pytest.mark.parametrize(
    "site", ["sum", "product", "pipeline", "mean", "multi"]
)
def test_every_receiver_aggregation_takes_the_promise(batches, site):
    """``aggregate_receivers*`` and what is built on them: with the
    promise the scatter is the sorted one, without it the plain one, and
    the values are the same bits."""
    _, plain, promised = batches
    h, w = _operands(plain, 8)
    msg = h[plain.senders]
    weight = jnp.asarray(np.random.default_rng(2).normal(size=(8, 4)), jnp.float32)
    call = {
        "sum": lambda b: seg.aggregate_receivers(msg, b),
        "product": lambda b: seg.aggregate_receivers_product(msg, w, b),
        "pipeline": lambda b: seg.aggregate_receivers_pipeline(
            msg, w, b, weight=weight, mean=True
        ),
        "mean": lambda b: seg.aggregate_receivers_mean(msg, b),
        "multi": lambda b: jnp.concatenate(seg.segment_multi_aggregate(msg, b), -1),
    }[site]
    before = dict(seg._DISPATCH)
    want = call(plain)
    assert seg._DISPATCH == {**before, "scatter": before["scatter"] + 1}
    got = call(promised)
    assert seg._DISPATCH["sorted_scatter"] == before["sorted_scatter"] + 1
    assert np.array_equal(got, want)


def test_gradient_of_gradient_with_the_promise(batches):
    """A toy energy -> forces -> loss through the block: what
    ``train/mlip.energy_and_forces`` asks of every op (ROADMAP R3)."""
    _, plain, promised = batches
    h, w = _operands(promised, 16)

    def force_loss(batch):
        def energy(h, w):
            return jnp.sum(jnp.tanh(_block(jnp.sin(h), w, batch)))

        def loss(h, w):
            return jnp.sum(jax.grad(energy)(h, w) ** 2)

        return jax.grad(loss, argnums=(0, 1))

    got = jax.jit(force_loss(promised))(h, w)
    want = jax.jit(force_loss(plain))(h, w)
    assert all(np.array_equal(g, r) for g, r in zip(got, want))


@pytest.mark.parametrize("shuffled", [False, True])
@pytest.mark.parametrize("collator", ["collate_packed", "store"])
def test_collators_sort_bit_identically(collator, shuffled):
    from hydragnn_tpu.data.pipeline import PackedStore, collate_packed

    samples = _mols(7, seed=4, shuffled=shuffled)
    spec = _spec(samples)
    want = collate(samples, spec, as_numpy=True)
    if collator == "store":
        got = PackedStore.build(samples).assemble(
            np.arange(len(samples)), spec
        )
    else:
        got = collate_packed(samples, spec)
    assert got.receivers_sorted is True and want.receivers_sorted is True
    for name in ("senders", "receivers", "edge_mask", "edge_attr"):
        a, b = getattr(got, name), getattr(want, name)
        assert a.dtype == b.dtype and np.array_equal(a, b), name
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(
        want
    )
    # without the flag on the spec neither sorts nor promises
    plain = collate(samples, _spec(samples, False), as_numpy=True)
    assert plain.receivers_sorted is False
    e_real = int(plain.edge_mask.sum())
    assert shuffled == bool(np.any(np.diff(plain.receivers[:e_real]) < 0))


def test_unsorted_receivers_are_sorted_sorted_ones_are_left(monkeypatch):
    samples = _mols(3, seed=5, shuffled=True)
    plain = collate(samples, _spec(samples, False), as_numpy=True)
    ordered = collate(samples, _spec(samples), as_numpy=True)
    e_real = int(plain.edge_mask.sum())
    assert np.any(np.diff(plain.receivers[:e_real]) < 0)
    assert np.all(np.diff(ordered.receivers) >= 0)
    # the same edges, each with its payload row
    key = lambda b: sorted(  # noqa: E731
        zip(
            b.senders[:e_real].tolist(),
            b.receivers[:e_real].tolist(),
            map(tuple, b.edge_attr[:e_real].tolist()),
        )
    )
    assert key(plain) == key(ordered)
    # ... and the same sum at every node, in whatever order
    h, w = _operands(plain, 4)
    np.testing.assert_allclose(
        _block(h, jnp.ones_like(w), jax.tree_util.tree_map(jnp.asarray, ordered)),
        _reference(h, jnp.ones_like(w), plain), rtol=1e-5, atol=1e-5,
    )
    # sorted receivers: nothing moves, and nothing is sorted to find out
    arrays = [
        np.array(a, copy=True)
        for a in (ordered.senders, ordered.receivers, ordered.edge_mask)
    ]
    payload = {"edge_attr": np.array(ordered.edge_attr, copy=True), "rel_pe": None}

    def no_sort(*args, **kwargs):
        raise AssertionError("argsort on receivers that are sorted")

    monkeypatch.setattr(np, "argsort", no_sort)
    assert sort_edges_by_receiver(*arrays, payload, e_real) is False
    assert np.array_equal(arrays[0], ordered.senders)
    assert np.array_equal(payload["edge_attr"], ordered.edge_attr)


def test_a_segment_plan_is_a_promise_too():
    """``apply_segment_plan`` has always sorted: its batches say so."""
    samples = _mols(3, seed=6, shuffled=True)
    batch = collate(
        samples, _spec(samples, False), with_segment_plan=True, as_numpy=True
    )
    assert batch.receivers_sorted is True
    assert np.all(np.diff(batch.receivers) >= 0)


@pytest.mark.parametrize("packing", [False, True])
def test_the_promise_changes_no_shape_and_needs_no_seed(packing):
    """Two seeds, two epochs, with and without ``sort_receivers``: the
    same padded shapes, one trace of a jitted step a spec, and every
    batch of the sorting loader keeps its promise."""
    from hydragnn_tpu.data.loader import GraphLoader

    samples = _mols(24, seed=7, shuffled=True)
    kw = {"packing": True} if packing else {"fixed_pad": True}
    traced = []

    @jax.jit
    def step(batch):
        traced.append(batch.receivers_sorted)
        return jnp.sum(seg.aggregate_receivers(batch.x[batch.senders], batch))

    shapes = {False: set(), True: set()}
    for sort in (False, True):
        for seed in (1, 2):
            loader = GraphLoader(
                samples, 4, shuffle=True, seed=seed, sort_receivers=sort, **kw
            )
            for epoch in (0, 1):
                loader.set_epoch(epoch)
                for _, spec in loader.epoch_plan(epoch):
                    assert spec.sorted_receivers is sort
                    shapes[sort].add(
                        (spec.num_nodes, spec.num_edges, spec.num_graphs)
                    )
                for batch in loader:
                    assert batch.receivers_sorted is sort
                    if sort:
                        assert np.all(np.diff(np.asarray(batch.receivers)) >= 0)
                    step(batch)
    assert shapes[False] == shapes[True]
    assert len(traced) == 2 * len(shapes[True])
    assert sorted(set(traced)) == [False, True]


def test_superstep_stack_keeps_the_promise():
    from hydragnn_tpu.data.graph import stack_batches
    from hydragnn_tpu.data.pipeline import _stack_group

    samples = _mols(4, seed=8)
    spec = _spec(samples)
    group = [collate(samples, spec, as_numpy=True) for _ in range(3)]
    for macro in (stack_batches(group), _stack_group(group, {})):
        assert macro.batch.receivers_sorted is True
        one = jax.tree_util.tree_map(lambda x: x[1], macro.batch)
        assert one.receivers_sorted is True
        assert np.array_equal(one.receivers, group[1].receivers)
    mixed = group[:2] + [collate(samples, _spec(samples, False), as_numpy=True)]
    with pytest.raises(ValueError, match="receivers_sorted"):
        _stack_group(mixed, {})


def test_energy_and_forces_on_a_batch_with_the_promise():
    """``train/mlip.energy_and_forces`` differentiates through the sorted
    scatter, and training then differentiates the forces: the same bits
    as through the plain one."""
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.train.mlip import energy_and_forces, energy_force_loss
    from tests.test_interatomic_potential import (
        _mlip_config,
        mock_molecular_samples,
    )

    samples = mock_molecular_samples()
    for s in samples:  # receiver order, so both batches hold the same arrays
        s.edge_index = s.edge_index[:, np.argsort(s.edge_index[1], kind="stable")]
    cfg = _mlip_config("node")
    model = create_model(cfg)
    plain = collate(samples, _spec(samples, False))
    promised = collate(samples, _spec(samples))
    params, bs = init_params(model, plain)
    variables = {"params": params, "batch_stats": bs}
    before = dict(seg._DISPATCH)
    e1, f1, _ = energy_and_forces(model, variables, promised, cfg)
    assert seg._DISPATCH["sorted_scatter"] > before["sorted_scatter"]
    assert seg._DISPATCH["scatter"] == before["scatter"]
    e0, f0, _ = energy_and_forces(model, variables, plain, cfg)
    assert np.array_equal(e1, e0) and np.array_equal(f1, f0)
    loss = lambda b: lambda p: energy_force_loss(  # noqa: E731
        model, {"params": p, "batch_stats": bs}, b, cfg
    )[0]
    g1 = jax.jit(jax.grad(loss(promised)))(params)
    g0 = jax.jit(jax.grad(loss(plain)))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g0)):
        assert np.array_equal(a, b)
