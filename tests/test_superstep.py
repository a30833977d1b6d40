"""Superstep executor (ISSUE 4): K train steps per device dispatch via
``lax.scan`` over same-spec stacked macro-batches.

The load-bearing invariant is BITWISE identity: a K-group dispatch
(train/loop.make_superstep_fn) must reproduce K sequential single-step
dispatches exactly — loss sums, per-task sums, params — with packing on
and off, across serial and pipeline delivery, through run tails shorter
than K, and at K=1 (where nothing is wrapped at all).
"""

import dataclasses

import numpy as np
import pytest

import tests._cpu  # noqa: F401

import jax
import jax.numpy as jnp

from hydragnn_tpu.data.graph import GraphSample, MacroBatch, PadSpec
from hydragnn_tpu.ops.neighbors import radius_graph


def _mols(n, lo=5, hi=11, seed=0):
    r = np.random.default_rng(seed)
    out = []
    for i in range(n):
        k = int(r.integers(lo, hi))
        pos = r.uniform(0, 1.8 * k ** (1 / 3), (k, 3)).astype(np.float32)
        out.append(
            GraphSample(
                x=r.integers(0, 3, (k, 1)).astype(np.float32),
                pos=pos,
                edge_index=radius_graph(pos, 2.2, max_neighbours=16),
                y_graph=np.array([r.normal()], np.float32),
            )
        )
    return out


def _config(steps="auto", workers=0, num_epoch=2, batch_size=4):
    return {
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "SchNet",
                "radius": 2.2,
                "max_neighbours": 16,
                "num_gaussians": 8,
                "num_filters": 8,
                "hidden_dim": 8,
                "num_conv_layers": 2,
                "output_heads": {
                    "graph": {
                        "num_sharedlayers": 1,
                        "dim_sharedlayers": 8,
                        "num_headlayers": 1,
                        "dim_headlayers": [8],
                    }
                },
                "task_weights": [1.0],
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_names": ["e"],
                "output_index": [0],
                "type": ["graph"],
                "output_dim": [1],
            },
            "Training": {
                "batch_size": batch_size,
                "num_epoch": num_epoch,
                "Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
                "Parallelism": {
                    "scheme": "single",
                    "pipeline": {"workers": workers},
                    "superstep": {"steps": steps},
                },
            },
        }
    }


@pytest.fixture(scope="module")
def tiny_model():
    """One compiled model family shared by every step-parity test."""
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.models.create import create_model_config, init_params
    from hydragnn_tpu.train.optimizer import select_optimizer

    samples = _mols(64, seed=3)
    cfgd = update_config(_config(), samples)
    model, cfg = create_model_config(cfgd)
    batch0 = next(iter(GraphLoader(samples, 4)))
    params, bs = init_params(model, batch0)
    tx = select_optimizer(cfgd["NeuralNetwork"]["Training"])
    # HOST copies: donated steps delete their input buffers, so every
    # test must start from an independent device copy (_fresh_state).
    params = jax.tree_util.tree_map(
        lambda x: np.array(x, copy=True), jax.device_get(params)
    )
    bs = jax.tree_util.tree_map(
        lambda x: np.array(x, copy=True), jax.device_get(bs)
    )
    return samples, model, cfg, tx, params, bs


def _fresh_state(tiny_model):
    from hydragnn_tpu.train.state import create_train_state

    _, _, _, tx, params, bs = tiny_model
    # jnp.array COPIES: donation must never reach the fixture's host
    # buffers (XLA:CPU device_put would zero-copy them).
    dev_params = jax.tree_util.tree_map(jnp.array, params)
    dev_bs = jax.tree_util.tree_map(jnp.array, bs)
    return create_train_state(dev_params, tx, dev_bs)


def _leaves_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(u), np.asarray(v))
        for u, v in zip(la, lb)
    )


# ----------------------------------------------------------------------
# Grouping arithmetic (pure functions of the plan)
# ----------------------------------------------------------------------


def _spec(n, e, g):
    return PadSpec(num_nodes=n, num_edges=e, num_graphs=g)


def test_superstep_groups_runs_and_tails():
    from hydragnn_tpu.data.padschedule import superstep_groups

    a, b = _spec(16, 32, 5), _spec(24, 48, 5)
    plan = [(i, a) for i in range(10)] + [(i, b) for i in range(3)]
    groups = superstep_groups(plan, 4)
    # 10-run of a: two full 4-groups + 2 singletons; 3-run of b: singles
    assert [len(g) for g in groups] == [4, 4, 1, 1, 1, 1, 1]
    # order and content preserved exactly
    assert [e for g in groups for e in g] == plan
    # k=1: all singletons, plan order untouched
    assert [g[0] for g in superstep_groups(plan, 1)] == plan
    # deterministic (pure)
    assert superstep_groups(plan, 4) == groups


def test_superstep_groups_interleaved_specs_never_group_across_runs():
    from hydragnn_tpu.data.padschedule import superstep_groups

    a, b = _spec(16, 32, 5), _spec(24, 48, 5)
    plan = [(0, a), (1, b), (2, a), (3, b)]
    groups = superstep_groups(plan, 2)
    assert [len(g) for g in groups] == [1, 1, 1, 1]


def test_superstep_groups_none_spec_stays_single():
    from hydragnn_tpu.data.padschedule import superstep_groups

    a = _spec(16, 32, 5)
    plan = [(0, a), (1, None), (2, a), (3, a)]
    groups = superstep_groups(plan, 2)
    assert [len(g) for g in groups] == [1, 1, 2]
    assert groups[1][0][1] is None


def test_packed_epoch_plan_folds_4x_at_k8():
    """Packing collapses an epoch of varied molecules to a couple of
    budget shapes in spec-major order, so K=8 cuts the Python
    dispatches of the epoch at least 4x; the bucket ladder over the
    same shuffle has no runs to fold."""
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.data.padschedule import superstep_groups

    samples = _mols(512, 9, 30, seed=0)
    plan = list(
        GraphLoader(
            samples, 16, shuffle=True, seed=0, packing=True
        ).epoch_plan(0)
    )
    assert len(plan) >= 4 * len(superstep_groups(plan, 8))
    ladder = list(
        GraphLoader(
            samples, 16, shuffle=True, seed=0, fixed_pad="auto"
        ).epoch_plan(0)
    )
    assert len(ladder) < 2 * len(superstep_groups(ladder, 8))


def test_auto_superstep_k_floor_cap_and_fragmentation():
    from hydragnn_tpu.data.padschedule import (
        auto_superstep_k,
        estimate_spec_bytes,
        superstep_groups,  # noqa: F401  (same grouping the auto sims)
    )

    a = _spec(64, 128, 9)
    long_run = [(i, a) for i in range(128)]
    # long uniform run: largest candidate wins
    assert auto_superstep_k(long_run) == 32
    # short plans never engage (dispatch amortization is a long-epoch
    # optimization; unit-test-sized runs keep today's exact shape)
    assert auto_superstep_k(long_run[:32]) == 1
    assert auto_superstep_k([], ) == 1
    # memory cap: K * est bytes must fit
    cap = estimate_spec_bytes(a) * 8
    assert auto_superstep_k(long_run, max_host_bytes=cap) == 8
    # fragmentation: alternating specs -> no runs -> 1
    b = _spec(80, 160, 9)
    frag = [(i, a if i % 2 else b) for i in range(128)]
    assert auto_superstep_k(frag) == 1


def test_resolve_superstep_k_scheme_and_pinning(tiny_model):
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.parallel.runtime import (
        ParallelPlan,
        resolve_superstep_k,
    )

    samples, *_ = tiny_model
    loader = GraphLoader(samples, 4, fixed_pad=True)
    # explicit pin wins whatever the plan length
    plan = ParallelPlan(scheme="single", superstep_steps=8)
    assert resolve_superstep_k(plan, loader) == 8
    # auto on a short (16-step) plan: floor keeps K=1
    plan = ParallelPlan(scheme="single", superstep_steps="auto")
    assert resolve_superstep_k(plan, loader) == 1
    # multibranch — and a degenerate meshless dp plan — always 1
    # (dp WITH a mesh now resolves K at step level:
    # tests/test_dp_fastpath.py::test_resolve_superstep_k_dp)
    plan = ParallelPlan(scheme="dp", superstep_steps=8)
    assert resolve_superstep_k(plan, loader) == 1
    plan = ParallelPlan(scheme="multibranch", superstep_steps=8)
    assert resolve_superstep_k(plan, loader) == 1
    # the batches-per-epoch measurement cap forces K=1 (a macro runs K
    # steps atomically and would overshoot the cap by up to K-1)
    plan = ParallelPlan(scheme="single", superstep_steps=8)
    monkey = pytest.MonkeyPatch()
    try:
        monkey.setenv("HYDRAGNN_TPU_MAX_NUM_BATCH", "10")
        assert resolve_superstep_k(plan, loader) == 1
    finally:
        monkey.undo()


def test_estimate_spec_bytes_counts_triplets():
    from hydragnn_tpu.data.padschedule import estimate_spec_bytes

    base = PadSpec(num_nodes=64, num_edges=256, num_graphs=9)
    trip = PadSpec(
        num_nodes=64, num_edges=256, num_graphs=9, num_triplets=4096
    )
    # DimeNet-class padded triplet counts dwarf E: the host-RAM cap
    # must see them, or auto-K blows max_host_bytes on exactly the
    # densest batches.
    assert estimate_spec_bytes(trip) > 2 * estimate_spec_bytes(base)


def test_config_superstep_grammar():
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.parallel.runtime import _superstep_from_config

    assert _superstep_from_config({})["superstep_steps"] == "auto"
    assert (
        _superstep_from_config({"superstep": {"steps": 8}})[
            "superstep_steps"
        ]
        == 8
    )
    with pytest.raises(ValueError, match="superstep.steps"):
        _superstep_from_config({"superstep": {"steps": "fast"}})
    with pytest.raises(ValueError, match="boolean"):
        _superstep_from_config({"superstep": {"steps": True}})
    # update_config rejects unknown keys in the block eagerly
    cfg = _config()
    cfg["NeuralNetwork"]["Training"]["Parallelism"]["superstep"] = {
        "step": 8
    }
    with pytest.raises(ValueError, match="unknown keys"):
        update_config(cfg, _mols(2))


# ----------------------------------------------------------------------
# Bitwise parity: scan vs sequential steps
# ----------------------------------------------------------------------


@pytest.mark.parametrize("packing", [False, True])
def test_scan_bitwise_vs_sequential_steps(tiny_model, packing):
    """K scanned steps == K sequential jitted train_step calls, bit for
    bit (loss/task sums AND final params), with the packed former on
    and off."""
    from hydragnn_tpu.data.graph import stack_batches
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.train.loop import make_superstep_fn, make_train_step

    samples, model, cfg, tx, params, bs = tiny_model
    loader = GraphLoader(
        samples, 4, shuffle=True, seed=7,
        **({"packing": True} if packing else {"fixed_pad": True}),
    )
    batches = [
        jax.tree_util.tree_map(np.asarray, b) for b in loader
    ]
    # packing may emit a tail bin on a different budget: keep the
    # leading same-spec run only (that is all a macro group ever holds)
    K = 1
    while (
        K < len(batches)
        and batches[K].num_nodes == batches[0].num_nodes
        and batches[K].num_edges == batches[0].num_edges
        and batches[K].num_graphs == batches[0].num_graphs
    ):
        K += 1
    K = min(K, 6)
    assert K >= 2, "need a same-spec run to stack"

    step = make_train_step(model, tx, cfg, donate=False)
    state = _fresh_state(tiny_model)
    lsum = tsum = ngsum = None
    for b in batches[:K]:
        ng = jnp.sum(b.graph_mask).astype(jnp.float32)
        state, loss, tasks = step(state, b)
        if lsum is None:
            lsum, tsum, ngsum = loss * ng, tasks * ng, ng
        else:
            lsum, tsum, ngsum = lsum + loss * ng, tsum + tasks * ng, ngsum + ng

    sstep = make_superstep_fn(model, tx, cfg, train=True, donate=False)
    macro = stack_batches(batches[:K])
    assert macro.k == K
    state2 = _fresh_state(tiny_model)
    zero = jnp.zeros((), jnp.float32)
    state2, (l2, t2, g2) = sstep(
        state2,
        (zero, jnp.zeros((1,), jnp.float32), zero),
        jax.device_put(macro.batch),
    )
    assert float(lsum) == float(l2)
    assert np.array_equal(np.asarray(tsum), np.asarray(t2))
    assert float(ngsum) == float(g2)
    assert _leaves_equal(
        jax.device_get(state.params), jax.device_get(state2.params)
    )
    assert int(state2.step) == K


def test_eval_superstep_bitwise(tiny_model):
    from hydragnn_tpu.data.graph import stack_batches
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.train.loop import make_eval_step, make_superstep_fn

    samples, model, cfg, tx, params, bs = tiny_model
    batches = [
        jax.tree_util.tree_map(np.asarray, b)
        for b in GraphLoader(samples, 4, fixed_pad=True)
    ][:4]
    state = _fresh_state(tiny_model)
    estep = make_eval_step(model, cfg)
    lsum = tsum = ngsum = None
    for b in batches:
        ng = jnp.sum(b.graph_mask).astype(jnp.float32)
        loss, tasks = estep(state, b)
        if lsum is None:
            lsum, tsum, ngsum = loss * ng, tasks * ng, ng
        else:
            lsum, tsum, ngsum = lsum + loss * ng, tsum + tasks * ng, ngsum + ng
    sstep = make_superstep_fn(model, tx, cfg, train=False, donate=False)
    zero = jnp.zeros((), jnp.float32)
    l2, t2, g2 = sstep(
        state,
        (zero, jnp.zeros((1,), jnp.float32), zero),
        jax.device_put(stack_batches(batches).batch),
    )
    assert float(lsum) == float(l2)
    assert np.array_equal(np.asarray(tsum), np.asarray(t2))
    assert float(ngsum) == float(g2)


def test_donation_safety_across_repeated_dispatches(tiny_model):
    """The donated form (state AND accumulator through the carry) must
    be safe to call in a loop: every buffer the caller rebinds, none it
    reuses. Two epochs of grouped dispatches, then the donated result
    must still match the non-donated sequential loop."""
    from hydragnn_tpu.data.loader import GraphLoader, SuperstepLoader
    from hydragnn_tpu.train.loop import (
        _run_epoch,
        make_superstep_fn,
        make_train_step,
        superstep_task_count,
    )

    samples, model, cfg, tx, params, bs = tiny_model
    mk = lambda: GraphLoader(  # noqa: E731
        samples, 4, shuffle=True, seed=5, fixed_pad=True
    )
    step = make_train_step(model, tx, cfg)  # donated, like production
    sstep = make_superstep_fn(model, tx, cfg, train=True)  # donated
    n_tasks = superstep_task_count(cfg)

    state_a = _fresh_state(tiny_model)
    base = mk()
    for ep in range(2):
        base.set_epoch(ep)
        state_a, loss_a, tasks_a = _run_epoch(
            step, state_a, base, train=True
        )

    state_b = _fresh_state(tiny_model)
    wrapped = SuperstepLoader(mk(), 4)
    for ep in range(2):
        wrapped.set_epoch(ep)
        state_b, loss_b, tasks_b = _run_epoch(
            step, state_b, wrapped, train=True,
            superstep_fn=sstep, n_tasks=n_tasks,
        )
    assert loss_a == loss_b
    assert np.array_equal(tasks_a, tasks_b)
    assert _leaves_equal(
        jax.device_get(state_a.params), jax.device_get(state_b.params)
    )


def test_tail_shorter_than_k_falls_back_to_singles(tiny_model):
    """A 16-step epoch at K=6 -> two macro groups + four singles; the
    mixed delivery must still reproduce the per-step loop bitwise."""
    from hydragnn_tpu.data.loader import GraphLoader, SuperstepLoader
    from hydragnn_tpu.train.loop import (
        _run_epoch,
        make_superstep_fn,
        make_train_step,
        superstep_task_count,
    )

    samples, model, cfg, tx, params, bs = tiny_model
    mk = lambda: GraphLoader(samples, 4, fixed_pad=True)  # noqa: E731
    wrapped = SuperstepLoader(mk(), 6)
    items = list(wrapped)
    ks = [it.k if isinstance(it, MacroBatch) else 1 for it in items]
    assert ks == [6, 6, 1, 1, 1, 1]
    assert len(wrapped) == len(items)

    step = make_train_step(model, tx, cfg, donate=False)
    sstep = make_superstep_fn(model, tx, cfg, train=True, donate=False)
    state_a = _fresh_state(tiny_model)
    state_a, loss_a, tasks_a = _run_epoch(step, state_a, mk(), train=True)
    state_b = _fresh_state(tiny_model)
    state_b, loss_b, tasks_b = _run_epoch(
        step, state_b, wrapped, train=True,
        superstep_fn=sstep, n_tasks=superstep_task_count(cfg),
    )
    assert loss_a == loss_b and np.array_equal(tasks_a, tasks_b)
    assert _leaves_equal(
        jax.device_get(state_a.params), jax.device_get(state_b.params)
    )


# ----------------------------------------------------------------------
# Delivery: serial vs pipeline, caches, K=1 identity
# ----------------------------------------------------------------------


def test_grouping_determinism_serial_vs_pipeline():
    """Serial SuperstepLoader and the pipeline's worker-side stacking
    must deliver the SAME items — same group boundaries, same stacked
    bytes — for a seeded shuffled epoch (packing on: the production
    shape)."""
    from hydragnn_tpu.data.loader import GraphLoader, SuperstepLoader
    from hydragnn_tpu.data.pipeline import ParallelPipelineLoader

    samples = _mols(96, seed=11)
    mk = lambda: GraphLoader(  # noqa: E731
        samples, 4, shuffle=True, seed=2, packing=True
    )
    for epoch in (0, 1):
        serial = SuperstepLoader(mk(), 8)
        serial.set_epoch(epoch)
        pipe = ParallelPipelineLoader(
            mk(), workers=2, depth=2, packed=True, chunk=2, superstep_k=8
        )
        pipe.set_epoch(epoch)
        items_s, items_p = list(serial), list(pipe)
        assert len(items_s) == len(items_p)
        for a, b in zip(items_s, items_p):
            assert isinstance(a, MacroBatch) == isinstance(b, MacroBatch)
            if isinstance(a, MacroBatch):
                assert a.k == b.k
            assert _leaves_equal(a, b)


def test_superstep_loader_cache_replay_and_sharing(tiny_model):
    """Fixed-order eval loaders with cache_batches replay identical
    grouped deliveries from a cache SHARED on the base loader — so the
    val/test pattern (two wrappers over one cached eval loader)
    collates and holds the epoch once. GraphLoader's own per-step
    cache stays untouched (it must never hold macro items)."""
    from hydragnn_tpu.data.loader import GraphLoader, SuperstepLoader

    samples, *_ = tiny_model
    base = GraphLoader(samples, 4, fixed_pad=True, cache_batches=True)
    wrapped = SuperstepLoader(base, 4)
    first = list(wrapped)
    assert getattr(base, "_superstep_cache", None) is not None
    assert base._superstep_cache[0] == 4
    assert base._batch_cache is None  # per-step cache untouched
    second = list(wrapped)
    assert len(first) == len(second)
    for a, b in zip(first, second):
        assert _leaves_equal(a, b)
    # a sibling wrapper over the SAME base replays the shared cache
    # (no re-collate, no second copy): mutate the cache sentinel-style
    # and observe the sibling seeing it.
    sibling = SuperstepLoader(base, 4)
    third = list(sibling)
    assert len(third) == len(first)
    for a, b in zip(first, third):
        assert _leaves_equal(a, b)
    # K-mismatched wrapper must NOT replay the k=4 group boundaries
    other = SuperstepLoader(base, 3)
    ks = [it.k if isinstance(it, MacroBatch) else 1 for it in other]
    assert max(ks) == 3


def test_k1_run_bit_identical_to_superstep_run(tiny_model):
    """The acceptance invariant end-to-end: run_training with
    superstep steps=8 reproduces steps=1 (today's loop) bitwise —
    losses per epoch, val/test metrics, final params — through the
    parallel pipeline feed."""
    from hydragnn_tpu.runner import run_training

    samples, *_ = tiny_model
    tr, va, te = samples[:64], _mols(12, seed=21), _mols(12, seed=22)
    out = {}
    for steps in (1, 8):
        cfg = _config(steps=steps, workers=2, num_epoch=2)
        state, model, mcfg, hist, _ = run_training(
            cfg, (tr, va, te), seed=0
        )
        out[steps] = (
            hist.train_loss,
            hist.val_loss,
            hist.test_loss,
            jax.device_get(state.params),
        )
    assert out[1][0] == out[8][0]
    assert out[1][1] == out[8][1]
    assert out[1][2] == out[8][2]
    assert _leaves_equal(out[1][3], out[8][3])


def test_wrap_loader_k1_returns_todays_wrappers(tiny_model):
    """steps=1 (or auto on a short plan) must not change the feed-path
    object graph at all — K=1 reproduces today's behavior exactly."""
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.parallel.runtime import ParallelPlan, wrap_loader

    samples, *_ = tiny_model
    for steps in (1, "auto"):
        plan = ParallelPlan(
            scheme="single", superstep_steps=steps, pipeline_workers=0
        )
        wrapped = wrap_loader(
            plan, GraphLoader(samples, 4, fixed_pad=True)
        )
        chain = [type(x).__name__ for x in _chain(wrapped)]
        assert "SuperstepLoader" not in chain
        plan2 = ParallelPlan(
            scheme="single", superstep_steps=steps, pipeline_workers=2
        )
        wrapped2 = wrap_loader(
            plan2, GraphLoader(samples, 4, fixed_pad=True)
        )
        assert getattr(wrapped2, "superstep_k", 1) == 1


def _chain(loader):
    from hydragnn_tpu.data.loader import iter_loader_chain

    return iter_loader_chain(loader)


def test_run_epoch_raises_without_superstep_fn(tiny_model):
    from hydragnn_tpu.data.loader import GraphLoader, SuperstepLoader
    from hydragnn_tpu.train.loop import _run_epoch, make_train_step

    samples, model, cfg, tx, params, bs = tiny_model
    step = make_train_step(model, tx, cfg, donate=False)
    wrapped = SuperstepLoader(GraphLoader(samples, 4, fixed_pad=True), 4)
    with pytest.raises(RuntimeError, match="MacroBatch"):
        _run_epoch(step, _fresh_state(tiny_model), wrapped, train=True)


def test_superstep_task_count(tiny_model):
    from hydragnn_tpu.train.loop import superstep_task_count

    _, _, cfg, *_ = tiny_model
    assert superstep_task_count(cfg) == len(cfg.heads)
    mlip_cfg = dataclasses.replace(
        cfg, enable_interatomic_potential=True
    )
    assert superstep_task_count(mlip_cfg) == 3
