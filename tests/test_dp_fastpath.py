"""Sharded fast path under data parallelism (ISSUE 5): the
device-coordinated packer, packed ``[D, ...]`` delivery through serial
and pipeline feeds, and the dp superstep executor's bitwise-identity
contract on the fake 8-device CPU mesh (tests/conftest.py pins
``--xla_force_host_platform_device_count=8``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.data.graph import GraphSample, MacroBatch
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


def _config(
    *,
    steps=1,
    workers=0,
    packing=True,
    num_epoch=2,
    batch_size=4,
    data=8,
):
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
                    "scheme": "dp",
                    "data": data,
                    "pipeline": {"workers": workers},
                    "packing": {"enabled": packing},
                    "superstep": {"steps": steps},
                },
            },
        }
    }


def _leaves_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(u), np.asarray(v))
        for u, v in zip(la, lb)
    )


# ----------------------------------------------------------------------
# Device-coordinated packer (pure plan arithmetic)
# ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,d,lohi,bs",
    [
        (300, 8, (5, 40), 8),  # varied sizes, several budgets
        (64, 8, (5, 11), 4),  # small epoch: one step per spec at most
        (200, 4, (20, 21), 8),  # uniform sizes
        (53, 8, (5, 30), 4),  # awkward counts force balancing splits
    ],
)
def test_pack_epoch_ffd_dp_device_agreement(n, d, lohi, bs):
    """The coordination invariant: every device sees the same number of
    steps, the same budget (compiled shape) at every step, and the
    union of all bins is exactly the epoch's sample multiset — nothing
    dropped, nothing duplicated."""
    from hydragnn_tpu.data.padschedule import (
        epoch_batch_indices,
        fit_pack_budgets,
        pack_epoch_ffd_dp,
    )

    r = np.random.default_rng(1)
    ns = r.integers(*lohi, size=n).astype(np.int64)
    es = (ns * 3).astype(np.int64)
    budgets = fit_pack_budgets(ns, es, bs)
    for ep in range(3):
        order = np.concatenate(
            list(
                epoch_batch_indices(
                    n, bs, shuffle=True, seed=0, epoch=ep
                )
            )
        )
        plan = pack_epoch_ffd_dp(order, ns, es, budgets, d)
        # plan length a multiple of D: equal per-device step counts
        assert len(plan) % d == 0 and len(plan) >= d
        n_steps = len(plan) // d
        # per-step budget identity across the data axis, and therefore
        # an identical per-epoch spec SEQUENCE on every device
        per_dev = [
            [
                (s.num_nodes, s.num_edges, s.num_graphs)
                for (_, s) in plan[dev :: d]
            ]
            for dev in range(d)
        ]
        assert all(seq == per_dev[0] for seq in per_dev[1:])
        assert all(len(seq) == n_steps for seq in per_dev)
        # no sample dropped or duplicated
        got = np.sort(np.concatenate([idx for idx, _ in plan]))
        assert np.array_equal(got, np.sort(order))
        # every bin respects its budget's capacity
        for idx, s in plan:
            assert int(ns[idx].sum()) + 1 <= s.num_nodes
            assert int(es[idx].sum()) <= s.num_edges
            assert len(idx) + 1 <= s.num_graphs


def test_pack_epoch_ffd_dp_feasibility_is_epoch_invariant():
    """The canonical (-n, -e, pos) packing order makes the bin
    size-structure — and therefore the balance pass's feasibility AND
    the per-epoch spec sequence — a function of the size multiset
    alone: the runner's epoch-0 probe proves every later shuffle.
    Heavy node-count ties with divergent edge counts (the hazardous
    case: epoch-order tie-breaking would reshape bins per shuffle)."""
    from hydragnn_tpu.data.padschedule import (
        epoch_batch_indices,
        fit_pack_budgets,
        pack_epoch_ffd_dp,
    )

    r = np.random.default_rng(0)
    ns = np.repeat([10, 20, 30], 40).astype(np.int64)
    es = (ns * 2 + r.integers(0, 25, size=120)).astype(np.int64)
    budgets = fit_pack_budgets(ns, es, 6)
    profiles = set()
    for ep in range(12):
        order = np.concatenate(
            list(
                epoch_batch_indices(
                    120, 6, shuffle=True, seed=0, epoch=ep
                )
            )
        )
        plan = pack_epoch_ffd_dp(order, ns, es, budgets, 8)
        profiles.add(
            tuple(
                (s.num_nodes, s.num_edges, s.num_graphs)
                for _, s in plan
            )
        )
    assert len(profiles) == 1


def test_pack_dp_shards_rejects_resampling():
    """num_samples resamples the size multiset per epoch, so a later
    epoch could become infeasible to coordinate — rejected up front
    instead of raising mid-train."""
    from hydragnn_tpu.data.loader import GraphLoader

    with pytest.raises(ValueError, match="num_samples"):
        GraphLoader(
            _mols(64), 4, shuffle=True, num_samples=128,
            packing=True, pack_dp_shards=8,
        )


def test_pack_epoch_ffd_dp_too_few_graphs_raises():
    from hydragnn_tpu.data.padschedule import (
        fit_pack_budgets,
        pack_epoch_ffd_dp,
    )

    ns = np.full(4, 10, np.int64)
    es = np.full(4, 20, np.int64)
    budgets = fit_pack_budgets(ns, es, 2)
    with pytest.raises(ValueError, match="coordinate packed bins"):
        pack_epoch_ffd_dp(np.arange(4), ns, es, budgets, 8)


def test_dp_step_plan_folds_and_flags_mixed_steps():
    from hydragnn_tpu.data.graph import PadSpec
    from hydragnn_tpu.data.padschedule import dp_step_plan

    a = PadSpec(num_nodes=64, num_edges=128, num_graphs=5)
    b = PadSpec(num_nodes=32, num_edges=64, num_graphs=5)
    plan = [(0, a), (1, a), (2, a), (3, b), (4, a), (5, b), (6, a)]
    steps, tail = dp_step_plan(plan, 3)
    # step 0 shares spec a; step 1 mixes a/b -> ungroupable (None)
    assert [s for _, s in steps] == [a, None]
    assert [e[0] for e in tail] == [6]


def test_packed_dp_plan_folds_4x_at_k8_and_pads_less_than_the_ladder():
    """Plan arithmetic on an 8-device data mesh over zinc-like sizes:
    the coordinated packer's spec-major steps fold at least 4x at K=8,
    and its padded node+edge slots over real ones stay under those of
    the dp spec-schedule ladder on the same shuffle."""
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.data.padschedule import (
        batch_size_rows,
        dataset_size_arrays,
        dp_spec_schedule,
        dp_step_plan,
        epoch_batch_indices,
        superstep_groups,
    )

    n_dev, batch_size = 8, 8
    samples = _mols(256, 18, 38, seed=2) * 8
    loader = GraphLoader(
        samples, batch_size, shuffle=True, seed=0, packing=True,
        pack_dp_shards=n_dev,
    )
    ns, es = dataset_size_arrays(samples)
    sched = dp_spec_schedule(
        ns, es, batch_size=batch_size, n_procs=1, steps_group=n_dev,
        seed=0, shuffle=True,
    )
    for ep in range(2):
        plan = list(loader.epoch_plan(ep))
        steps, tail = dp_step_plan(plan, n_dev)
        assert not tail
        assert len(steps) >= 4 * len(superstep_groups(steps, 8))
        packed = sum(s.num_nodes + s.num_edges for _, s in plan) / sum(
            int(ns[idx].sum()) + int(es[idx].sum()) for idx, _ in plan
        )
        rows = batch_size_rows(
            ns,
            es,
            epoch_batch_indices(
                len(ns), batch_size, shuffle=True, seed=0, epoch=ep
            ),
        )
        assert len(rows) % n_dev == 0  # no masked remainder step to count
        specs = [sched.spec(ep, j) for j in range(len(rows))]
        ladder = sum(s.num_nodes + s.num_edges for s in specs) / sum(
            int(rn) - 1 + int(re_) for rn, re_, _ in rows
        )
        assert 1.0 <= packed < ladder


# ----------------------------------------------------------------------
# resolve_superstep_k under dp
# ----------------------------------------------------------------------


def test_resolve_superstep_k_dp():
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.parallel.mesh import make_mesh
    from hydragnn_tpu.parallel.runtime import (
        ParallelPlan,
        resolve_superstep_k,
    )

    samples = _mols(256, seed=3)
    mesh = make_mesh({"data": 8})
    loader = GraphLoader(samples, 4, fixed_pad=True)
    # explicit pin wins (mesh present)
    plan = ParallelPlan(scheme="dp", mesh=mesh, superstep_steps=4)
    assert resolve_superstep_k(plan, loader) == 4
    # dp without a mesh is degenerate: K=1
    plan = ParallelPlan(scheme="dp", superstep_steps=4)
    assert resolve_superstep_k(plan, loader) == 1
    # auto on a short STEP-level plan (64 batches / 8 devices = 8
    # steps, under the 64-step floor): K=1
    short = GraphLoader(_mols(64, seed=3), 4, fixed_pad=True)
    plan = ParallelPlan(scheme="dp", mesh=mesh, superstep_steps="auto")
    assert resolve_superstep_k(plan, short) == 1
    # multibranch stays pinned at 1
    plan = ParallelPlan(scheme="multibranch", superstep_steps=4)
    assert resolve_superstep_k(plan, loader) == 1


# ----------------------------------------------------------------------
# Delivery: packed [D, ...] stacking, serial vs pipeline, K=1 wrappers
# ----------------------------------------------------------------------


def _delivered(loader):
    out = []
    for item in loader:
        if isinstance(item, MacroBatch):
            out.append(
                (item.k, jax.tree_util.tree_map(np.asarray, item.batch))
            )
        else:
            out.append(
                (1, jax.tree_util.tree_map(np.asarray, item))
            )
    return out


def test_dp_packed_delivery_serial_vs_pipeline_bit_identical():
    """Packed [D, ...] (and [K, D, ...]) delivery under dp must be
    bit-identical between the serial feed and the worker pipeline —
    the PR-1 contract extended to the sharded fast path."""
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.data.pipeline import ParallelPipelineLoader
    from hydragnn_tpu.parallel.dp import DPLoader
    from hydragnn_tpu.parallel.mesh import make_mesh

    samples = _mols(160, seed=11)
    mesh = make_mesh({"data": 8})

    def _base():
        return GraphLoader(
            samples, 4, shuffle=True, seed=0, packing=True,
            pack_dp_shards=8,
        )

    for k in (1, 2):
        serial = DPLoader(_base(), mesh, superstep_k=k)
        piped = DPLoader(
            ParallelPipelineLoader(
                _base(),
                workers=2,
                to_device=False,
                hold=DPLoader.required_hold(mesh, superstep_k=k),
            ),
            mesh,
            superstep_k=k,
        )
        a = _delivered(serial)
        b = _delivered(piped)
        assert len(a) == len(b) and len(a) > 0
        for (ka, ba), (kb, bb) in zip(a, b):
            assert ka == kb
            assert _leaves_equal(ba, bb)


def test_dp_superstep_delivery_matches_k1_content():
    """Grouping changes dispatch boundaries, never content: flattening
    the K-axis of macro deliveries reproduces the K=1 step sequence
    bit for bit."""
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.parallel.dp import DPLoader
    from hydragnn_tpu.parallel.mesh import make_mesh

    samples = _mols(160, seed=11)
    mesh = make_mesh({"data": 8})

    def _base():
        return GraphLoader(
            samples, 4, shuffle=True, seed=0, packing=True,
            pack_dp_shards=8,
        )

    flat = _delivered(DPLoader(_base(), mesh, superstep_k=1))
    grouped = _delivered(DPLoader(_base(), mesh, superstep_k=2))
    regrouped = []
    for k, b in grouped:
        if k == 1:
            regrouped.append(b)
        else:
            for t in range(k):
                regrouped.append(
                    jax.tree_util.tree_map(lambda x: x[t], b)
                )
    assert len(regrouped) == len(flat)
    for (_, a), b in zip(flat, regrouped):
        assert _leaves_equal(a, b)


def test_wrap_loader_dp_k1_and_superstep_false_keep_todays_chain():
    """With K resolved (or forced) to 1 the dp chain is exactly today's
    wrappers: a DPLoader that yields plain [D, ...] GraphBatches —
    superstep=False consumers (run_test's per-sample collection) are
    untouched even when the plan asks for K>1."""
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.data.loader import iter_loader_chain
    from hydragnn_tpu.parallel import runtime
    from hydragnn_tpu.parallel.dp import DPLoader
    from hydragnn_tpu.parallel.mesh import make_mesh

    samples = _mols(96, seed=5)
    mesh = make_mesh({"data": 8})
    plan = runtime.ParallelPlan(
        scheme="dp", mesh=mesh, superstep_steps=4, pipeline_workers=0
    )
    loader = GraphLoader(samples, 4, fixed_pad=True)
    wrapped = runtime.wrap_loader(plan, loader, superstep=False)
    dpl = next(
        ld
        for ld in iter_loader_chain(wrapped)
        if isinstance(ld, DPLoader)
    )
    assert dpl.superstep_k == 1
    assert all(not isinstance(b, MacroBatch) for b in wrapped)
    # with superstep allowed, the plan's pin reaches the DPLoader
    wrapped2 = runtime.wrap_loader(plan, GraphLoader(samples, 4, fixed_pad=True))
    dpl2 = next(
        ld
        for ld in iter_loader_chain(wrapped2)
        if isinstance(ld, DPLoader)
    )
    assert dpl2.superstep_k == 4


def test_dp_delivery_with_fastpath_off_is_pre_pr_identical():
    """Acceptance: with packing disabled and K=1 the delivered [D, ...]
    sequence is bit-identical to the pre-PR chain (a bare DPLoader over
    the same spec-schedule-free loader)."""
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.parallel import runtime
    from hydragnn_tpu.parallel.dp import DPLoader
    from hydragnn_tpu.parallel.mesh import make_mesh

    samples = _mols(96, seed=5)
    mesh = make_mesh({"data": 8})
    plan = runtime.ParallelPlan(
        scheme="dp", mesh=mesh, superstep_steps=1,
        pipeline_workers=0, prefetch=0, packing=False,
    )
    new = _delivered(
        runtime.wrap_loader(
            plan, GraphLoader(samples, 4, fixed_pad=True)
        )
    )
    old = _delivered(
        DPLoader(GraphLoader(samples, 4, fixed_pad=True), mesh)
    )
    assert len(new) == len(old) > 0
    for (ka, a), (kb, b) in zip(new, old):
        assert ka == kb == 1
        assert _leaves_equal(a, b)


# ----------------------------------------------------------------------
# The dp superstep executor: bitwise identity (the ISSUE acceptance)
# ----------------------------------------------------------------------


def test_run_training_dp_packing_falls_back_per_split():
    """A split too small to feed every device a coordinated packed plan
    falls back to the spec-schedule former PER SPLIT at startup (the
    len() probe) — the train loader keeps the packed fast path, the
    run completes, and nothing can raise mid-train (feasibility is
    epoch-invariant under the canonical packing order)."""
    from hydragnn_tpu.runner import run_training

    samples = _mols(80, seed=9)
    # val/test splits of 5 graphs each: < 8 devices, uncoordinatable;
    # the 70-graph train split coordinates fine
    tr, va, te = samples[:70], samples[70:75], samples[75:]
    cfg = _config(steps=1, workers=0, packing=True, num_epoch=1)
    state, _, _, hist, _ = run_training(cfg, datasets=(tr, va, te), seed=0)
    assert len(hist.train_loss) == 1
    assert np.isfinite(hist.train_loss).all()
    # the packed-train run must differ from an all-unpacked run only in
    # eval handling: compare against packing fully disabled — training
    # trajectories DIFFER (packed former) while both runs complete
    cfg2 = _config(steps=1, workers=0, packing=False, num_epoch=1)
    _, _, _, hist2, _ = run_training(cfg2, datasets=(tr, va, te), seed=0)
    assert hist.train_loss != hist2.train_loss, (
        "train split lost its packed former to an eval-split fallback"
    )


def test_dp_scan_bitwise_vs_sequential_dp_steps():
    """K scanned dp steps == K sequential jitted dp step dispatches,
    bit for bit (loss/task sums AND final params), on the fake
    8-device mesh — the dp form of the PR-4 contract."""
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.models.create import create_model_config, init_params
    from hydragnn_tpu.parallel.dp import (
        DPLoader,
        make_dp_superstep_fn,
        make_dp_train_step,
        replicate_state,
    )
    from hydragnn_tpu.parallel.mesh import make_mesh
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.state import create_train_state

    samples = _mols(128, seed=3)
    cfgd = update_config(_config(), samples)
    model, cfg = create_model_config(cfgd)
    tx = select_optimizer(cfgd["NeuralNetwork"]["Training"])
    mesh = make_mesh({"data": 8})

    base = GraphLoader(samples, 4, fixed_pad=True)
    params, bs = init_params(model, next(iter(base)))
    host_params = jax.tree_util.tree_map(
        lambda x: np.array(x, copy=True), jax.device_get(params)
    )
    host_bs = jax.tree_util.tree_map(
        lambda x: np.array(x, copy=True), jax.device_get(bs)
    )

    def fresh_state():
        return replicate_state(
            create_train_state(
                jax.tree_util.tree_map(jnp.array, host_params),
                tx,
                jax.tree_util.tree_map(jnp.array, host_bs),
            ),
            mesh,
        )

    k = 4
    flat = list(iter(DPLoader(base, mesh)))[:k]
    assert len(flat) == k

    step = make_dp_train_step(model, tx, cfg, mesh)
    st = fresh_state()
    loss_sum = tasks_sum = ng = None
    for sb in flat:
        g = jnp.sum(sb.graph_mask).astype(jnp.float32)
        st, tot, tasks = step(st, sb)
        if loss_sum is None:
            loss_sum, tasks_sum, ng = tot * g, tasks * g, g
        else:
            loss_sum = loss_sum + tot * g
            tasks_sum = tasks_sum + tasks * g
            ng = ng + g
    seq_params = jax.device_get(st.params)
    seq_acc = jax.device_get((loss_sum, tasks_sum, ng))

    sstep = make_dp_superstep_fn(model, tx, cfg, mesh, train=True)
    base2 = GraphLoader(samples, 4, fixed_pad=True)
    macro = next(
        iter(DPLoader(base2, mesh, superstep_k=k))
    )
    assert isinstance(macro, MacroBatch) and macro.k == k
    st2 = fresh_state()
    acc0 = (
        jnp.zeros((), jnp.float32),
        jnp.zeros((1,), jnp.float32),
        jnp.zeros((), jnp.float32),
    )
    st2, acc = sstep(st2, acc0, macro.batch)
    scan_params = jax.device_get(st2.params)
    scan_acc = jax.device_get(acc)

    assert _leaves_equal(seq_params, scan_params)
    for a, b in zip(seq_acc, scan_acc):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_dp_superstep_composes_with_fsdp_bitwise():
    """The scan carries the param shardings unchanged: on a
    {data:4, fsdp:2} mesh the K-scan over the fsdp-sharded dp step is
    still bit-equal to K sequential dispatches."""
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.models.create import create_model_config, init_params
    from hydragnn_tpu.parallel.dp import (
        DPLoader,
        make_dp_superstep_fn,
        make_dp_train_step,
        replicate_state,
    )
    from hydragnn_tpu.parallel.mesh import make_mesh
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.state import create_train_state

    samples = _mols(96, seed=6)
    cfgd = update_config(_config(), samples)
    model, cfg = create_model_config(cfgd)
    tx = select_optimizer(cfgd["NeuralNetwork"]["Training"])
    mesh = make_mesh({"data": 4, "fsdp": 2})
    base = GraphLoader(samples, 4, fixed_pad=True)
    params, bs = init_params(model, next(iter(base)))
    host_params = jax.tree_util.tree_map(
        lambda x: np.array(x, copy=True), jax.device_get(params)
    )
    host_bs = jax.tree_util.tree_map(
        lambda x: np.array(x, copy=True), jax.device_get(bs)
    )

    def fresh_state():
        return replicate_state(
            create_train_state(
                jax.tree_util.tree_map(jnp.array, host_params),
                tx,
                jax.tree_util.tree_map(jnp.array, host_bs),
            ),
            mesh,
            fsdp=True,
        )

    k = 2
    flat = list(iter(DPLoader(base, mesh)))[:k]
    step = make_dp_train_step(model, tx, cfg, mesh)
    st = fresh_state()
    for sb in flat:
        st, _, _ = step(st, sb)
    seq_params = jax.device_get(st.params)

    macro = next(
        iter(
            DPLoader(
                GraphLoader(samples, 4, fixed_pad=True),
                mesh,
                superstep_k=k,
            )
        )
    )
    assert isinstance(macro, MacroBatch)
    sstep = make_dp_superstep_fn(model, tx, cfg, mesh, train=True)
    st2 = fresh_state()
    st2, _ = sstep(
        st2,
        (
            jnp.zeros((), jnp.float32),
            jnp.zeros((1,), jnp.float32),
            jnp.zeros((), jnp.float32),
        ),
        macro.batch,
    )
    assert _leaves_equal(seq_params, jax.device_get(st2.params))


def test_dp_eval_superstep_bitwise(tmp_path):
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.models.create import create_model_config, init_params
    from hydragnn_tpu.parallel.dp import (
        DPLoader,
        make_dp_eval_step,
        make_dp_superstep_fn,
        replicate_state,
    )
    from hydragnn_tpu.parallel.mesh import make_mesh
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.state import create_train_state

    samples = _mols(128, seed=4)
    cfgd = update_config(_config(), samples)
    model, cfg = create_model_config(cfgd)
    tx = select_optimizer(cfgd["NeuralNetwork"]["Training"])
    mesh = make_mesh({"data": 8})
    base = GraphLoader(samples, 4, fixed_pad=True)
    params, bs = init_params(model, next(iter(base)))
    state = replicate_state(
        create_train_state(params, tx, bs), mesh
    )

    k = 4
    flat = list(iter(DPLoader(base, mesh)))[:k]
    estep = make_dp_eval_step(model, cfg, mesh)
    loss_sum = tasks_sum = ng = None
    for sb in flat:
        g = jnp.sum(sb.graph_mask).astype(jnp.float32)
        tot, tasks = estep(state, sb)
        if loss_sum is None:
            loss_sum, tasks_sum, ng = tot * g, tasks * g, g
        else:
            loss_sum = loss_sum + tot * g
            tasks_sum = tasks_sum + tasks * g
            ng = ng + g
    seq = jax.device_get((loss_sum, tasks_sum, ng))

    sstep = make_dp_superstep_fn(model, tx, cfg, mesh, train=False)
    macro = next(
        iter(
            DPLoader(
                GraphLoader(samples, 4, fixed_pad=True),
                mesh,
                superstep_k=k,
            )
        )
    )
    acc = sstep(
        state,
        (
            jnp.zeros((), jnp.float32),
            jnp.zeros((1,), jnp.float32),
            jnp.zeros((), jnp.float32),
        ),
        macro.batch,
    )
    scan = jax.device_get(acc)
    for a, b in zip(seq, scan):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_run_training_dp_superstep_bitwise_identity():
    """THE acceptance gate: packed + K-scan dp training through
    run_training (>= 8 optimizer steps per epoch) produces bit-equal
    losses AND params vs K=1 sequential dp steps, through both the
    serial and the pipeline feed."""
    from hydragnn_tpu.data.loader import split_dataset
    from hydragnn_tpu.runner import run_training

    samples = _mols(400, seed=13)
    tr, va, te = split_dataset(samples, 0.8)
    runs = {}
    for name, steps, workers in (
        ("k1_serial", 1, 0),
        ("k4_serial", 4, 0),
        ("k4_pipeline", 4, 2),
    ):
        cfg = _config(steps=steps, workers=workers, packing=True)
        state, _, _, hist, _ = run_training(
            cfg, datasets=(tr, va, te), seed=0
        )
        runs[name] = (
            jax.device_get(state.params),
            list(hist.train_loss),
            list(hist.val_loss),
            list(hist.test_loss),
        )
    ref = runs["k1_serial"]
    # >= 8 steps per epoch: 320 train graphs / batch 4 / 8 devices = 10
    assert len(ref[1]) == 2
    for name in ("k4_serial", "k4_pipeline"):
        got = runs[name]
        assert _leaves_equal(ref[0], got[0]), f"{name}: params differ"
        assert ref[1] == got[1], f"{name}: train losses differ"
        assert ref[2] == got[2], f"{name}: val losses differ"
        assert ref[3] == got[3], f"{name}: test losses differ"
