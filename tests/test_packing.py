"""Bin-packed batch forming: budget fitting, first-fit-decreasing
epoch packing, loader integration, packed-vs-ladder parity, and the
packing-off bit-identity invariant (ISSUE 3 tentpole).
"""

import numpy as np
import pytest

import tests._cpu  # noqa: F401

import dataclasses

import jax


def _mols(n, lo, hi, seed=0, with_node_targets=False):
    from hydragnn_tpu.data.graph import GraphSample
    from hydragnn_tpu.ops.neighbors import radius_graph

    r = np.random.default_rng(seed)
    out = []
    for i in range(n):
        k = int(r.integers(lo, hi))
        # constant density (box scales with k^(1/3)): edge counts stay
        # roughly node-linear, like molecular datasets
        pos = r.uniform(0, 1.6 * k ** (1 / 3), (k, 3)).astype(np.float32)
        kw = {}
        if with_node_targets:
            kw["y_node"] = r.normal(size=(k, 1)).astype(np.float32)
        out.append(
            GraphSample(
                x=np.full((k, 1), float(i), np.float32),
                pos=pos,
                edge_index=radius_graph(pos, 2.2),
                y_graph=np.array([float(i)], np.float32),
                **kw,
            )
        )
    return out


def _batches_equal(la, lb):
    if len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        for f in dataclasses.fields(x):
            u, v = getattr(x, f.name), getattr(y, f.name)
            if (u is None) != (v is None):
                return False
            if u is None:
                continue
            if not np.array_equal(np.asarray(u), np.asarray(v)):
                return False
    return True


# ----------------------------------------------------------------------
# Fitting + FFD arithmetic
# ----------------------------------------------------------------------


def test_ffd_covers_epoch_within_capacity():
    from hydragnn_tpu.data.padschedule import (
        epoch_batch_indices,
        fit_pack_budgets,
        pack_epoch_ffd,
    )

    r = np.random.default_rng(0)
    ns = r.integers(10, 40, 300)
    es = (ns * 8 + r.integers(-15, 15, 300)).clip(1)
    budgets = fit_pack_budgets(ns, es, 32)
    assert budgets and budgets[0].capacity_nodes >= int(ns.max())
    order = np.concatenate(
        list(epoch_batch_indices(300, 32, shuffle=True, seed=3, epoch=0))
    )
    bins = pack_epoch_ffd(order, ns, es, budgets)
    # every sample exactly once
    got = np.concatenate([idx for idx, _ in bins])
    assert sorted(got.tolist()) == sorted(order.tolist())
    for idx, spec in bins:
        assert spec.fits(
            int(ns[idx].sum()), int(es[idx].sum()), len(idx)
        )
    # deterministic for identical inputs
    bins2 = pack_epoch_ffd(order, ns, es, budgets)
    assert all(
        np.array_equal(a[0], b[0]) and a[1] == b[1]
        for a, b in zip(bins, bins2)
    )


def test_packing_cuts_pad_waste_on_varied_sizes():
    """The acceptance shape: zinc-like sizes pack to a low residual."""
    from hydragnn_tpu.data.padschedule import (
        dataset_size_arrays,
        epoch_batch_indices,
        fit_pack_budgets,
        pack_epoch_ffd,
    )

    samples = _mols(256, 18, 39, seed=2)
    ns, es = dataset_size_arrays(samples)
    budgets = fit_pack_budgets(ns, es, 64)
    exe = real = 0.0
    for ep in range(2):
        order = np.concatenate(
            list(
                epoch_batch_indices(
                    256, 64, shuffle=True, seed=0, epoch=ep
                )
            )
        )
        for idx, spec in pack_epoch_ffd(order, ns, es, budgets):
            exe += spec.num_nodes + spec.num_edges
            real += float(ns[idx].sum() + es[idx].sum())
    assert exe / real <= 1.10  # ISSUE acceptance bound


def test_oversized_graph_rejected():
    from hydragnn_tpu.data.graph import PackSpec
    from hydragnn_tpu.data.padschedule import pack_epoch_ffd

    ns = np.array([5, 200], np.int64)
    es = np.array([10, 400], np.int64)
    tiny = PackSpec(num_nodes=64, num_edges=128, num_graphs=9)
    with pytest.raises(ValueError, match="exceeds the largest"):
        pack_epoch_ffd(np.array([0, 1]), ns, es, [tiny])


def test_max_nodes_per_graph_ignores_padding_slots():
    """Packed tail bins carry long padding-node runs whose slot ids
    count up to the padded remainder; the dense-layout bound must
    reflect REAL graphs only."""
    from hydragnn_tpu.data.graph import PadSpec, collate

    samples = _mols(3, 6, 10, seed=11)
    real_max = max(s.num_nodes for s in samples)
    n = sum(s.num_nodes for s in samples)
    spec = PadSpec(
        num_nodes=n + 100, num_edges=512, num_graphs=len(samples) + 20
    )
    batch = collate(samples, spec, as_numpy=True)
    assert batch.max_nodes_per_graph == real_max


def test_non_nested_budget_set_rejected():
    """Bins open under the largest budget only; a non-nested sibling
    (edge-heavy but node-narrow) would silently never be used — loud
    error instead."""
    from hydragnn_tpu.data.graph import PackSpec
    from hydragnn_tpu.data.padschedule import pack_epoch_ffd

    ns = np.array([10, 10], np.int64)
    es = np.array([20, 20], np.int64)
    wide = PackSpec(num_nodes=257, num_edges=512, num_graphs=17)
    edge_heavy = PackSpec(num_nodes=129, num_edges=4096, num_graphs=17)
    with pytest.raises(ValueError, match="nested"):
        pack_epoch_ffd(np.array([0, 1]), ns, es, [wide, edge_heavy])


def test_auto_baseline_uses_worst_case_clamp(monkeypatch):
    """When the ladder would blow the bucket budget and the run would
    clamp to ONE worst-case shape, the auto decision must compare
    against THAT (the motivating 1.4x regime), not an idealized
    per-batch ladder."""
    from hydragnn_tpu.data.padschedule import packing_beats_ladder

    r = np.random.default_rng(0)
    ns = r.integers(8, 120, 512)  # high variance: many bucket keys
    es = ns * 9
    monkeypatch.setenv("HYDRAGNN_TPU_MAX_PAD_BUCKETS", "2")
    won = packing_beats_ladder(ns, es, 32)
    assert won is not None  # vs the worst-case clamp packing wins big
    budgets, slack = won
    assert budgets and slack is not None
    # forced baselines mirror the resolved fixed-pad mode
    assert packing_beats_ladder(ns, es, 32, baseline="worst") is not None


# ----------------------------------------------------------------------
# Loader integration
# ----------------------------------------------------------------------


def test_packed_loader_delivers_every_graph_once():
    from hydragnn_tpu.data.loader import GraphLoader

    samples = _mols(120, 8, 24, seed=1)
    ld = GraphLoader(samples, 16, shuffle=True, seed=5, packing=True)
    assert len(ld) == len(list(ld.epoch_plan(0)))
    seen = []
    slots = real = 0
    for b in ld:
        gm = np.asarray(b.graph_mask)
        seen += [int(v) for v in np.asarray(b.y_graph)[gm, 0]]
        slots += b.num_nodes + b.num_edges
        real += int(np.asarray(b.node_mask).sum())
        real += int(np.asarray(b.edge_mask).sum())
    assert sorted(seen) == list(range(120))
    st = ld.packing_stats()
    assert st is not None and 0.5 < st["node_fill"] <= 1.0
    # the plan's ratio is the delivered batches': padded node+edge
    # slots over what the masks mark real, never under 1
    assert st["pad_ratio"] >= 1.0
    assert slots / real == st["pad_ratio"]
    # shapes come only from the fitted budgets
    keys = ld.planned_spec_keys()
    assert 1 <= len(keys) <= 2


def test_epoch_plan_bit_identical_with_packing_off():
    """The invariant the ISSUE pins: with packing disabled, epoch_plan
    reproduces the pre-packing sequences exactly — the shuffled batch
    index arrays from epoch_batch_indices, with the documented spec
    arithmetic (bucket ladder / fixed worst case)."""
    from hydragnn_tpu.data.graph import PadSpec, bucket_size
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.data.padschedule import (
        dataset_size_arrays,
        epoch_batch_indices,
    )

    samples = _mols(90, 8, 24, seed=4)
    ns, es = dataset_size_arrays(samples)
    for fixed in (True, False):
        ld = GraphLoader(
            samples, 16, shuffle=True, seed=7, fixed_pad=fixed
        )
        for ep in (0, 1):
            plan = list(ld.epoch_plan(ep))
            exp_idx = list(
                epoch_batch_indices(
                    90, 16, shuffle=True, seed=7, epoch=ep
                )
            )
            assert len(plan) == len(exp_idx)
            for (idx, spec), eidx in zip(plan, exp_idx):
                assert np.array_equal(idx, eidx)
                if fixed:
                    assert spec.num_nodes == ld.pad_spec.num_nodes
                    assert spec.num_edges == ld.pad_spec.num_edges
                else:
                    assert spec == PadSpec(
                        num_nodes=bucket_size(int(ns[eidx].sum()) + 1),
                        num_edges=bucket_size(
                            max(int(es[eidx].sum()), 1)
                        ),
                        num_graphs=len(eidx) + 1,
                        num_triplets=None,
                    )


def test_packing_rejects_incompatible_modes():
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.data.padschedule import (
        dataset_size_arrays,
        dp_spec_schedule,
    )

    samples = _mols(40, 8, 16, seed=0)
    ns, es = dataset_size_arrays(samples)
    sched = dp_spec_schedule(
        ns, es, batch_size=8, n_procs=1, steps_group=1, seed=0,
        shuffle=True,
    )
    with pytest.raises(ValueError, match="spec_schedule"):
        GraphLoader(
            samples, 8, shuffle=True, packing=True, spec_schedule=sched
        )
    with pytest.raises(ValueError, match="triplet"):
        GraphLoader(samples, 8, packing=True, with_triplets=True)


def test_pipeline_bit_identical_under_packing():
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.data.pipeline import ParallelPipelineLoader

    samples = _mols(96, 8, 24, seed=6)
    la = list(GraphLoader(samples, 16, shuffle=True, seed=2, packing=True))
    for workers, chunk in ((1, 1), (3, 2)):
        lb = list(
            ParallelPipelineLoader(
                GraphLoader(
                    samples, 16, shuffle=True, seed=2, packing=True
                ),
                workers=workers,
                depth=2,
                packed=True,
                chunk=chunk,
            )
        )
        assert _batches_equal(la, lb)


# ----------------------------------------------------------------------
# Model-level parity: packing changes only padding, never numerics.
# ----------------------------------------------------------------------


def _parity_model(batch):
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.models.spec import BranchSpec, HeadSpec, ModelConfig

    cfg = ModelConfig(
        mpnn_type="SchNet",
        input_dim=1,
        hidden_dim=8,
        num_conv_layers=2,
        heads=(HeadSpec("g", "graph", 1), HeadSpec("n", "node", 1)),
        graph_branches=(BranchSpec(),),
        node_branches=(
            BranchSpec(
                node_head_type="mlp",
                dim_headlayers=(8, 8),
                num_headlayers=2,
            ),
        ),
        task_weights=(1.0, 1.0),
        radius=2.2,
        num_gaussians=8,
        num_filters=8,
    )
    model = create_model(cfg)
    params, bs = init_params(model, batch)
    return model, cfg, params, bs


def test_packed_vs_ladder_loss_and_grad_parity():
    """The SAME graphs collated at the ladder spec vs at a (larger)
    packed budget spec: masking + per-graph heads make the extra
    padding inert. Total/per-task losses and per-graph node outputs
    come out bit-exact at the node level; losses, gradients and pooled
    graph outputs match to reduction-order ulps (sums over
    differently-padded rows regroup XLA's reduction tree — tolerance
    1e-6 relative)."""
    from hydragnn_tpu.data.graph import PadSpec, collate
    from hydragnn_tpu.train.loop import make_loss_fn

    samples = _mols(10, 6, 14, seed=3, with_node_targets=True)
    ladder = collate(samples, PadSpec.for_samples(samples))
    n = sum(s.num_nodes for s in samples)
    e = sum(s.num_edges for s in samples)
    packed_spec = PadSpec(
        num_nodes=n + 41, num_edges=e + 96, num_graphs=len(samples) + 9
    )
    packed = collate(samples, packed_spec)
    model, cfg, params, bs = _parity_model(ladder)

    loss_fn = make_loss_fn(model, cfg)
    (la, (ta, _)), ga = jax.value_and_grad(loss_fn, has_aux=True)(
        params, bs, ladder
    )
    (lb, (tb, _)), gb = jax.value_and_grad(loss_fn, has_aux=True)(
        params, bs, packed
    )
    np.testing.assert_allclose(
        np.asarray(la), np.asarray(lb), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(ta), np.asarray(tb), rtol=1e-6
    )
    for x, y in zip(
        jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)
    ):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-6, atol=1e-7
        )

    outs_a = model.apply(
        {"params": params, "batch_stats": bs}, ladder, train=False
    )
    outs_b = model.apply(
        {"params": params, "batch_stats": bs}, packed, train=False
    )
    n_real = int(np.asarray(ladder.node_mask).sum())
    g_real = int(np.asarray(ladder.graph_mask).sum())
    # node head: row-aligned compute, bit-exact across paddings
    np.testing.assert_array_equal(
        np.asarray(outs_a[1])[:n_real], np.asarray(outs_b[1])[:n_real]
    )
    # graph head: pooled through a segment reduce, ulp-level only
    np.testing.assert_allclose(
        np.asarray(outs_a[0])[:g_real],
        np.asarray(outs_b[0])[:g_real],
        rtol=1e-6,
        atol=1e-7,
    )


def test_packed_loader_trains_end_to_end():
    """A jitted train step consumes the packed loader's mixed budget
    shapes (one compile per budget) and the loss goes down."""
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.train.loop import make_train_step
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.state import create_train_state

    samples = _mols(48, 6, 14, seed=8, with_node_targets=True)
    ld = GraphLoader(samples, 12, shuffle=True, seed=0, packing=True)
    first = next(iter(ld))
    model, cfg, params, bs = _parity_model(first)
    tx = select_optimizer(
        {"Optimizer": {"type": "AdamW", "learning_rate": 1e-2}}
    )
    state = create_train_state(params, tx, bs)
    step = make_train_step(model, tx, cfg)
    losses = []
    for ep in range(12):
        ld.set_epoch(ep)
        ep_loss = 0.0
        for batch in ld:
            state, tot, _ = step(state, batch)
            ep_loss += float(tot)
        losses.append(ep_loss)
    assert losses[-1] < losses[0] * 0.7


def test_runner_resolve_packing_envelope():
    """Packing applies on the single scheme (per-batch bins) and on
    single-process dp meshes (device-coordinated bins — docs/PACKING.md
    sharded fast path); multibranch and triplet models fall back."""
    from hydragnn_tpu.parallel.mesh import make_mesh
    from hydragnn_tpu.parallel.runtime import ParallelPlan
    from hydragnn_tpu.runner import _resolve_packing

    samples = _mols(64, 8, 20, seed=9)
    single = ParallelPlan(scheme="single", packing=True)
    on, budgets, slack = _resolve_packing(single, False, 16, samples)
    assert on and budgets and slack is not None
    # dp on a single-process mesh now rides the coordinated packer
    dp_plan = ParallelPlan(
        scheme="dp", mesh=make_mesh({"data": 8}), packing=True
    )
    on, budgets, slack = _resolve_packing(dp_plan, False, 16, samples)
    assert on and budgets and slack is not None
    # ...but a training split too small to feed every device does not
    on, _, _ = _resolve_packing(dp_plan, False, 16, samples[:6])
    assert not on
    on, _, _ = _resolve_packing(
        ParallelPlan(scheme="multibranch", packing=True),
        False, 16, samples,
    )
    assert not on
    on, _, _ = _resolve_packing(single, True, 16, samples)  # triplets
    assert not on
    off = ParallelPlan(scheme="single", packing=False)
    on, _, _ = _resolve_packing(off, False, 16, samples)
    assert not on
    # auto: uniform sizes gain nothing -> ladder kept; varied sizes win
    auto = ParallelPlan(scheme="single", packing="auto")
    uniform = _mols(64, 12, 13, seed=9)
    on_u, _, _ = _resolve_packing(auto, False, 16, uniform)
    varied = _mols(256, 18, 39, seed=2)
    on_v, b_v, s_v = _resolve_packing(auto, False, 64, varied)
    assert on_v and b_v and s_v is not None
    assert isinstance(on_u, bool)


def test_plan_from_config_packing_block():
    from hydragnn_tpu.parallel.runtime import plan_from_config

    cfg = {
        "NeuralNetwork": {
            "Training": {
                "Parallelism": {
                    "scheme": "single",
                    "packing": {
                        "enabled": True,
                        "max_budgets": 3,
                        "slack": 1.05,
                        "max_graphs": 96,
                    },
                }
            }
        }
    }
    plan = plan_from_config(cfg, devices=[object()])
    assert plan.packing is True
    assert plan.packing_max_budgets == 3
    assert plan.packing_slack == 1.05
    assert plan.packing_max_graphs == 96
    # default: auto
    plan = plan_from_config(
        {"NeuralNetwork": {"Training": {}}}, devices=[object()]
    )
    assert plan.packing == "auto"
    # string spellings of false must DISABLE, never truthy-enable
    for off in ("false", "0", "no", "off", False):
        cfg_off = {
            "NeuralNetwork": {
                "Training": {
                    "Parallelism": {"packing": {"enabled": off}}
                }
            }
        }
        assert plan_from_config(cfg_off, devices=[object()]).packing is False
    cfg_on = {
        "NeuralNetwork": {
            "Training": {"Parallelism": {"packing": {"enabled": "true"}}}
        }
    }
    assert plan_from_config(cfg_on, devices=[object()]).packing is True
    # unknown spellings are a loud error, not a silent enable
    cfg_bad = {
        "NeuralNetwork": {
            "Training": {
                "Parallelism": {"packing": {"enabled": "sometimes"}}
            }
        }
    }
    with pytest.raises(ValueError, match="not recognized"):
        plan_from_config(cfg_bad, devices=[object()])
