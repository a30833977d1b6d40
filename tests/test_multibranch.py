"""Multibranch task-parallel training (reference MultiTaskModelMP,
hydragnn/models/MultiTaskModelMP.py:269-532): branch split, per-branch
gradient semantics, dual optimizer, gradient accumulation, and an e2e
sanity run over an 8-device CPU mesh.
"""

import numpy as np
import pytest

import tests._cpu  # noqa: F401

import jax
import jax.numpy as jnp

from hydragnn_tpu.data.graph import GraphSample, collate
from hydragnn_tpu.models.create import create_model, init_params
from hydragnn_tpu.models.spec import BranchSpec, HeadSpec, ModelConfig
from hydragnn_tpu.ops.neighbors import radius_graph
from hydragnn_tpu.parallel.mesh import make_mesh
from hydragnn_tpu.parallel.multibranch import (
    MultiBranchLoader,
    accumulate,
    branch_of_device,
    dual_optimizer,
    make_multibranch_train_step,
    proportional_branch_split,
    rescale_decoder_grads,
)
from hydragnn_tpu.train.losses import multihead_loss
from hydragnn_tpu.train.state import create_train_state


def test_proportional_branch_split():
    assert proportional_branch_split([100, 100], 8) == [4, 4]
    assert sum(proportional_branch_split([500, 100, 100], 8)) == 8
    split = proportional_branch_split([1000, 10], 8)
    assert split[0] > split[1] >= 1
    with pytest.raises(ValueError):
        proportional_branch_split([1, 1, 1], 2)
    assert list(branch_of_device([2, 1])) == [0, 0, 1]


def _samples(n, dataset_id, seed):
    r = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        k = int(r.integers(4, 8))
        pos = r.uniform(0, 3.0, (k, 3)).astype(np.float32)
        x = r.normal(size=(k, 2)).astype(np.float32)
        # Learnable target with a branch-specific scale so branch heads
        # must specialize.
        y = (1.0 + dataset_id) * float(x.mean())
        out.append(
            GraphSample(
                x=x,
                pos=pos,
                edge_index=radius_graph(pos, 2.5, max_neighbours=12),
                y_graph=np.array([y], np.float32),
                dataset_id=dataset_id,
            )
        )
    return out


def _cfg(n_branches=2):
    return ModelConfig(
        mpnn_type="SchNet",
        input_dim=2,
        hidden_dim=8,
        num_conv_layers=2,
        heads=(HeadSpec("e", "graph", 1),),
        graph_branches=tuple(
            BranchSpec(name=f"branch-{i}") for i in range(n_branches)
        ),
        node_branches=(),
        task_weights=(1.0,),
        radius=2.5,
        num_gaussians=8,
        num_filters=8,
    )


def test_multibranch_gradient_semantics():
    """The rescaled full-mesh gradient mean must equal the reference's
    two-process-group reduction: encoder grads averaged over WORLD,
    branch-b decoder grads averaged over branch b's devices only
    (MultiTaskModelMP.gradient_all_reduce, :458-460)."""
    cfg = _cfg()
    model = create_model(cfg)
    dpb = [3, 1]  # 4 "devices", branch 0 gets 3
    D = sum(dpb)
    bod = branch_of_device(dpb)
    from hydragnn_tpu.data.graph import PadSpec

    spec = PadSpec(num_nodes=24, num_edges=192, num_graphs=3)
    batches = [
        collate(_samples(2, int(bod[d]), seed=d), spec) for d in range(D)
    ]
    from hydragnn_tpu.parallel.mesh import stack_batches

    stacked = stack_batches(batches)
    params, bs = init_params(model, batches[0])

    def device_loss(p, batch):
        out = model.apply({"params": p, "batch_stats": bs}, batch, train=False)
        tot, _ = multihead_loss(out, batch, cfg)
        return tot

    # Full-mesh mean + rescale (what the multibranch step does).
    def mesh_loss(p):
        return jnp.mean(jax.vmap(lambda b: device_loss(p, b))(stacked))

    mesh_grads = jax.grad(mesh_loss)(params)
    rescaled = rescale_decoder_grads(mesh_grads, cfg, D, tuple(dpb))

    # Reference semantics computed directly.
    per_dev = [jax.grad(device_loss)(params, b) for b in batches]

    def mean_over(devs):
        return jax.tree_util.tree_map(
            lambda *xs: sum(xs) / len(xs), *[per_dev[d] for d in devs]
        )

    world_mean = mean_over(range(D))
    branch_means = [
        mean_over([d for d in range(D) if bod[d] == bi])
        for bi in range(len(dpb))
    ]

    flat_r = jax.tree_util.tree_flatten_with_path(rescaled)[0]
    flat_w = jax.tree_util.tree_flatten_with_path(world_mean)[0]
    flat_b = [
        jax.tree_util.tree_flatten_with_path(bm)[0] for bm in branch_means
    ]
    for i, (path, g) in enumerate(flat_r):
        keys = [getattr(p, "key", "") for p in path]
        is_decoder = any(k.startswith("decoder") for k in keys)
        if is_decoder:
            bi = 0 if any(k.endswith("branch-0") for k in keys) else 1
            expected = flat_b[bi][i][1]
        else:
            expected = flat_w[i][1]
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(expected), rtol=1e-4, atol=1e-6
        )


@pytest.mark.parametrize("zero", [False, True], ids=["replicated", "zero"])
def test_multibranch_train_step_runs(zero):
    """``zero``: params and moments sharded over the data axis itself
    (ZeRO/GSPMD), the layout of the multi-dataset job."""
    cfg = _cfg()
    model = create_model(cfg)
    mesh = make_mesh({"data": 8})
    dpb = proportional_branch_split([60, 20], 8)
    branch_sets = [_samples(60, 0, seed=1), _samples(20, 1, seed=2)]
    loader = MultiBranchLoader(
        branch_sets, dpb, batch_size=4, mesh=mesh, seed=0
    )
    batch0 = next(iter(loader.loaders[0]))
    params, bs = init_params(model, batch0)
    tx = dual_optimizer(
        {"Optimizer": {"type": "AdamW", "learning_rate": 1e-3}},
        decoder_lr=3e-3,
    )
    state = create_train_state(params, tx, bs)
    from hydragnn_tpu.parallel.dp import replicate_state

    state = replicate_state(state, mesh, fsdp=zero, axis="data")
    if zero:
        assert any(
            not p.sharding.is_fully_replicated
            for p in jax.tree_util.tree_leaves(state.params)
        )
    step = make_multibranch_train_step(model, tx, cfg, mesh, dpb)
    losses = []
    for epoch in range(8):
        loader.set_epoch(epoch)
        for stacked in loader:
            state, tot, tasks = step(state, stacked)
            losses.append(float(tot))
    assert np.isfinite(losses).all()
    k = max(len(losses) // 4, 1)
    assert np.mean(losses[-k:]) < np.mean(losses[:k]), (
        losses[:3],
        losses[-3:],
    )


def test_accumulate_wrapper():
    import optax

    tx = accumulate(optax.sgd(1e-2), every=4)
    params = {"w": jnp.ones(3)}
    st = tx.init(params)
    g = {"w": jnp.ones(3)}
    p = params
    for i in range(4):
        updates, st = tx.update(g, st, p)
        p = optax.apply_updates(p, updates)
    # After 4 accumulation steps exactly one SGD step has been applied.
    np.testing.assert_allclose(np.asarray(p["w"]), 1.0 - 1e-2, rtol=1e-5)


def test_multibranch_heterogeneous_branch_fields():
    """One periodic branch (cell/edge_shifts) + one gas-phase branch:
    every device slot's batches must share ONE pytree structure (the
    optional-field union is computed over the concatenated branch
    datasets), so cross-slot stacking works — regression for the
    mixed-dataset structure divergence fixed in collate/ensure_fields."""
    import dataclasses

    mesh = make_mesh({"data": 8})
    molecules = _samples(40, 0, seed=1)
    crystals = [
        dataclasses.replace(
            s,
            edge_shifts=np.zeros((s.num_edges, 3), np.float32),
            cell=np.eye(3, dtype=np.float32),
        )
        for s in _samples(40, 1, seed=2)
    ]
    dpb = proportional_branch_split([40, 40], 8)
    loader = MultiBranchLoader(
        [molecules, crystals], dpb, batch_size=4, mesh=mesh, seed=0
    )
    structures = set()
    for stacked in loader:
        structures.add(str(jax.tree_util.tree_structure(stacked)))
        assert stacked.edge_shifts is not None
        assert stacked.cell is not None
    assert len(structures) == 1


def test_multibranch_run_prediction_public_api(tmp_path, monkeypatch):
    """run_prediction under the multibranch scheme (the reference runs
    prediction through the wrapper it trained with,
    run_prediction.py:62-71): per-branch per-sample collection through
    the trained state, and the disk-restored state must reproduce the
    in-memory predictions exactly."""
    import os

    from hydragnn_tpu.data.loader import split_dataset
    from hydragnn_tpu.runner import run_prediction, run_training

    config = {
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "SchNet",
                "radius": 2.5,
                "max_neighbours": 12,
                "num_gaussians": 8,
                "num_filters": 16,
                "hidden_dim": 16,
                "num_conv_layers": 2,
                "output_heads": {
                    "graph": {
                        "num_sharedlayers": 1,
                        "dim_sharedlayers": 16,
                        "num_headlayers": 1,
                        "dim_headlayers": [16],
                    }
                },
                "task_weights": [1.0],
            },
            "Variables_of_interest": {
                "input_node_features": [0, 1],
                "output_names": ["y"],
                "output_index": [0],
                "type": ["graph"],
                "output_dim": [1],
            },
            "Training": {
                "batch_size": 4,
                "num_epoch": 2,
                "Optimizer": {"type": "AdamW", "learning_rate": 5e-3},
                "Parallelism": {"scheme": "multibranch"},
            },
        }
    }
    sets = [
        split_dataset(_samples(40, 0, seed=21), 0.7),
        split_dataset(_samples(56, 1, seed=22), 0.7),
    ]
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        state, model, cfg, hist, full = run_training(
            config, datasets=sets, seed=0
        )
        err0, tasks0, trues0, preds0 = run_prediction(
            full, datasets=sets, state=state, model=model, cfg=cfg
        )
        # Keyed by branch: one (trues, preds) list per branch, sized to
        # that branch's test split.
        assert len(trues0) == len(preds0) == 2
        for bi, (_, _, te) in enumerate(sets):
            assert len(preds0[bi][0]) == len(te)
        assert np.isfinite(err0)
        # Disk restore through the public API reproduces exactly.
        err1, _, _, preds1 = run_prediction(full, datasets=sets)
        np.testing.assert_allclose(err0, err1, rtol=1e-6)
        for b0, b1 in zip(preds0, preds1):
            for p0, p1 in zip(b0, b1):
                np.testing.assert_allclose(p0, p1, rtol=1e-6, atol=1e-7)
    finally:
        os.chdir(cwd)
