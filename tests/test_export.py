"""AOT inference export (hydragnn_tpu/export.py): serialized-artifact
roundtrip against the live model, file save/load, and the MLIP
energy+forces serving form. The reference analog is its fused-inference
deployment (run-scripts/SC26_fused_inference*.sh).
"""

import numpy as np

import tests._cpu  # noqa: F401

import jax
import jax.numpy as jnp

from hydragnn_tpu.data.graph import GraphSample, PadSpec, collate
from hydragnn_tpu.models.create import create_model, init_params
from hydragnn_tpu.models.spec import BranchSpec, HeadSpec, ModelConfig
from hydragnn_tpu.train.state import create_train_state


def _setup(enable_mlip=False):
    import optax

    rng = np.random.default_rng(0)
    samples = []
    for _ in range(6):
        n = int(rng.integers(5, 9))
        pos = rng.uniform(0, 3.0, (n, 3)).astype(np.float32)
        ei = np.stack(
            [np.repeat(np.arange(n), 2), rng.integers(0, n, 2 * n)]
        )
        samples.append(
            GraphSample(
                x=rng.normal(size=(n, 1)).astype(np.float32),
                pos=pos,
                edge_index=ei.astype(np.int64),
                y_graph=np.array([float(pos.sum())], np.float32),
                energy=float(pos.sum()),
                forces=rng.normal(size=(n, 3)).astype(np.float32),
            )
        )
    cfg = ModelConfig(
        mpnn_type="SchNet",
        input_dim=1,
        hidden_dim=8,
        num_conv_layers=2,
        heads=(HeadSpec("e", "graph", 1),),
        graph_branches=(BranchSpec(),),
        node_branches=(),
        task_weights=(1.0,),
        radius=3.0,
        num_gaussians=8,
        num_filters=8,
        graph_pooling="add",
        enable_interatomic_potential=enable_mlip,
    )
    model = create_model(cfg)
    spec = PadSpec.for_samples(samples)
    batch = collate(samples[:4], spec)
    params, batch_stats = init_params(model, batch)
    state = create_train_state(params, optax.adam(1e-3), batch_stats)
    batch2 = collate(samples[2:6], spec)  # same bucket shapes
    return model, cfg, state, batch, batch2


def test_export_roundtrip_matches_live_model(tmp_path):
    from hydragnn_tpu.export import export_inference, load_exported

    model, cfg, state, batch, batch2 = _setup()
    path = str(tmp_path / "model.hlo")
    blob = export_inference(model, cfg, state, batch, path=path)
    assert len(blob) > 100
    # cross-backend serving: the artifact must record both platforms
    from jax import export as jax_export

    assert set(jax_export.deserialize(blob).platforms) >= {"cpu", "tpu"}
    fn = load_exported(path)

    live = model.apply(
        {"params": state.params, "batch_stats": state.batch_stats},
        batch2,
        train=False,
    )
    exported = fn(batch2)
    assert len(exported) == len(live)
    np.testing.assert_allclose(
        np.asarray(exported[0]), np.asarray(live[0]), rtol=1e-5, atol=1e-6
    )


def test_export_bytes_source():
    from hydragnn_tpu.export import export_inference, load_exported

    model, cfg, state, batch, _ = _setup()
    blob = export_inference(model, cfg, state, batch)
    fn = load_exported(blob)
    out = fn(batch)
    assert np.isfinite(np.asarray(out[0])).all()


def test_export_mlip_energy_forces():
    """with_forces bakes the grad-of-energy path into the artifact."""
    from hydragnn_tpu.export import export_inference, load_exported
    from hydragnn_tpu.train.mlip import energy_and_forces

    model, cfg, state, batch, batch2 = _setup(enable_mlip=True)
    blob = export_inference(
        model, cfg, state, batch, with_forces=True
    )
    fn = load_exported(blob)
    ge, forces = fn(batch2)
    ge_live, forces_live, _ = energy_and_forces(
        model,
        {"params": state.params, "batch_stats": state.batch_stats},
        batch2,
        cfg,
        train=False,
    )
    np.testing.assert_allclose(
        np.asarray(ge), np.asarray(ge_live), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(forces), np.asarray(forces_live), rtol=1e-4, atol=1e-5
    )


def test_export_roundtrip_packed_shape_bit_equal():
    """Packed-shape coverage (ISSUE 11): on a bin-packed budget-shaped
    GraphBatch the exported artifact is BIT-EQUAL to the live jitted
    forward — the serving engine AOT-compiles the same make_forward
    program, so this is the exported-forward contract the serving path
    rides (docs/SERVING.md)."""
    from hydragnn_tpu.data.graph import PackSpec
    from hydragnn_tpu.export import (
        export_inference,
        load_exported,
        make_forward,
    )

    model, cfg, state, batch, _ = _setup()
    # a packed budget spec: lane-rounded, NOT a ladder point, with
    # generous slack slots like real FFD tail bins
    rng = np.random.default_rng(5)
    samples = []
    for _ in range(5):
        n = int(rng.integers(5, 9))
        pos = rng.uniform(0, 3.0, (n, 3)).astype(np.float32)
        ei = np.stack(
            [np.repeat(np.arange(n), 2), rng.integers(0, n, 2 * n)]
        )
        samples.append(
            GraphSample(
                x=rng.normal(size=(n, 1)).astype(np.float32),
                pos=pos,
                edge_index=ei.astype(np.int64),
                y_graph=np.array([float(pos.sum())], np.float32),
                energy=float(pos.sum()),
                forces=rng.normal(size=(n, 3)).astype(np.float32),
            )
        )
    budget = PackSpec(num_nodes=56, num_edges=96, num_graphs=7)
    packed = collate(samples, budget.pad_spec())
    blob = export_inference(model, cfg, state, packed)
    fn = load_exported(blob)

    variables = {
        "params": jax.device_get(state.params),
        "batch_stats": jax.device_get(state.batch_stats),
    }
    live = jax.jit(make_forward(model, cfg, variables))(packed)
    exported = fn(packed)
    assert len(exported) == len(live)
    for a, b in zip(exported, live):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_export_packed_edge_mask_slots_are_inert():
    """The artifact's masking contract on packed shapes: rewriting the
    PADDED edge slots (redirecting them from the padding node onto
    real nodes, edge_mask still False) must not move a single output
    bit — masked contributions are exact zeros, so real graphs cannot
    see them. A failure here means a model consumed padding edges
    through the point-at-padding-node convention instead of the
    mask."""
    import dataclasses

    from hydragnn_tpu.data.graph import PackSpec
    from hydragnn_tpu.export import export_inference, load_exported

    model, cfg, state, _, _ = _setup()
    rng = np.random.default_rng(7)
    samples = []
    for _ in range(4):
        n = int(rng.integers(5, 9))
        pos = rng.uniform(0, 3.0, (n, 3)).astype(np.float32)
        ei = np.stack(
            [np.repeat(np.arange(n), 2), rng.integers(0, n, 2 * n)]
        )
        samples.append(
            GraphSample(
                x=rng.normal(size=(n, 1)).astype(np.float32),
                pos=pos,
                edge_index=ei.astype(np.int64),
                y_graph=np.array([float(pos.sum())], np.float32),
                energy=float(pos.sum()),
                forces=rng.normal(size=(n, 3)).astype(np.float32),
            )
        )
    budget = PackSpec(num_nodes=48, num_edges=80, num_graphs=6)
    packed = collate(samples, budget.pad_spec())
    blob = export_inference(model, cfg, state, packed)
    fn = load_exported(blob)
    base = fn(packed)

    e_real = sum(s.num_edges for s in samples)
    senders = np.array(packed.senders)
    receivers = np.array(packed.receivers)
    n_pad_edges = senders.shape[0] - e_real
    assert n_pad_edges > 0, "fixture must exercise padded edge slots"
    senders[e_real:] = rng.integers(0, 5, n_pad_edges)
    receivers[e_real:] = rng.integers(0, 5, n_pad_edges)
    poked = dataclasses.replace(
        packed,
        senders=jnp.asarray(senders),
        receivers=jnp.asarray(receivers),
    )
    out = fn(poked)
    for a, b in zip(out, base):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_export_cli_from_checkpoint(tmp_path):
    """python -m hydragnn_tpu.export <config> <out>: restores the run's
    checkpoint and writes a servable artifact (the checkpoint-to-
    deployment workflow, no retraining)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = f"""
import json, sys; sys.path.insert(0, {repo!r})
import hydragnn_tpu
from hydragnn_tpu.data.synthetic import deterministic_graph_data
deterministic_graph_data("dataset/demo", number_configurations=40, seed=1)
config = json.load(open({repo!r} + "/tests/inputs/ci.json"))
config["Dataset"]["path"] = {{"total": "dataset/demo"}}
config["NeuralNetwork"]["Training"]["num_epoch"] = 2
hydragnn_tpu.run_training(config)
json.dump(config, open("cfg.json", "w"))
"""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=repo,
    )
    r = subprocess.run(
        [sys.executable, "-c", script],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=240,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    r = subprocess.run(
        [sys.executable, "-m", "hydragnn_tpu.export", "cfg.json",
         "model.hlo"],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=240,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    info = json.loads(r.stdout.strip().splitlines()[-1])
    assert info["artifact"] == "model.hlo"
    assert (tmp_path / "model.hlo").stat().st_size == info["bytes"] > 100
