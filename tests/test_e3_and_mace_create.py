"""Round-5 advisor satellites: pin the generation-time spherical
harmonics to the runtime basis, and lock the post-b015722 MACE
construction path (host-float64 Wigner D fit) end-to-end through
``models/create.py``.
"""

import numpy as np
import pytest

import tests._cpu  # noqa: F401


@pytest.mark.parametrize("l", [0, 1, 2, 3])
def test_sh_basis_np_matches_runtime_sh_basis(l):
    """_sh_basis_np (generation-time, host numpy float64) and sh_basis
    (runtime, JAX) evaluate the SAME constants; a normalization or
    ordering change to one must fail here before it silently
    desynchronizes Wigner-D/3j generation from runtime harmonics
    (ADVICE.md round 5, e3.py:290)."""
    import jax

    from hydragnn_tpu.ops.e3 import _sh_basis_np, sh_basis

    rng = np.random.default_rng(11)
    v = rng.normal(size=(64, 3))
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    want = _sh_basis_np(v, l)
    with jax.enable_x64(True):
        got = np.asarray(
            sh_basis(np.asarray(v, np.float64), l, normalize=False)
        )[:, l * l : (l + 1) * (l + 1)]
    assert got.dtype == np.float64
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


def test_mace_constructs_and_trains_through_create():
    """CPU regression lock for the live-TPU round-5 failure "Wigner D
    fit failed for l=1" (fixed in b015722 by evaluating the fit
    harmonics in host float64): build MACE end-to-end through the JSON
    config path (models/create.py) and take one finite train step —
    the path that generates every Wigner/3j constant."""
    import jax

    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data.graph import GraphSample
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.models.create import create_model_config, init_params
    from hydragnn_tpu.ops.neighbors import radius_graph
    from hydragnn_tpu.train.loop import make_train_step
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.state import create_train_state

    rng = np.random.default_rng(3)
    samples = []
    for _ in range(6):
        n = int(rng.integers(6, 10))
        pos = rng.uniform(0, 3.5, (n, 3)).astype(np.float32)
        samples.append(
            GraphSample(
                x=rng.integers(1, 9, size=(n, 1)).astype(np.float32),
                pos=pos,
                edge_index=radius_graph(pos, 3.0, max_neighbours=12),
                y_graph=np.array([rng.normal()], np.float32),
            )
        )
    config = {
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "MACE",
                "radius": 3.0,
                "max_neighbours": 12,
                "hidden_dim": 8,
                "num_conv_layers": 2,
                "num_radial": 4,
                "max_ell": 2,
                "node_max_ell": 2,
                "correlation": 2,
                "avg_num_neighbors": 8.0,
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
                "output_names": ["energy"],
                "output_index": [0],
                "type": ["graph"],
                "output_dim": [1],
            },
            "Training": {
                "batch_size": 6,
                "Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
            },
        }
    }
    config = update_config(config, samples)
    model, cfg = create_model_config(config)
    assert cfg.mpnn_type == "MACE"
    loader = GraphLoader(samples, 6)
    batch = next(iter(loader))
    params, bs = init_params(model, batch)
    tx = select_optimizer(config["NeuralNetwork"]["Training"])
    state = create_train_state(params, tx, bs)
    step = make_train_step(model, tx, cfg)
    state, tot, tasks = step(state, batch)
    assert np.isfinite(float(tot))


@pytest.mark.parametrize("l", [1, 2])
def test_wigner_d_fit_is_fp64_regardless_of_rot_dtype(l):
    """Regression for ``Wigner D fit failed for l=1: err 0.00599``, as
    a TPU run once failed: a float32 — or jax-array under default x64-off —
    rotation matrix must not drag the lstsq fit to fp32 (numpy defers
    ``v @ rot.T`` to ``jax.Array.__rmatmul__``), where the 1e-6 fp64
    verification tolerance is unreachable. The fit now coerces to
    float64 numpy up front; the fitted D must be identical whatever the
    input container/dtype, under BOTH x64 settings."""
    import jax

    from hydragnn_tpu.ops.e3 import _rotation_samples, wigner_d_from_sh

    rot64 = _rotation_samples()[0]
    want = wigner_d_from_sh(l, rot64)
    # orthogonal representation sanity
    assert np.allclose(want @ want.T, np.eye(2 * l + 1), atol=1e-8)

    import jax.numpy as jnp

    for cast in (
        lambda r: np.asarray(r, np.float32),
        lambda r: jnp.asarray(r, jnp.float32),  # x64-off default: f32
    ):
        got = wigner_d_from_sh(l, cast(rot64))
        # float32 only rounds the INPUT rotation (~1e-7 per entry); the
        # fit itself stays fp64, so the result matches to that level.
        assert np.abs(got - want).max() < 1e-5

    with jax.enable_x64(True):
        got = wigner_d_from_sh(l, jnp.asarray(rot64))
        assert np.array_equal(got, want)  # fp64 in, bitwise-equal fit
