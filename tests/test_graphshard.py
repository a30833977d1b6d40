"""Graph-dimension parallelism: a single giant graph sharded over the
8-device CPU mesh must produce the same energy, forces, and parameter
gradients as the single-device computation (the collectives are
all_gather / psum_scatter pairs, transposed correctly under autodiff).
"""

import numpy as np
import pytest

import tests._cpu  # noqa: F401

import jax
import jax.numpy as jnp

from hydragnn_tpu.ops.neighbors import radius_graph
from hydragnn_tpu.parallel.graphshard import (
    GraphShards,
    init_params,
    reference_mpnn_forward,
    sharded_mpnn_forward,
)
from hydragnn_tpu.parallel.mesh import make_mesh

CUTOFF = 2.5
NG = 12
LAYERS = 2


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(0)
    n = 200  # one "giant" graph
    pos = rng.uniform(0, 8.0, (n, 3)).astype(np.float32)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    ei = radius_graph(pos, CUTOFF, max_neighbours=24)
    mesh = make_mesh({"graph": 8})
    shards = GraphShards.build(x, pos, ei, 8).device_put(mesh)
    params = init_params(jax.random.PRNGKey(0), 4, 16, LAYERS, NG)
    return mesh, shards, params


def _ref(params, shards):
    return reference_mpnn_forward(
        params,
        shards.x,
        shards.pos,
        shards.node_mask,
        shards.senders,
        shards.receivers,
        shards.edge_mask,
        cutoff=CUTOFF,
        num_gaussians=NG,
        num_layers=LAYERS,
    )


def test_forward_matches_single_device(setup):
    mesh, shards, params = setup
    e_sharded = sharded_mpnn_forward(
        params, shards, mesh, cutoff=CUTOFF, num_gaussians=NG,
        num_layers=LAYERS,
    )
    e_ref = _ref(params, shards)
    np.testing.assert_allclose(
        float(e_sharded), float(e_ref), rtol=1e-5
    )


def test_forces_match_single_device(setup):
    mesh, shards, params = setup

    def e_sharded(pos):
        import dataclasses

        s = dataclasses.replace(shards, pos=pos)
        return sharded_mpnn_forward(
            params, s, mesh, cutoff=CUTOFF, num_gaussians=NG,
            num_layers=LAYERS,
        )

    def e_ref(pos):
        import dataclasses

        s = dataclasses.replace(shards, pos=pos)
        return _ref(params, s)

    f_sh = -jax.grad(e_sharded)(shards.pos)
    f_rf = -jax.grad(e_ref)(shards.pos)
    np.testing.assert_allclose(
        np.asarray(f_sh), np.asarray(f_rf), rtol=1e-4, atol=1e-5
    )


def test_param_grads_match_single_device(setup):
    mesh, shards, params = setup
    g_sh = jax.grad(
        lambda p: sharded_mpnn_forward(
            p, shards, mesh, cutoff=CUTOFF, num_gaussians=NG,
            num_layers=LAYERS,
        )
    )(params)
    g_rf = jax.grad(lambda p: _ref(p, shards))(params)
    flat_sh = jax.tree_util.tree_leaves(g_sh)
    flat_rf = jax.tree_util.tree_leaves(g_rf)
    for a, b in zip(flat_sh, flat_rf):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        )


def test_jit_compiles_with_collectives(setup):
    mesh, shards, params = setup
    f = jax.jit(
        lambda p, pos: sharded_mpnn_forward(
            p,
            __import__("dataclasses").replace(shards, pos=pos),
            mesh,
            cutoff=CUTOFF,
            num_gaussians=NG,
            num_layers=LAYERS,
        )
    )
    e1 = f(params, shards.pos)
    e2 = f(params, shards.pos + 0.0)
    np.testing.assert_allclose(float(e1), float(e2), rtol=1e-6)
