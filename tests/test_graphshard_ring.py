"""Graph-dimension parallelism, ring attention over the sharded giant
graph. Split from tests/test_graphshard.py, whose fixture, constants
and reference it shares: ``--dist loadfile`` gives a file to one
worker, and this one test is three minutes of it there.
"""

import numpy as np

import tests._cpu  # noqa: F401

import jax

from hydragnn_tpu.parallel.graphshard import (
    init_params,
    reference_mpnn_forward,
    sharded_mpnn_forward,
)
from tests.test_graphshard import (  # noqa: F401  (setup: fixture)
    CUTOFF,
    LAYERS,
    NG,
    setup,
)


def test_ring_attention_matches_dense(setup):
    """Ring attention over the sharded giant graph must reproduce the
    single-device dense masked softmax attention exactly (online
    softmax blockwise == full softmax), including through autodiff."""
    mesh, shards, _ = setup
    heads = 2
    params = init_params(
        jax.random.PRNGKey(3), 4, 16, LAYERS, NG, attn_heads=heads
    )

    e_sharded = sharded_mpnn_forward(
        params, shards, mesh,
        cutoff=CUTOFF, num_gaussians=NG, num_layers=LAYERS,
        attn_heads=heads,
    )
    e_ref = reference_mpnn_forward(
        params,
        shards.x, shards.pos, shards.node_mask,
        shards.senders, shards.receivers, shards.edge_mask,
        cutoff=CUTOFF, num_gaussians=NG, num_layers=LAYERS,
        attn_heads=heads,
    )
    np.testing.assert_allclose(
        float(e_sharded), float(e_ref), rtol=2e-5
    )

    # Forces (grad wrt positions) agree through ppermute + online
    # softmax backward.
    import dataclasses

    g_sharded = jax.grad(
        lambda p: sharded_mpnn_forward(
            params, dataclasses.replace(shards, pos=p), mesh,
            cutoff=CUTOFF, num_gaussians=NG, num_layers=LAYERS,
            attn_heads=heads,
        )
    )(shards.pos)
    g_ref = jax.grad(
        lambda p: reference_mpnn_forward(
            params, shards.x, p, shards.node_mask,
            shards.senders, shards.receivers, shards.edge_mask,
            cutoff=CUTOFF, num_gaussians=NG, num_layers=LAYERS,
            attn_heads=heads,
        )
    )(shards.pos)
    np.testing.assert_allclose(
        np.asarray(g_sharded), np.asarray(g_ref), rtol=1e-3, atol=2e-5
    )
