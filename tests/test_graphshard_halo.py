"""Graph-dimension parallelism, the halo-exchange path (ppermute of
boundary shells in place of the all-gather): same energy and forces as
the all-gather path and the single-device reference. Split from
tests/test_graphshard.py, whose constants and reference it shares:
``--dist loadfile`` gives a file to one worker, and the all-gather,
ring and halo tests together ran past five minutes there.
"""

import numpy as np
import pytest

import tests._cpu  # noqa: F401

import jax

from hydragnn_tpu.ops.neighbors import radius_graph
from hydragnn_tpu.parallel.graphshard import (
    GraphShards,
    init_params,
    reference_mpnn_forward,
    sharded_mpnn_forward,
)
from hydragnn_tpu.parallel.mesh import make_mesh
from tests.test_graphshard import CUTOFF, LAYERS, NG, _ref


@pytest.fixture(scope="module")
def halo_setup():
    """A locality-ordered giant graph (nodes sorted along z) — the
    regime halo exchange exists for: boundary shells are thin, so the
    halo is much smaller than the full node set."""
    from hydragnn_tpu.parallel.graphshard import HaloShards

    rng = np.random.default_rng(3)
    n = 240
    # Elongated box: each of the 8 z-slabs is deeper than the cutoff,
    # so only adjacent slabs exchange and the halo is a thin shell.
    pos = (
        rng.uniform(0, 1.0, (n, 3)) * np.array([6.0, 6.0, 24.0])
    ).astype(np.float32)
    pos = pos[np.argsort(pos[:, 2])]  # spatial ordering
    x = rng.normal(size=(n, 4)).astype(np.float32)
    ei = radius_graph(pos, CUTOFF, max_neighbours=24)
    mesh = make_mesh({"graph": 8})
    full = GraphShards.build(x, pos, ei, 8).device_put(mesh)
    halo = HaloShards.build(x, pos, ei, 8).device_put(mesh)
    params = init_params(jax.random.PRNGKey(1), 4, 16, LAYERS, NG)
    return mesh, full, halo, params


def test_halo_matches_allgather_and_reference(halo_setup):
    """Differential proof: the halo-exchange forward equals both the
    all-gather sharded forward and the single-device reference on the
    same graph."""
    from hydragnn_tpu.parallel.graphshard import halo_mpnn_forward

    mesh, full, halo, params = halo_setup
    kw = dict(cutoff=CUTOFF, num_gaussians=NG, num_layers=LAYERS)
    e_halo = float(halo_mpnn_forward(params, halo, mesh, **kw))
    e_gather = float(sharded_mpnn_forward(params, full, mesh, **kw))
    e_ref = float(_ref(params, full))
    np.testing.assert_allclose(e_halo, e_gather, rtol=1e-5)
    np.testing.assert_allclose(e_halo, e_ref, rtol=1e-5)


def test_halo_forces_match(halo_setup):
    """Forces = -grad wrt positions must flow through the ppermute
    halo exchange (transpose = reverse ppermute)."""
    import dataclasses

    from hydragnn_tpu.parallel.graphshard import halo_mpnn_forward

    mesh, full, halo, params = halo_setup
    kw = dict(cutoff=CUTOFF, num_gaussians=NG, num_layers=LAYERS)

    g_halo = jax.grad(
        lambda p: halo_mpnn_forward(
            params, dataclasses.replace(halo, pos=p), mesh, **kw
        )
    )(halo.pos)
    g_ref = jax.grad(
        lambda p: reference_mpnn_forward(
            params, full.x, p, full.node_mask, full.senders,
            full.receivers, full.edge_mask, **kw
        )
    )(full.pos)
    np.testing.assert_allclose(
        np.asarray(g_halo), np.asarray(g_ref), rtol=1e-4, atol=1e-5
    )


def test_halo_memory_model(halo_setup):
    """The whole point: per-device rows materialized by a layer must be
    well below the full node count on a locality-ordered graph (the
    all-gather path pays N_pad rows per device)."""
    _, _, halo, _ = halo_setup
    assert halo.halo_rows < halo.num_nodes_padded / 2
    # Cutoff 2.5 on a z-sorted 10A box: only adjacent shards exchange.
    assert len(halo.hops) <= 2
