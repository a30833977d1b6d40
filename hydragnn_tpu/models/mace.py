"""MACE stack: E(3)-equivariant n-body cluster-expansion MPNN.

TPU-native reimplementation of the reference MACE integration
(hydragnn/models/MACEStack.py:74-577 and
hydragnn/utils/model/mace_utils/modules/blocks.py): one-hot Z in 1..118
node attributes (MACEStack.py:510-541), per-graph position centering
(:436-443), Bessel radial embedding with polynomial cutoff and optional
Agnesi/Soft distance transforms (blocks.py:141), spherical-harmonic edge
attributes (MACEStack.py:155-162), RealAgnosticAttResidual interaction
(blocks.py:301-404), symmetric-contraction product basis (blocks.py:181),
and per-layer multihead readouts summed across layers (MACEStack.py:375-421
— wired through ``per_layer_readouts`` in the multihead core).

Feature layout: equivariant node features are dense [N, C, M] arrays
with M = (lmax+1)^2 concatenated real-spherical-harmonic components —
the "reshaped irreps" layout (reference irreps_tools.py:15-106) used
*everywhere*, so every linear is a batched per-l matmul on the MXU and
no irreps bookkeeping survives to runtime. Deviation from the reference:
optional scalar edge attributes condition the radial MLP instead of
being appended as extra l=0 tensor-product inputs (functionally
equivalent conditioning; static shapes stay simple).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from hydragnn_tpu.data.graph import GraphBatch
from hydragnn_tpu.models.layers import MLP, NUM_ELEMENTS, species_onehot
from hydragnn_tpu.models.spec import ModelConfig
from hydragnn_tpu.ops import (
    agnesi_transform,
    bessel_basis,
    chebyshev_basis,
    edge_vectors_and_lengths,
    gaussian_smearing,
    polynomial_cutoff,
    segment_mean,
    segment_sum,
    soft_transform,
)
from hydragnn_tpu.ops.e3 import real_wigner_3j, sh_basis, sh_dim
from hydragnn_tpu.ops.symmetric_contraction import SymmetricContraction

# Covalent radii in Angstrom, index = atomic number Z (0 unused), Cordero
# et al. 2008 / Pyykkoe for the heavy elements — the table the reference's
# Agnesi/Soft transforms read via ase.data.covalent_radii
# (mace_utils/modules/radial.py:168-173).
COVALENT_RADII = np.array(
    [
        0.20, 0.31, 0.28, 1.28, 0.96, 0.84, 0.76, 0.71, 0.66, 0.57, 0.58,
        1.66, 1.41, 1.21, 1.11, 1.07, 1.05, 1.02, 1.06, 2.03, 1.76, 1.70,
        1.60, 1.53, 1.39, 1.39, 1.32, 1.26, 1.24, 1.32, 1.22, 1.22, 1.20,
        1.19, 1.20, 1.20, 1.16, 2.20, 1.95, 1.90, 1.75, 1.64, 1.54, 1.47,
        1.46, 1.42, 1.39, 1.45, 1.44, 1.42, 1.39, 1.39, 1.38, 1.39, 1.40,
        2.44, 2.15, 2.07, 2.04, 2.03, 2.01, 1.99, 1.98, 1.98, 1.96, 1.94,
        1.92, 1.92, 1.89, 1.90, 1.87, 1.87, 1.75, 1.70, 1.62, 1.51, 1.44,
        1.41, 1.36, 1.36, 1.32, 1.45, 1.46, 1.48, 1.40, 1.50, 1.50, 2.60,
        2.21, 2.15, 2.06, 2.00, 1.96, 1.90, 1.87, 1.80, 1.69, 1.66, 1.68,
        1.68, 1.65, 1.67, 1.73, 1.76, 1.61, 1.57, 1.49, 1.43, 1.41, 1.34,
        1.29, 1.28, 1.21, 1.22, 1.36, 1.43, 1.62, 1.75, 1.65, 1.57,
    ]
)


def _blk(l: int) -> slice:
    return slice(l * l, (l + 1) * (l + 1))


class IrrepsLinear(nn.Module):
    """Per-l channel-mixing linear [N, C_in, M_in] -> [N, C_out, M_out].

    The counterpart of e3nn o3.Linear with uniform multiplicities: only
    same-l paths exist; each is a channel matmul with 1/sqrt(C_in)
    normalization. l blocks present in the input but not the output (or
    vice versa) are dropped (or zero-filled).
    """

    lmax_in: int
    lmax_out: int
    c_out: int

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        n, c_in, _ = x.shape
        outs = []
        for l in range(self.lmax_out + 1):
            if l <= self.lmax_in:
                w = self.param(
                    f"w{l}",
                    nn.initializers.normal(stddev=1.0),
                    (c_in, self.c_out),
                )
                blk = x[:, :, _blk(l)]
                outs.append(
                    jnp.einsum("nci,co->noi", blk, w) / math.sqrt(c_in)
                )
            else:
                outs.append(
                    jnp.zeros((n, self.c_out, 2 * l + 1), x.dtype)
                )
        return jnp.concatenate(outs, axis=-1)


def tp_paths(lmax_node: int, lmax_edge: int, lmax_out: int):
    """Channelwise tensor-product paths (l1, l2, l3) with CG tensors."""
    paths = []
    for l1 in range(lmax_node + 1):
        for l2 in range(lmax_edge + 1):
            for l3 in range(abs(l1 - l2), min(l1 + l2, lmax_out) + 1):
                paths.append((l1, l2, l3))
    return paths


def channelwise_tp(
    x: jax.Array,  # [E, C, M1] gathered sender features
    sh: jax.Array,  # [E, M2] edge spherical harmonics
    weights: jax.Array,  # [E, P, C] per-edge per-path weights
    paths,
    lmax_out: int,
) -> jax.Array:
    """MACE's 'uvu' connected tensor product (o3.TensorProduct with
    per-edge external weights, reference blocks.py:314-326).

    Returns [E, C, M3]. Each output l3 block averages its contributing
    paths with 1/sqrt(n_paths) normalization.
    """
    return jnp.concatenate(
        _tp_path_blocks(x, sh, weights, paths, lmax_out), axis=-1
    )


def _tp_path_blocks(x, sh, weights, paths, lmax_out):
    """Shared per-path computation for both channelwise TP entry
    points: one einsum per (l1, l2, l3) path with the per-edge
    per-channel weight FUSED into the contraction (no separate scaled
    [E, C, 2l3+1] intermediate), accumulated per output-l3 block in
    edge space, each block normalized by 1/sqrt(paths into it)."""
    e, c, _ = x.shape
    counts = np.zeros(lmax_out + 1)
    for _, _, l3 in paths:
        counts[l3] += 1
    out_blocks = [
        jnp.zeros((e, c, 2 * l + 1), x.dtype) for l in range(lmax_out + 1)
    ]
    for p, (l1, l2, l3) in enumerate(paths):
        cg = jnp.asarray(real_wigner_3j(l1, l2, l3), x.dtype)
        term = jnp.einsum(
            "abk,eca,eb,ec->eck",
            cg,
            x[:, :, _blk(l1)],
            sh[:, _blk(l2)],
            weights[:, p, :],
        )
        out_blocks[l3] = out_blocks[l3] + term
    return [
        b / math.sqrt(max(counts[l], 1.0))
        for l, b in enumerate(out_blocks)
    ]


def channelwise_tp_aggregate(
    x: jax.Array,  # [E, C, M1] gathered sender features
    sh: jax.Array,  # [E, M2] edge spherical harmonics
    weights: jax.Array,  # [E, P, C] per-edge per-path weights
    paths,
    lmax_out: int,
    batch: GraphBatch,
) -> jax.Array:
    """``channelwise_tp`` + receiver aggregation as ONE op
    [E, C, M1] -> [N, C, M3].

    The concatenated edge message goes through a single
    ``aggregate_receivers`` call, so MACE rides the same dispatch as
    every other stack: the planned Pallas sorted-segment kernel when
    the batch carries a block plan (collate with_segment_plan=True) on
    TPU, the XLA scatter otherwise — one scatter of width C*M3 total
    (per-path scattering would multiply scatter volume ~5.7x at
    lmax=2). On the planned path the plan gather runs INSIDE the
    kernel (edge_pipeline_planned's aligned-tile staging), so the
    wide [E, C*M3] message streams HBM->VMEM exactly once — at MACE's
    message width that is the largest single-tensor round-trip the
    fused pipeline removes. The weight multiply is fused into each path einsum
    (_tp_path_blocks), which also drops the per-path scaled
    intermediates of the standalone op."""
    from hydragnn_tpu.ops.segment import aggregate_receivers

    e, c, _ = x.shape
    mji = jnp.concatenate(
        _tp_path_blocks(x, sh, weights, paths, lmax_out), axis=-1
    )
    return aggregate_receivers(mji.reshape(e, -1), batch).reshape(
        batch.num_nodes, c, -1
    )


class MACEInteraction(nn.Module):
    """RealAgnosticAttResidualInteractionBlock (blocks.py:301-404):
    linear_up, scalar down-projection feeding the radial MLP together
    with the Bessel edge features, channelwise TP with the edge SH,
    sum-aggregation scaled by 1/avg_num_neighbors, output linear, and a
    linear skip to the hidden irreps."""

    channels: int
    lmax_node_in: int  # l content of incoming node features
    lmax_edge: int  # sh lmax (max_ell)
    lmax_hidden: int  # hidden/skip l content (node_max_ell; 0 last layer)
    avg_num_neighbors: float
    radial_dim: int

    @nn.compact
    def __call__(
        self,
        node_feats: jax.Array,  # [N, C, M_in]
        edge_sh: jax.Array,  # [E, M_e]
        edge_feats: jax.Array,  # [E, R] radial features
        batch: GraphBatch,
    ) -> Tuple[jax.Array, jax.Array]:
        c = self.channels
        snd, rcv = batch.senders, batch.receivers

        sc = IrrepsLinear(
            lmax_in=self.lmax_node_in,
            lmax_out=self.lmax_hidden,
            c_out=c,
            name="skip_linear",
        )(node_feats)
        up = IrrepsLinear(
            lmax_in=self.lmax_node_in,
            lmax_out=self.lmax_node_in,
            c_out=c,
            name="linear_up",
        )(node_feats)
        down = nn.Dense(c, use_bias=False, name="linear_down")(
            node_feats[:, :, 0]
        )

        paths = tp_paths(self.lmax_node_in, self.lmax_edge, self.lmax_edge)
        aug = jnp.concatenate(
            [edge_feats, down[snd], down[rcv]], axis=-1
        )
        rad = MLP(
            features=(self.radial_dim,) * 3 + (len(paths) * c,),
            act="silu",
            final_activation=False,
            name="conv_tp_weights",
        )(aug)
        w = rad.reshape(rad.shape[0], len(paths), c)
        w = w * batch.edge_mask[:, None, None].astype(w.dtype)

        # TP + aggregation as one op: weight-fused path einsums, one
        # plan-aware scatter (see channelwise_tp_aggregate).
        msg = channelwise_tp_aggregate(
            up[snd], edge_sh, w, paths, self.lmax_edge, batch
        )
        msg = msg / self.avg_num_neighbors
        msg = IrrepsLinear(
            lmax_in=self.lmax_edge,
            lmax_out=self.lmax_edge,
            c_out=c,
            name="linear",
        )(msg)
        return msg, sc


class MACELayer(nn.Module):
    """Interaction + product basis + sizing (reference get_conv,
    MACEStack.py:280-377)."""

    channels: int
    lmax_node_in: int
    lmax_edge: int
    lmax_hidden: int
    correlation: int
    avg_num_neighbors: float
    radial_dim: int
    use_sc: bool = True

    @nn.compact
    def __call__(
        self,
        node_feats: jax.Array,
        node_onehot: jax.Array,
        edge_sh: jax.Array,
        edge_feats: jax.Array,
        batch: GraphBatch,
    ) -> jax.Array:
        msg, sc = MACEInteraction(
            channels=self.channels,
            lmax_node_in=self.lmax_node_in,
            lmax_edge=self.lmax_edge,
            lmax_hidden=self.lmax_hidden,
            avg_num_neighbors=self.avg_num_neighbors,
            radial_dim=self.radial_dim,
            name="interaction",
        )(node_feats, edge_sh, edge_feats, batch)
        prod = SymmetricContraction(
            lmax_in=self.lmax_edge,
            lmax_out=self.lmax_hidden,
            correlation=self.correlation,
            num_elements=NUM_ELEMENTS,
            name="product",
        )(msg, node_onehot)
        prod = IrrepsLinear(
            lmax_in=self.lmax_hidden,
            lmax_out=self.lmax_hidden,
            c_out=self.channels,
            name="product_linear",
        )(prod)
        out = prod + sc if self.use_sc else prod
        # sizing linear (hidden -> output irreps; same dims here)
        return IrrepsLinear(
            lmax_in=self.lmax_hidden,
            lmax_out=self.lmax_hidden,
            c_out=self.channels,
            name="sizing",
        )(out)


class MACEStack(nn.Module):
    """MACE encoder following the framework stack protocol, with
    per-layer readouts handled by the multihead core."""

    cfg: ModelConfig
    norm_kind = "none"
    inter_layer_activation = False
    per_layer_readouts = True

    def setup(self):
        cfg = self.cfg
        if cfg.radius is None or cfg.num_radial is None:
            raise ValueError("MACE requires radius and num_radial")
        if cfg.max_ell is None or cfg.node_max_ell is None:
            raise ValueError("MACE requires max_ell and node_max_ell")
        if cfg.max_ell < 1 or cfg.node_max_ell < 1:
            raise ValueError("MACE requires max_ell >= 1, node_max_ell >= 1")
        c = cfg.hidden_dim
        radial_dim = max(1, math.ceil(c / 3.0))
        corr = cfg.correlation if cfg.correlation is not None else 2
        ann = (
            cfg.avg_num_neighbors
            if cfg.avg_num_neighbors
            else 1.0
        )
        layers = []
        for i in range(cfg.num_conv_layers):
            last = i == cfg.num_conv_layers - 1
            layers.append(
                MACELayer(
                    channels=c,
                    lmax_node_in=0 if i == 0 else cfg.node_max_ell,
                    lmax_edge=cfg.max_ell,
                    lmax_hidden=0 if last else cfg.node_max_ell,
                    correlation=corr,
                    avg_num_neighbors=ann,
                    radial_dim=radial_dim,
                    use_sc=True,
                    name=f"layer_{i}",
                )
            )
        self.layers = layers
        self.node_embedding = nn.Dense(
            c, use_bias=False, name="node_embedding"
        )

    def embed(
        self, batch: GraphBatch
    ) -> Tuple[jax.Array, Optional[jax.Array], Dict[str, Any]]:
        cfg = self.cfg
        if batch.pos is None:
            raise ValueError(
                "MACE requires node positions (batch.pos) to be set."
            )
        # Per-graph position centering (reference MACEStack.py:436-443).
        pos = batch.pos
        mean_pos = segment_mean(
            pos, batch.node_graph_idx, batch.num_graphs, mask=batch.node_mask
        )
        pos = pos - mean_pos[batch.node_graph_idx]

        vec, length = edge_vectors_and_lengths(
            pos, batch.senders, batch.receivers, batch.edge_shifts
        )
        edge_sh = sh_basis(vec, cfg.max_ell, normalize=True)
        onehot = species_onehot(batch.x, batch.node_mask)

        # Radial embedding (reference RadialEmbeddingBlock, blocks.py:141):
        # the cutoff sees the RAW length; the basis sees the (optionally)
        # transformed length, with per-edge r_0 from covalent radii.
        d = length
        if cfg.distance_transform in ("Agnesi", "Soft"):
            z = jnp.clip(jnp.round(batch.x[:, 0]), 1, NUM_ELEMENTS).astype(
                jnp.int32
            )
            rc = jnp.asarray(COVALENT_RADII, d.dtype)[z]
            r_uv = rc[batch.senders] + rc[batch.receivers]
            if cfg.distance_transform == "Agnesi":
                d = agnesi_transform(d, 0.5 * r_uv)
            else:
                d = soft_transform(d, 0.25 * r_uv)
        p = cfg.envelope_exponent if cfg.envelope_exponent else 5
        if cfg.radial_type in (None, "bessel"):
            rb = bessel_basis(d, cfg.radius, cfg.num_radial)
        elif cfg.radial_type == "chebyshev":
            rb = chebyshev_basis(d, cfg.radius, cfg.num_radial)
        elif cfg.radial_type == "gaussian":
            rb = gaussian_smearing(d, 0.0, cfg.radius, cfg.num_radial)
        else:
            raise ValueError(f"Unknown radial_type {cfg.radial_type}")
        edge_feats = rb * polynomial_cutoff(length, cfg.radius, p)[:, None]
        if batch.edge_attr is not None:
            # Deviation: scalar edge attrs condition the radial MLP.
            edge_feats = jnp.concatenate(
                [edge_feats, batch.edge_attr], axis=-1
            )

        node_feats = self.node_embedding(onehot)[:, :, None]  # [N, C, 1]
        extras = {
            "edge_sh": edge_sh,
            "edge_feats": edge_feats,
            "onehot": onehot,
            "readout0_input": onehot,
        }
        # inv = scalar channels; equiv carries the flattened l>0 content
        # (empty at embedding time).
        return node_feats[:, :, 0], None, extras

    def conv(
        self,
        i: int,
        inv: jax.Array,
        equiv: Optional[jax.Array],
        batch: GraphBatch,
        extras: Dict[str, Any],
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        cfg = self.cfg
        c = cfg.hidden_dim
        if equiv is None or equiv.shape[-1] == 0:
            node_feats = inv[:, :, None]
        else:
            m_in = sh_dim(cfg.node_max_ell)
            node_feats = jnp.concatenate(
                [inv[:, :, None], equiv.reshape(-1, c, m_in - 1)], axis=-1
            )
        out = self.layers[i](
            node_feats,
            extras["onehot"],
            extras["edge_sh"],
            extras["edge_feats"],
            batch,
        )
        inv = out[:, :, 0]
        equiv = out[:, :, 1:].reshape(out.shape[0], -1)
        return inv, equiv
