"""Equivariant MPNN stacks: EGNN, PaiNN, PNAEq.

TPU-native reimplementations of:
  - EGCLStack (hydragnn/models/EGCLStack.py:22-300): E(n)-equivariant
    conv — edge MLP of [x_i, x_j, |d_ij|, edge_attr], coordinate update
    from gated unit displacements (mean-aggregated), node MLP over
    summed edge features. Coordinates are only updated on non-last
    layers (EGCLStack.py:70-90).
  - PAINNStack (hydragnn/models/PAINNStack.py:27-352): scalar + vector
    node channels; message = sinc-RBF filter x cutoff gating a scalar
    MLP, split into three gates (vector-state gate, edge-direction
    gate, scalar message); update = U/V linear maps on the vector
    channel with norm/inner-product mixing (PAINNStack.py:275-330).
    The edge-direction gate multiplies r_ij / d_ij^2, where the paper
    (Schuett et al., arXiv:2102.03150, eq. 8) has the unit vector
    r_ij / d_ij: HydraGNN divides the normalised displacement by the
    distance once more (PAINNStack.py:255-258), and this stack keeps it.
  - PNAEqStack (hydragnn/models/PNAEqStack.py:41-538): the PaiNN layout
    with PNA multi-aggregator/degree-scaler aggregation of the scalar
    message channel (aggregators mean/min/max/std x scalers identity/
    amplification/attenuation/linear/inverse_linear).

All segment reductions are masked over padded edges so results on a
bucketed ``GraphBatch`` equal results on the unpadded graphs.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from hydragnn_tpu.data.graph import GraphBatch
from hydragnn_tpu.models.layers import MLP, species_onehot
from hydragnn_tpu.models.pna import _deg_stats, pna_scaled_aggregate
from hydragnn_tpu.models.spec import ModelConfig
from hydragnn_tpu.ops import (
    cosine_cutoff,
    edge_vectors_and_lengths,
    segment_mean,
    segment_sum,
    sinc_basis,
)
from hydragnn_tpu.ops.segment import aggregate_receivers
from hydragnn_tpu.utils import tracer as tr


# ----------------------------------------------------------------------
# EGNN
# ----------------------------------------------------------------------


class E_GCL(nn.Module):
    """One E(n)-equivariant graph conv layer (reference E_GCL,
    hydragnn/models/EGCLStack.py:175-300)."""

    out_dim: int
    hidden_dim: int
    edge_dim: Optional[int] = None
    equivariant: bool = False

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        pos: Optional[jax.Array],
        batch: GraphBatch,
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        snd, rcv = batch.senders, batch.receivers
        unit, length = edge_vectors_and_lengths(
            pos, snd, rcv, batch.edge_shifts, normalize=True, eps=1.0
        )
        parts = [x[snd], x[rcv], length[:, None]]
        if self.edge_dim and batch.edge_attr is not None:
            parts.append(batch.edge_attr)
        edge_feat = MLP(
            features=(self.hidden_dim, self.hidden_dim),
            act="relu",
            final_activation=True,
            name="edge_mlp",
        )(jnp.concatenate(parts, axis=-1))

        if self.equivariant:
            # Coordinate channel (reference coord_model, EGCLStack.py:267-275):
            # gated unit displacements, mean-aggregated at the sender side.
            gate = nn.Dense(self.hidden_dim, name="coord_dense")(edge_feat)
            gate = jax.nn.relu(gate)
            gate = nn.Dense(
                1,
                use_bias=False,
                kernel_init=nn.initializers.variance_scaling(
                    1e-6, "fan_avg", "uniform"
                ),
                name="coord_gate",
            )(gate)
            trans = jnp.clip(unit * jnp.tanh(gate), -100.0, 100.0)
            agg = segment_mean(trans, snd, batch.num_nodes, mask=batch.edge_mask)
            pos = pos + agg

        agg = segment_sum(edge_feat, snd, batch.num_nodes, mask=batch.edge_mask)
        out = MLP(
            features=(self.hidden_dim, self.out_dim),
            act="relu",
            name="node_mlp",
        )(jnp.concatenate([x, agg], axis=-1))
        return out, pos


class EGCLStack(nn.Module):
    """EGNN stack (reference EGCLStack, hydragnn/models/EGCLStack.py:22)."""

    cfg: ModelConfig
    norm_kind = "none"

    def setup(self):
        cfg = self.cfg
        self.convs = [
            E_GCL(
                out_dim=cfg.hidden_dim,
                hidden_dim=cfg.hidden_dim,
                edge_dim=cfg.edge_dim,
                equivariant=cfg.equivariance
                and i != cfg.num_conv_layers - 1,
                name=f"conv_{i}",
            )
            for i in range(cfg.num_conv_layers)
        ]

    def embed(
        self, batch: GraphBatch
    ) -> Tuple[jax.Array, Optional[jax.Array], Dict[str, Any]]:
        if batch.pos is None:
            raise ValueError("EGNN requires node positions")
        return batch.x, batch.pos, {}

    def conv(self, i, inv, equiv, batch, extras):
        return self.convs[i](inv, equiv, batch)


# ----------------------------------------------------------------------
# PaiNN
# ----------------------------------------------------------------------


class PainnMessage(nn.Module):
    """PaiNN message block (reference PainnMessage,
    hydragnn/models/PAINNStack.py:194-272)."""

    node_size: int
    num_radial: int
    cutoff: float
    edge_dim: Optional[int] = None

    @nn.compact
    def __call__(
        self,
        s: jax.Array,
        v: jax.Array,
        batch: GraphBatch,
        unit: jax.Array,
        dist: jax.Array,
    ) -> Tuple[jax.Array, jax.Array]:
        snd, rcv = batch.senders, batch.receivers
        F = self.node_size

        scalar_out = MLP(
            features=(F, 3 * F), act="silu", name="scalar_message_mlp"
        )(s)
        # The edge work, timed under one scope: the radial filter, both
        # sender gathers, the products and both receiver sums.
        with tr.scope("vector_message"):
            rbf = sinc_basis(dist, self.cutoff, self.num_radial)
            filt = nn.Dense(3 * F, name="filter_layer")(rbf)
            filt = filt * cosine_cutoff(dist, self.cutoff)[:, None]
            if self.edge_dim and batch.edge_attr is not None:
                filt = filt * MLP(
                    features=(F, 3 * F), act="silu", name="edge_filter"
                )(batch.edge_attr)
            filter_out = filt * scalar_out[snd]
            gate_v, gate_e, msg_s = jnp.split(filter_out, 3, axis=-1)

            # Vector message: gated neighbor vectors + gated edge
            # directions over d once more (module docstring).
            msg_v = v[snd] * gate_v[:, None, :] + gate_e[:, None, :] * (
                unit / jnp.maximum(dist, 1e-9)[:, None]
            )[:, :, None]

            n = batch.num_nodes
            # Both channels ride the planned-kernel dispatch. The [E, 3, F]
            # vector message folds its 3-axis into the feature dim — the
            # reduce is linear, so it commutes with the (row-major) reshape
            # and the fold is bit-identical to the 3-D masked scatter.
            s = s + aggregate_receivers(msg_s, batch)
            e, _, fv = msg_v.shape
            v = v + aggregate_receivers(
                msg_v.reshape(e, 3 * fv), batch
            ).reshape(n, 3, fv)
        return s, v


class PainnUpdate(nn.Module):
    """PaiNN update block (reference PainnUpdate,
    hydragnn/models/PAINNStack.py:275-330)."""

    node_size: int
    last_layer: bool = False

    @nn.compact
    @tr.scoped("vector_update")
    def __call__(self, s: jax.Array, v: jax.Array):
        F = self.node_size
        # bias=False is REQUIRED for equivariance: v is [N, 3, F] and a
        # bias would add the same value to every spatial component — a
        # fixed (1,1,1) lab-frame direction that does not rotate with
        # the input. (Intentional divergence: the reference's
        # PAINNStack.py:281-282 uses nn.Linear with its default bias on
        # the vector channel, which silently breaks equivariance once
        # the bias trains away from zero; its CI only checks invariance
        # at init, where biases are exactly zero.)
        Uv = nn.Dense(F, use_bias=False, name="update_U")(v)
        Vv = nn.Dense(F, use_bias=False, name="update_V")(v)
        Vv_norm = jnp.sqrt(jnp.sum(Vv * Vv, axis=1) + 1e-12)
        out_dim = 2 * F if self.last_layer else 3 * F
        mlp_out = MLP(features=(F, out_dim), act="silu", name="update_mlp")(
            jnp.concatenate([Vv_norm, s], axis=-1)
        )
        inner = jnp.sum(Uv * Vv, axis=1)
        if self.last_layer:
            a_sv, a_ss = jnp.split(mlp_out, 2, axis=-1)
            return s + a_sv * inner + a_ss, v
        a_vv, a_sv, a_ss = jnp.split(mlp_out, 3, axis=-1)
        return s + a_sv * inner + a_ss, v + a_vv[:, None, :] * Uv


class _PainnLayout(nn.Module):
    """Shared PaiNN-style stack scaffolding: scalar channel s [N, F] and
    vector channel v [N, 3, F], message+update+resize per layer
    (reference PAINNStack.get_conv, hydragnn/models/PAINNStack.py:76-148).

    Subclasses provide ``_make_message(i, node_size)``; the update /
    resize modules are identical across PaiNN variants. The tanh resize
    MLP prevents exploding gradients on random-signal fits (reference
    PAINNStack.py:95-100 comment).
    """

    cfg: ModelConfig
    norm_kind = "none"

    def setup(self):
        cfg = self.cfg
        if cfg.radius is None or cfg.num_radial is None:
            raise ValueError(
                f"{type(self).__name__} requires radius and num_radial"
            )
        # With GPS global attention the input embedding lifts node (and
        # edge) features to hidden_dim before the stack (reference
        # PAINNStack._embedding, hydragnn/models/PAINNStack.py:173-186;
        # wrapped per conv by Base._apply_global_attn:234-247), and with
        # species_embedding the atom type is embedded into hidden_dim
        # (PaiNN's Embedding(Z, F)), so every layer runs at hidden width.
        self.species_embed = (
            nn.Dense(cfg.hidden_dim, use_bias=False, name="species_embed")
            if cfg.species_embedding
            else None
        )
        if cfg.use_global_attn or cfg.species_embedding:
            in_dims = [cfg.hidden_dim] * cfg.num_conv_layers
        else:
            in_dims = [cfg.input_dim] + [cfg.hidden_dim] * (
                cfg.num_conv_layers - 1
            )
        self.messages = [
            self._make_message(i, in_dims[i])
            for i in range(cfg.num_conv_layers)
        ]
        self.updates = [
            PainnUpdate(
                node_size=in_dims[i],
                last_layer=i == cfg.num_conv_layers - 1,
                name=f"update_{i}",
            )
            for i in range(cfg.num_conv_layers)
        ]
        self.node_embed_out = [
            MLP(
                features=(cfg.hidden_dim, cfg.hidden_dim),
                act="tanh",
                name=f"node_embed_out_{i}",
            )
            for i in range(cfg.num_conv_layers)
        ]
        self.vec_embed_out = [
            # bias=False: resizes the vector channel [N, 3, F] — see the
            # equivariance note in PainnUpdate (reference
            # PAINNStack.py:98 has the same trainable-bias leak).
            nn.Dense(
                cfg.hidden_dim, use_bias=False, name=f"vec_embed_out_{i}"
            )
            for i in range(cfg.num_conv_layers - 1)
        ]

    def embed(
        self, batch: GraphBatch
    ) -> Tuple[jax.Array, Optional[jax.Array], Dict[str, Any]]:
        if batch.pos is None:
            raise ValueError(f"{type(self).__name__} requires node positions")
        unit, dist = edge_vectors_and_lengths(
            batch.pos,
            batch.senders,
            batch.receivers,
            batch.edge_shifts,
            normalize=True,
        )
        s = batch.x
        if self.species_embed is not None:
            s = self.species_embed(species_onehot(s, batch.node_mask))
        v = jnp.zeros((batch.num_nodes, 3, s.shape[-1]), s.dtype)
        return s, v, {"unit": unit, "dist": dist}

    def conv(self, i, inv, equiv, batch, extras):
        cfg = self.cfg
        last = i == cfg.num_conv_layers - 1
        s, v = self.messages[i](
            inv, equiv, batch, extras["unit"], extras["dist"]
        )
        s, v = self.updates[i](s, v)
        s = self.node_embed_out[i](s)
        if not last:
            v = self.vec_embed_out[i](v)
        return s, v


class PAINNStack(_PainnLayout):
    """PaiNN stack (reference PAINNStack, hydragnn/models/PAINNStack.py:27)."""

    def _make_message(self, i: int, node_size: int) -> nn.Module:
        cfg = self.cfg
        # Under GPS the edge attributes are the hidden-dim lifted
        # (edge_attr + rel_pe) embeddings from GPSInputEmbed.
        edge_dim = cfg.hidden_dim if cfg.use_global_attn else cfg.edge_dim
        return PainnMessage(
            node_size=node_size,
            num_radial=cfg.num_radial,
            cutoff=cfg.radius,
            edge_dim=edge_dim,
            name=f"message_{i}",
        )


# ----------------------------------------------------------------------
# PNAEq
# ----------------------------------------------------------------------


class PNAEqMessage(nn.Module):
    """PaiNN-style message with PNA degree-scaler aggregation of the
    scalar channel (reference PainnMessage in PNAEqStack,
    hydragnn/models/PNAEqStack.py:240-419)."""

    node_size: int
    num_radial: int
    cutoff: float
    avg_deg_lin: float
    avg_deg_log: float
    edge_dim: Optional[int] = None

    @nn.compact
    def __call__(
        self,
        s: jax.Array,
        v: jax.Array,
        batch: GraphBatch,
        unit: jax.Array,
        dist: jax.Array,
    ) -> Tuple[jax.Array, jax.Array]:
        snd, rcv = batch.senders, batch.receivers
        F = self.node_size
        n = batch.num_nodes

        # sinc RBF x cosine cutoff (reference rbf_BasisLayer,
        # PNAEqStack.py:479-538).
        rbf = sinc_basis(dist, self.cutoff, self.num_radial)
        rbf = rbf * cosine_cutoff(dist, self.cutoff)[:, None]

        parts = [s[snd], s[rcv], jnp.tanh(nn.Dense(F, name="rbf_emb")(rbf))]
        if self.edge_dim and batch.edge_attr is not None:
            parts.append(nn.Dense(F, name="edge_encoder")(batch.edge_attr))
        msg = nn.Dense(F, name="pre_nn")(jnp.concatenate(parts, axis=-1))

        scalar_out = self._scalar_mlp(msg, F)
        filter_out = scalar_out * nn.Dense(
            3 * F, use_bias=False, name="rbf_lin"
        )(rbf)
        gate_v, gate_e, msg_s = jnp.split(filter_out, 3, axis=-1)

        msg_v = v[snd] * gate_v[:, None, :] + gate_e[:, None, :] * unit[:, :, None]

        # PNA aggregation of the scalar message at the destination
        # (4 aggregators x 5 scalers; reference PNAEqStack.py:57-66,398-403).
        scaled = pna_scaled_aggregate(
            msg_s,
            batch,
            self.avg_deg_lin,
            self.avg_deg_log,
            inverse_linear=True,
        )
        delta_s = nn.Dense(F, name="post_nn")(
            jnp.concatenate([s, scaled], axis=-1)
        )
        s = s + delta_s
        # 3-axis folded into the feature dim so the vector aggregation
        # rides the planned kernel (see PainnMessage).
        e, _, fv = msg_v.shape
        v = v + aggregate_receivers(msg_v.reshape(e, 3 * fv), batch).reshape(
            n, 3, fv
        )
        return s, v

    def _scalar_mlp(self, x: jax.Array, F: int) -> jax.Array:
        """Dense-tanh-Dense-silu-Dense(3F) (reference scalar_message_mlp,
        PNAEqStack.py:318-325)."""
        x = jnp.tanh(nn.Dense(F, name="scalar_mlp_0")(x))
        x = jax.nn.silu(nn.Dense(F, name="scalar_mlp_1")(x))
        return nn.Dense(3 * F, name="scalar_mlp_2")(x)


class PNAEqStack(_PainnLayout):
    """PNAEq stack (reference PNAEqStack, hydragnn/models/PNAEqStack.py:41)."""

    def _make_message(self, i: int, node_size: int) -> nn.Module:
        cfg = self.cfg
        if cfg.pna_deg is None:
            raise ValueError("PNAEq requires the pna_deg degree histogram")
        avg_lin, avg_log = _deg_stats(cfg.pna_deg)
        return PNAEqMessage(
            node_size=node_size,
            num_radial=cfg.num_radial,
            cutoff=cfg.radius,
            avg_deg_lin=avg_lin,
            avg_deg_log=avg_log,
            edge_dim=cfg.hidden_dim if cfg.use_global_attn else cfg.edge_dim,
            name=f"message_{i}",
        )
