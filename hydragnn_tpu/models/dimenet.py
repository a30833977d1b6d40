"""DimeNet(++) stack: directional message passing with angular triplets.

TPU-native counterpart of the reference DIMEStack
(hydragnn/models/DIMEStack.py:34-328): per layer a linear node projection,
an embedding block mixing (x_i, x_j, rbf) into edge messages, an
interaction block that exchanges messages between adjacent edges weighted
by a 2-D spherical basis of (distance, angle), and an output block
aggregating edges back to nodes. Triplet indices are built host-side at
collate time (static shapes); the spherical basis is evaluated in
hydragnn_tpu/ops/sbf.py.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from hydragnn_tpu.data.graph import GraphBatch
from hydragnn_tpu.models.spec import ModelConfig
from hydragnn_tpu.ops import edge_vectors_and_lengths
from hydragnn_tpu.ops.segment import _sum_at_receivers, sum_over_triplets
from hydragnn_tpu.ops.sbf import bessel_basis_envelope, spherical_basis
from hydragnn_tpu.utils import tracer as tr

ACT = jax.nn.silu


class ResidualLayer(nn.Module):
    dim: int

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        h = ACT(nn.Dense(self.dim, name="lin1")(x))
        h = ACT(nn.Dense(self.dim, name="lin2")(h))
        return x + h


class EmbeddingBlock(nn.Module):
    """Edge-message embedding from endpoint features + radial basis
    (reference HydraEmbeddingBlock, hydragnn/models/DIMEStack.py:282-328)."""

    hidden_dim: int
    edge_dim: Optional[int] = None

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        rbf: jax.Array,
        batch: GraphBatch,
        edge_attr: Optional[jax.Array],
    ) -> jax.Array:
        rbf_h = ACT(nn.Dense(self.hidden_dim, name="lin_rbf")(rbf))
        parts = [x[batch.receivers], x[batch.senders], rbf_h]
        if edge_attr is not None:
            parts.append(ACT(nn.Dense(self.hidden_dim, name="edge_lin")(edge_attr)))
        return ACT(nn.Dense(self.hidden_dim, name="lin")(jnp.concatenate(parts, -1)))


class InteractionPPBlock(nn.Module):
    """DimeNet++ interaction: triplet message exchange with basis
    down-projections (behavioral spec: PyG InteractionPPBlock as used at
    hydragnn/models/DIMEStack.py:107-116)."""

    hidden_dim: int
    int_emb_size: int
    basis_emb_size: int
    num_before_skip: int
    num_after_skip: int

    @nn.compact
    def __call__(
        self,
        m: jax.Array,  # [E, H] edge messages
        rbf: jax.Array,  # [E, R]
        sbf: jax.Array,  # [T, S*R]
        batch: GraphBatch,
    ) -> jax.Array:
        H, I = self.hidden_dim, self.int_emb_size
        x_ji = ACT(nn.Dense(H, name="lin_ji")(m))
        x_kj = ACT(nn.Dense(H, name="lin_kj")(m))

        rbf_p = nn.Dense(self.basis_emb_size, use_bias=False, name="lin_rbf1")(rbf)
        rbf_p = nn.Dense(H, use_bias=False, name="lin_rbf2")(rbf_p)
        x_kj = x_kj * rbf_p

        x_kj = ACT(nn.Dense(I, name="lin_down")(x_kj))

        # The triplet exchange, timed under one scope: basis projection,
        # gather of the k->j messages, product, and their sum into j->i.
        with tr.scope("triplet"):
            sbf_p = nn.Dense(
                self.basis_emb_size, use_bias=False, name="lin_sbf1"
            )(sbf)
            sbf_p = nn.Dense(I, use_bias=False, name="lin_sbf2")(sbf_p)
            x_kj = sum_over_triplets(x_kj[batch.t_kj] * sbf_p, batch)
        x_kj = ACT(nn.Dense(H, name="lin_up")(x_kj))

        h = x_ji + x_kj
        for i in range(self.num_before_skip):
            h = ResidualLayer(H, name=f"before_skip_{i}")(h)
        h = ACT(nn.Dense(H, name="lin")(h)) + m
        for i in range(self.num_after_skip):
            h = ResidualLayer(H, name=f"after_skip_{i}")(h)
        return h


class OutputPPBlock(nn.Module):
    """Edge->node readout (behavioral spec: PyG OutputPPBlock as used at
    hydragnn/models/DIMEStack.py:117-126)."""

    out_emb_size: int
    out_dim: int
    num_layers: int = 1

    @nn.compact
    def __call__(
        self, m: jax.Array, rbf: jax.Array, batch: GraphBatch
    ) -> jax.Array:
        g = nn.Dense(m.shape[-1], use_bias=False, name="lin_rbf")(rbf)
        node = _sum_at_receivers(g * m, batch)
        node = nn.Dense(self.out_emb_size, use_bias=False, name="lin_up")(node)
        for i in range(self.num_layers):
            node = ACT(nn.Dense(self.out_emb_size, name=f"lin_{i}")(node))
        return nn.Dense(self.out_dim, use_bias=False, name="lin_out")(node)


class DIMEStack(nn.Module):
    """Stack of DimeNet++ blocks under the multihead core."""

    cfg: ModelConfig
    norm_kind = "none"

    # Defaults match the reference example configs (DimeNet++ sizes).
    @property
    def _sizes(self):
        cfg = self.cfg

        def d(v, default):
            return default if v is None else v

        return dict(
            num_radial=d(cfg.num_radial, 6),
            num_spherical=d(cfg.num_spherical, 7),
            envelope_exponent=d(cfg.envelope_exponent, 5),
            basis_emb_size=d(cfg.basis_emb_size, 8),
            int_emb_size=d(cfg.int_emb_size, 64),
            out_emb_size=d(cfg.out_emb_size, 16),
            num_before_skip=d(cfg.num_before_skip, 1),
            num_after_skip=d(cfg.num_after_skip, 2),
        )

    def setup(self):
        cfg = self.cfg
        if cfg.radius is None:
            raise ValueError("DimeNet requires radius")
        s = self._sizes
        lins, embs, inters, outs = [], [], [], []
        in_dim = cfg.hidden_dim if cfg.use_global_attn else cfg.input_dim
        for i in range(cfg.num_conv_layers):
            d_in = in_dim if i == 0 else cfg.hidden_dim
            hidden = cfg.hidden_dim if d_in == 1 else d_in
            lins.append(nn.Dense(hidden, name=f"lin_{i}"))
            embs.append(
                EmbeddingBlock(
                    hidden_dim=hidden, edge_dim=cfg.edge_dim, name=f"emb_{i}"
                )
            )
            inters.append(
                InteractionPPBlock(
                    hidden_dim=hidden,
                    int_emb_size=s["int_emb_size"],
                    basis_emb_size=s["basis_emb_size"],
                    num_before_skip=s["num_before_skip"],
                    num_after_skip=s["num_after_skip"],
                    name=f"inter_{i}",
                )
            )
            outs.append(
                OutputPPBlock(
                    out_emb_size=s["out_emb_size"],
                    out_dim=cfg.hidden_dim,
                    name=f"out_{i}",
                )
            )
        self.lins, self.embs, self.inters, self.outs = lins, embs, inters, outs

    def embed(
        self, batch: GraphBatch
    ) -> Tuple[jax.Array, Optional[jax.Array], Dict[str, Any]]:
        cfg = self.cfg
        if batch.pos is None:
            raise ValueError("DimeNet requires node positions")
        if batch.t_kj is None:
            raise ValueError(
                "DimeNet requires triplets; build batches with "
                "with_triplets=True (GraphLoader/PadSpec)"
            )
        s = self._sizes
        vec, dist = edge_vectors_and_lengths(
            batch.pos, batch.senders, batch.receivers, batch.edge_shifts
        )
        rbf = bessel_basis_envelope(
            dist, cfg.radius, s["num_radial"], s["envelope_exponent"]
        )
        with tr.scope("triplet_basis"):
            # Angle at node i between directions i->j and i->k, composed
            # from edge vectors so PBC shifts are respected (reference
            # DIMEStack._embedding, hydragnn/models/DIMEStack.py:180-186).
            v_ji = vec[batch.t_ji]  # pos_j - pos_i
            v_ki = vec[batch.t_kj] + v_ji  # pos_k - pos_i
            a = jnp.sum(v_ji * v_ki, axis=-1)
            b = jnp.linalg.norm(jnp.cross(v_ji, v_ki), axis=-1)
            angle = jnp.arctan2(b, a)
            sbf = spherical_basis(
                dist,
                angle,
                batch.t_kj,
                cutoff=cfg.radius,
                num_spherical=s["num_spherical"],
                num_radial=s["num_radial"],
                envelope_exponent=s["envelope_exponent"],
            )
        return batch.x, batch.pos, {"rbf": rbf, "sbf": sbf}

    def conv(
        self,
        i: int,
        inv: jax.Array,
        equiv: Optional[jax.Array],
        batch: GraphBatch,
        extras: Dict[str, Any],
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        rbf, sbf = extras["rbf"], extras["sbf"]
        x = self.lins[i](inv)
        m = self.embs[i](x, rbf, batch, batch.edge_attr)
        m = self.inters[i](m, rbf, sbf, batch)
        node = self.outs[i](m, rbf, batch)
        return node, equiv
