"""SchNet stack: continuous-filter convolutions with Gaussian smearing.

TPU-native reimplementation of the reference SCFStack / CFConv
(hydragnn/models/SCFStack.py:42-301): Gaussian RBF of edge length, filter
MLP with shifted-softplus, cosine cutoff weighting, gather -> filter *
features -> segment-sum aggregation, and the optional equivariant
coordinate-update channel (SCFStack.py:252-295). Distances are recomputed
from the current positions every layer (the static-shape analog of the
reference's per-forward RadiusInteractionGraph, SCFStack.py:129-161), so
coordinate updates propagate.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from hydragnn_tpu.data.graph import GraphBatch
from hydragnn_tpu.models.layers import DenseParams, MLP, shifted_softplus
from hydragnn_tpu.models.spec import ModelConfig
from hydragnn_tpu.ops import (
    cosine_cutoff,
    edge_vectors_and_lengths,
    gaussian_smearing,
    segment_mean,
    segment_sum,
)
from hydragnn_tpu.ops.segment import aggregate_receivers_pipeline
from hydragnn_tpu.utils import tracer as tr


class CFConv(nn.Module):
    """One continuous-filter convolution (reference CFConv,
    hydragnn/models/SCFStack.py:222-301)."""

    in_dim: int
    out_dim: int
    num_filters: int
    num_gaussians: int
    cutoff: float
    edge_dim: Optional[int] = None
    equivariant: bool = False

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        pos: Optional[jax.Array],
        batch: GraphBatch,
        edge_rbf: jax.Array,
        edge_len: jax.Array,
        edge_attr: Optional[jax.Array],
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        snd, rcv = batch.senders, batch.receivers
        C = cosine_cutoff(edge_len, self.cutoff)
        filt_in = (
            edge_rbf
            if edge_attr is None
            else jnp.concatenate([edge_rbf, edge_attr], axis=-1)
        )
        W = (
            MLP(
                features=(self.num_filters, self.num_filters),
                act="shifted_softplus",
                final_activation=False,
                name="filter_mlp",
            )(filt_in)
            * C[:, None]
        )
        h = nn.Dense(self.num_filters, use_bias=False, name="lin1")(x)

        if self.equivariant and pos is not None:
            # Coordinate-update channel (EGNN-style; reference
            # SCFStack.py:252-262): mean of unit displacements scaled by a
            # small learned gate of the filter weights.
            vec, _ = edge_vectors_and_lengths(
                pos, snd, rcv, batch.edge_shifts, normalize=True, eps=1.0
            )
            gate = MLP(
                features=(self.num_filters, 1),
                act="relu",
                name="coord_mlp",
            )(W)
            trans = jnp.clip(vec * gate, -100.0, 100.0)
            # Reference aggregates at edge_index row 0 = sender side.
            agg = segment_mean(
                trans, snd, batch.num_nodes, mask=batch.edge_mask
            )
            pos = pos + agg

        # gather -> filter multiply -> lin2 matmul -> reduce, dispatched
        # as ONE fused edge pipeline where the crossover table says the
        # Pallas kernel wins (ops/segment.aggregate_receivers_pipeline);
        # the fallback decomposes into exactly the old op order
        # (aggregate product, then the dense matmul). lin2 is a
        # DenseParams twin — same "lin2" param tree and init as the
        # nn.Dense it replaces (checkpoint-compatible) — so the matmul
        # can ride inside the kernel; the bias adds after the reduce
        # (segment-sum and matmul commute; the bias does not).
        w2, b2 = DenseParams(self.out_dim, name="lin2")(self.num_filters)
        # the scope holds the sender gather too: the block's boundary
        # is the algorithm's, whatever kernel implements it
        with tr.scope("edge_aggregate"):
            out = aggregate_receivers_pipeline(h[snd], W, batch, weight=w2)
        return out + b2, pos


class SchNetStack(nn.Module):
    """Stack of CFConv layers (reference SCFStack._init_conv,
    hydragnn/models/SCFStack.py:66-161)."""

    cfg: ModelConfig
    norm_kind = "none"

    def setup(self):
        cfg = self.cfg
        if cfg.radius is None or cfg.num_gaussians is None or cfg.num_filters is None:
            raise ValueError("SchNet requires radius, num_gaussians, num_filters")
        convs = []
        in_dim = cfg.hidden_dim if cfg.use_global_attn else cfg.input_dim
        for i in range(cfg.num_conv_layers):
            last = i == cfg.num_conv_layers - 1
            convs.append(
                CFConv(
                    in_dim=in_dim if i == 0 else cfg.hidden_dim,
                    out_dim=cfg.hidden_dim,
                    num_filters=cfg.num_filters,
                    num_gaussians=cfg.num_gaussians,
                    cutoff=cfg.radius,
                    edge_dim=cfg.edge_dim,
                    equivariant=cfg.equivariance and not last,
                    name=f"conv_{i}",
                )
            )
        self.convs = convs

    def embed(
        self, batch: GraphBatch
    ) -> Tuple[jax.Array, Optional[jax.Array], Dict[str, Any]]:
        return batch.x, batch.pos, {}

    def conv(
        self,
        i: int,
        inv: jax.Array,
        equiv: Optional[jax.Array],
        batch: GraphBatch,
        extras: Dict[str, Any],
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        cfg = self.cfg
        _, edge_len = edge_vectors_and_lengths(
            equiv, batch.senders, batch.receivers, batch.edge_shifts
        )
        edge_rbf = gaussian_smearing(
            edge_len, 0.0, cfg.radius, cfg.num_gaussians
        )
        inv, equiv = self.convs[i](
            inv, equiv, batch, edge_rbf, edge_len, batch.edge_attr
        )
        return inv, equiv
