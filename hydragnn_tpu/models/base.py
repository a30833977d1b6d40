"""Multi-headed GNN core: encoder orchestration + multihead decoders.

The TPU-native counterpart of the reference's abstract ``Base`` stack
(hydragnn/models/Base.py:36-983): N message-passing layers with per-layer
feature norm + activation, graph-attribute conditioning (FiLM /
concat_node / fuse_pool, Base.py:299-444), graph pooling (mean/add/max,
Base.py:147-170), and the multihead decoder — graph heads = per-branch
shared MLP + per-head MLP, node heads = MLP / per-node MLP
(Base.py:590-691), with per-graph branch routing by ``dataset_id``
(Base.py:764-841) done as masked dense compute + select (static shapes,
no data-dependent control flow).

Packed-batch contract: every head is graph-id aware — routing and
pooling key on ``node_graph_idx``/``dataset_id``, masks on
``node_mask``/``graph_mask`` — so bin-packed batches (variable graph
counts per fixed budget shape, large trailing padding-graph runs in
tail bins; data/padschedule.py) flow through unchanged: padding
graphs/nodes are inert in pooling, batch norms, branch selection, and
the losses.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, List, Optional, Tuple, Type

import jax
import jax.numpy as jnp
from flax import linen as nn

from hydragnn_tpu.data.graph import GraphBatch
from hydragnn_tpu.models.gps import GPSInputEmbed, GPSLayer
from hydragnn_tpu.models.layers import (
    MLP,
    DenseParams,
    MaskedBatchNorm,
    activation,
)
from hydragnn_tpu.models.spec import ModelConfig
from hydragnn_tpu.ops import segment_max, segment_mean, segment_sum
from hydragnn_tpu.ops.segment import aggregate_receivers_pipeline
from hydragnn_tpu.utils import tracer as tr


@tr.scoped("pool")
def graph_pool(
    x: jax.Array, batch: GraphBatch, mode: str
) -> jax.Array:
    """Masked graph pooling [N, F] -> [G, F] (reference Base.py:147-170)."""
    ids = batch.node_graph_idx
    g = batch.num_graphs
    if mode == "mean":
        return segment_mean(x, ids, g, mask=batch.node_mask)
    if mode == "add":
        return segment_sum(x, ids, g, mask=batch.node_mask)
    if mode == "max":
        return segment_max(x, ids, g, mask=batch.node_mask)
    raise ValueError(f"Unsupported graph_pooling: {mode}")


def select_branch(stacked: jax.Array, branch_ids: jax.Array) -> jax.Array:
    """Pick per-row branch outputs: stacked [B, K, D], ids [K] -> [K, D]."""
    k = stacked.shape[1]
    return stacked[branch_ids, jnp.arange(k)]


class MLPNode(nn.Module):
    """Node-level head MLP; ``per_node`` gives every node slot its own
    weights (reference MLPNode, hydragnn/models/Base.py:912-983).

    All node heads share one signature:
    ``__call__(x, batch, branch_mask=None, *, train=False)``.
    """

    hidden_dims: Tuple[int, ...]
    output_dim: int
    act: str
    per_node: bool = False
    num_nodes: Optional[int] = None

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        batch: GraphBatch,
        branch_mask: Optional[jax.Array] = None,
        *,
        train: bool = False,
    ) -> jax.Array:
        node_slot = batch.node_slot
        dims = tuple(self.hidden_dims) + (self.output_dim,)
        fn = activation(self.act)
        if not self.per_node:
            for i, d in enumerate(dims):
                x = nn.Dense(d, name=f"dense_{i}")(x)
                if i < len(dims) - 1:
                    x = fn(x)
            return x
        if self.num_nodes is None:
            raise ValueError("mlp_per_node requires a fixed num_nodes")
        in_dim = x.shape[-1]
        for i, d in enumerate(dims):
            w = self.param(
                f"w_{i}",
                nn.initializers.lecun_normal(),
                (self.num_nodes, in_dim, d),
            )
            b = self.param(
                f"b_{i}", nn.initializers.zeros, (self.num_nodes, d)
            )
            slot = jnp.minimum(node_slot, self.num_nodes - 1)
            x = jnp.einsum("nf,nfd->nd", x, w[slot]) + b[slot]
            if i < len(dims) - 1:
                x = fn(x)
            in_dim = d
        return x


class ConvNodeHead(nn.Module):
    """Node head built from message-passing layers instead of an MLP
    (reference "conv"-type node heads, Base.py:508-588: a chain of the
    stack's convolutions + BatchNorm per layer, final conv to the head
    dim). TPU deviation: heads use one generic dimension-changing conv
    (self + mean-aggregated neighbor linear, SAGE-style) rather than
    re-instantiating the encoder's conv family — head convs only map
    features, and a uniform conv keeps every stack's head jit-simple."""

    hidden_dims: Tuple[int, ...]
    output_dim: int
    act: str

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        batch: GraphBatch,
        branch_mask: Optional[jax.Array] = None,
        *,
        train: bool = False,
    ) -> jax.Array:
        fn = activation(self.act)
        # BN statistics must come only from THIS branch's (real) nodes;
        # in multi-branch batches other datasets' nodes would otherwise
        # pollute the running stats (reference conv heads run on the
        # branch subset, Base.py:508-588).
        bn_mask = (
            batch.node_mask
            if branch_mask is None
            else batch.node_mask & branch_mask
        )
        dims = tuple(self.hidden_dims) + (self.output_dim,)
        for i, d in enumerate(dims):
            last = i == len(dims) - 1
            # Dispatched aggregation: gather -> neigh matmul -> mean
            # reduce as ONE fused edge pipeline where the crossover
            # table says the Pallas kernel wins (the per-node degree
            # scale commutes with the matmul, so it divides after the
            # fused sum); the XLA scatter decomposition otherwise.
            # DenseParams keeps the "neigh_{i}" param tree of the
            # nn.Dense it replaces (checkpoint-compatible).
            w_n, _ = DenseParams(d, use_bias=False, name=f"neigh_{i}")(
                x.shape[-1]
            )
            with tr.scope("edge_aggregate"):  # the sender gather too
                neigh = aggregate_receivers_pipeline(
                    x[batch.senders], None, batch, weight=w_n, mean=True
                )
            x = nn.Dense(d, name=f"self_{i}")(x) + neigh
            x = MaskedBatchNorm(name=f"bn_{i}")(x, bn_mask, train=train)
            if not last:
                x = fn(x)
        return x


class MultiHeadDecoder(nn.Module):
    """Graph + node heads with branch routing (reference Base.py:590-691,
    forward dispatch Base.py:749-841)."""

    cfg: ModelConfig

    def setup(self):
        cfg = self.cfg
        self.graph_shared = [
            MLP(
                features=(b.dim_sharedlayers,) * b.num_sharedlayers,
                act=cfg.activation,
                final_activation=True,
                name=f"graph_shared_{b.name}",
            )
            for b in cfg.graph_branches
        ]
        graph_heads = []
        node_heads = []
        for hi, head in enumerate(cfg.heads):
            out_dim = head.dim * (1 + cfg.var_output)
            if head.type == "graph":
                graph_heads.append(
                    [
                        MLP(
                            features=tuple(
                                b.dim_headlayers[: b.num_headlayers]
                            )
                            + (out_dim,),
                            act=cfg.activation,
                            name=f"head{hi}_{b.name}",
                        )
                        for b in cfg.graph_branches
                    ]
                )
                node_heads.append(None)
            elif head.type == "node":
                per_branch = []
                for b in cfg.node_branches:
                    if b.node_head_type in ("mlp", "mlp_per_node"):
                        per_branch.append(
                            MLPNode(
                                hidden_dims=tuple(
                                    b.dim_headlayers[: b.num_headlayers]
                                ),
                                output_dim=out_dim,
                                act=cfg.activation,
                                per_node=b.node_head_type == "mlp_per_node",
                                num_nodes=cfg.num_nodes,
                                name=f"head{hi}_{b.name}",
                            )
                        )
                    elif b.node_head_type == "conv":
                        per_branch.append(
                            ConvNodeHead(
                                hidden_dims=tuple(
                                    b.dim_headlayers[: b.num_headlayers]
                                ),
                                output_dim=out_dim,
                                act=cfg.activation,
                                name=f"head{hi}_{b.name}",
                            )
                        )
                    else:
                        raise ValueError(
                            f"Unknown node head type {b.node_head_type}"
                        )
                node_heads.append(per_branch)
                graph_heads.append(None)
            else:
                raise ValueError(f"Unknown head type {head.type}")
        self.graph_heads = graph_heads
        self.node_heads = node_heads

    def __call__(
        self,
        node_repr: jax.Array,
        pooled: jax.Array,
        batch: GraphBatch,
        *,
        train: bool = False,
    ) -> List[jax.Array]:
        cfg = self.cfg
        outputs: List[jax.Array] = []
        graph_ids = (
            batch.dataset_id
            if batch.dataset_id is not None
            else jnp.zeros(batch.num_graphs, jnp.int32)
        )
        node_ids = graph_ids[batch.node_graph_idx]
        shared = [m(pooled) for m in self.graph_shared]
        for hi, head in enumerate(cfg.heads):
            if head.type == "graph":
                branch_outs = [
                    m(shared[b]) for b, m in enumerate(self.graph_heads[hi])
                ]
                if len(branch_outs) == 1:
                    outputs.append(branch_outs[0])
                else:
                    outputs.append(
                        select_branch(jnp.stack(branch_outs), graph_ids)
                    )
            else:
                multi = len(self.node_heads[hi]) > 1
                branch_outs = [
                    m(
                        node_repr,
                        batch,
                        (node_ids == bi) if multi else None,
                        train=train,
                    )
                    for bi, m in enumerate(self.node_heads[hi])
                ]
                if len(branch_outs) == 1:
                    outputs.append(branch_outs[0])
                else:
                    outputs.append(
                        select_branch(jnp.stack(branch_outs), node_ids)
                    )
        return outputs


class GraphAttrConditioner(nn.Module):
    """FiLM / concat_node / fuse_pool conditioning on ``graph_attr``
    (reference Base.py:299-444)."""

    cfg: ModelConfig
    mode: str

    @nn.compact
    def __call__(
        self, x: jax.Array, graph_attr: jax.Array, graph_idx: Optional[jax.Array]
    ) -> jax.Array:
        h = x.shape[-1]
        if self.mode == "film":
            gb = MLP(
                features=(2 * h,), act=self.cfg.activation, name="film"
            )(graph_attr)
            gamma, beta = jnp.split(gb, 2, axis=-1)
            if graph_idx is not None:
                gamma, beta = gamma[graph_idx], beta[graph_idx]
            return x * (1.0 + gamma) + beta
        attr = graph_attr if graph_idx is None else graph_attr[graph_idx]
        fused = jnp.concatenate([x, attr], axis=-1)
        return nn.Dense(h, name="proj")(fused)


class MultiHeadGraphModel(nn.Module):
    """Encoder stack + multihead decoder (reference Base.forward,
    hydragnn/models/Base.py:697-841)."""

    cfg: ModelConfig
    stack_cls: Type[nn.Module]

    def setup(self):
        cfg = self.cfg
        self.stack = self.stack_cls(cfg=cfg, name="stack")
        self.per_layer_readouts = getattr(
            self.stack_cls, "per_layer_readouts", False
        )
        if self.per_layer_readouts:
            # MACE-style: one decoder per layer plus one on the raw node
            # attributes, outputs summed (reference MACEStack.py:375-421).
            self.decoders = [
                MultiHeadDecoder(cfg=cfg, name=f"decoder_{i}")
                for i in range(cfg.num_conv_layers + 1)
            ]
        else:
            self.decoder = MultiHeadDecoder(cfg=cfg, name="decoder")
        norm_kind = getattr(self.stack_cls, "norm_kind", "none")
        if norm_kind == "batch":
            self.feature_norms = [
                MaskedBatchNorm(name=f"feature_norm_{i}")
                for i in range(cfg.num_conv_layers)
            ]
        else:
            self.feature_norms = None
        if cfg.use_global_attn:
            # Per-layer-readout stacks (MACE) keep their own chemically
            # meaningful scalar embedding (one-hot x irreps linear), so
            # the Laplacian PE is ADDED to the scalar channel instead of
            # replacing it via GPSInputEmbed (reference instead concats
            # node features with pos_emb(pe), MACEStack.py:478-492; same
            # information, residual form).
            if self.per_layer_readouts:
                self.gps_embed = None
                self.gps_pe_lift = nn.Dense(
                    cfg.hidden_dim, use_bias=False, name="gps_pe_lift"
                )
            else:
                self.gps_embed = GPSInputEmbed(cfg=cfg, name="gps_embed")
            self.gps_layers = [
                GPSLayer(cfg=cfg, name=f"gps_{i}")
                for i in range(cfg.num_conv_layers)
            ]
        else:
            self.gps_embed = None
            self.gps_layers = None
        if cfg.use_graph_attr_conditioning:
            mode = cfg.graph_attr_conditioning_mode
            if mode not in ("film", "concat_node", "fuse_pool"):
                raise ValueError(
                    "graph_attr_conditioning_mode must be film, "
                    f"concat_node, or fuse_pool; got {mode}"
                )
            self.conditioner = GraphAttrConditioner(
                cfg=cfg, mode=mode, name="graph_conditioner"
            )
        else:
            self.conditioner = None

    def _conv_fn(self):
        """The stack's conv method, remat-wrapped when gradient
        checkpointing is on (reference Base.py:707-721)."""
        if self.cfg.conv_checkpointing:
            return nn.remat(type(self.stack).conv, static_argnums=(1,))
        return type(self.stack).conv

    def _condition_inv(self, inv: jax.Array, batch: GraphBatch) -> jax.Array:
        """Apply film/concat_node graph-attr conditioning to node features
        (no-op for fuse_pool or when conditioning is off)."""
        if (
            self.conditioner is not None
            and self.cfg.graph_attr_conditioning_mode
            in ("film", "concat_node")
            and batch.graph_attr is not None
        ):
            return self.conditioner(
                inv, batch.graph_attr, batch.node_graph_idx
            )
        return inv

    def _pool(self, node_repr: jax.Array, batch: GraphBatch) -> jax.Array:
        """Graph pooling plus optional fuse_pool conditioning."""
        pooled = graph_pool(node_repr, batch, self.cfg.graph_pooling)
        if (
            self.conditioner is not None
            and self.cfg.graph_attr_conditioning_mode == "fuse_pool"
            and batch.graph_attr is not None
        ):
            pooled = self.conditioner(pooled, batch.graph_attr, None)
        return pooled

    def encode(
        self, batch: GraphBatch, *, train: bool = False
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """Run embedding + conv layers; returns (node_repr, equiv_feat)."""
        cfg = self.cfg
        act = activation(cfg.activation)
        if self.gps_embed is not None:
            x_emb, e_emb = self.gps_embed(batch)
            batch = batch.replace(
                x=x_emb,
                edge_attr=e_emb if e_emb is not None else batch.edge_attr,
            )
        inv, equiv, extras = self.stack.embed(batch)
        use_act = getattr(self.stack_cls, "inter_layer_activation", True)
        conv_fn = self._conv_fn()
        for i in range(cfg.num_conv_layers):
            h, equiv = conv_fn(self.stack, i, inv, equiv, batch, extras)
            if self.gps_layers is not None:
                inv = self.gps_layers[i](inv, h, batch, train=train)
            else:
                inv = h
            inv = self._condition_inv(inv, batch)
            if self.feature_norms is not None:
                inv = self.feature_norms[i](
                    inv, batch.node_mask, train=train
                )
            if use_act:
                inv = act(inv)
        return inv, equiv

    def _forward_per_layer_readouts(
        self, batch: GraphBatch, *, train: bool = False
    ) -> List[jax.Array]:
        """MACE-style forward: decoder on the embedding-time node
        attributes plus one decoder per conv layer, summed
        (reference MACEStack.forward, MACEStack.py:375-421)."""
        cfg = self.cfg
        inv, equiv, extras = self.stack.embed(batch)
        read0 = extras.get("readout0_input", inv)
        if self.gps_layers is not None:
            if batch.pe is None:
                raise ValueError(
                    "GPS global attention requires Laplacian PE; set "
                    "pe_dim>0 so the data pipeline attaches batch.pe"
                )
            inv = inv + self.gps_pe_lift(batch.pe)

        def _decode(d, node_repr):
            return d(
                node_repr, self._pool(node_repr, batch), batch, train=train
            )

        outputs = _decode(self.decoders[0], read0)
        conv_fn = self._conv_fn()
        for i in range(cfg.num_conv_layers):
            h, equiv = conv_fn(self.stack, i, inv, equiv, batch, extras)
            if self.gps_layers is not None:
                # Global attention on the scalar (l=0) channel between
                # interactions, like the reference's GPSConv wrap of
                # each MACE interaction (MACEStack.py:231,259).
                inv = self.gps_layers[i](inv, h, batch, train=train)
            else:
                inv = h
            inv = self._condition_inv(inv, batch)
            out_i = _decode(self.decoders[i + 1], inv)
            outputs = [a + b for a, b in zip(outputs, out_i)]
        return outputs

    def __call__(
        self, batch: GraphBatch, *, train: bool = False
    ) -> List[jax.Array]:
        # A stated precision reaches every product of the model, and their
        # transposes inherit it.
        precision = self.cfg.matmul_precision
        with (
            jax.default_matmul_precision(precision)
            if precision
            else contextlib.nullcontext()
        ):
            if self.per_layer_readouts:
                return self._forward_per_layer_readouts(batch, train=train)
            node_repr, _ = self.encode(batch, train=train)
            return self.decoder(
                node_repr, self._pool(node_repr, batch), batch, train=train
            )
