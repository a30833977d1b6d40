"""Graph-dimension parallelism: one giant graph sharded across devices.

The GNN analog of sequence/context parallelism (ring attention's role
for transformers): when a single structure has too many atoms/edges for
one chip, shard the NODE and EDGE dimensions over a mesh axis and let
XLA collectives move features over ICI. The reference cannot do this at
all (SURVEY.md §2.5: "graph-dimension sharding of giant graphs would be
a new capability, not parity"; its GPS attention and radius graphs are
single-device per graph).

Scheme (classic SP-style all-gather/reduce-scatter pair, shard_map'd):

  nodes:  [N] -> [N/D] per device        (features, positions)
  edges:  [E] -> [E/D] per device        (global sender/receiver ids)

  gather_nodes:   x_full = all_gather(x_shard)   -> index rows per edge
  scatter_nodes:  partial per-device segment-sum over the FULL node
                  range, then psum_scatter -> each device's node shard

Backward passes are the transposes (all_gather <-> reduce-scatter), and
shard_map differentiates through both. The all-gather scheme is the
small-graph fast path: simple, compiler-friendly, overlapped by XLA
latency hiding — but every device holds the full [N, F] gathered
array, so its memory ceiling is one device's HBM.

``HaloShards`` + ``halo_mpnn_forward`` remove that ceiling: edges are
assigned to the shard that OWNS their receiver (the scatter becomes a
plain local segment-sum — no collective at all), and each layer moves
only the BOUNDARY node rows a neighbor actually references, via one
``ppermute`` per ring-hop distance with static host-computed
capacities. Per-device memory is n_loc + halo rows instead of N; for
locality-ordered giant graphs (the regime the feature exists for) the
halo is a thin shell. Differentially tested halo-vs-allgather on the
virtual mesh (tests/test_graphshard.py); memory model in
docs/PARALLELISM.md.

``sharded_mpnn_forward`` runs a SchNet-style continuous-filter conv
stack + energy readout entirely under shard_map; ``GraphShards`` holds
the host-side partitioning. Differentially tested against the
single-device computation on a virtual mesh (tests/test_graphshard.py).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hydragnn_tpu.ops.rbf import cosine_cutoff, gaussian_smearing


AXIS = "graph"


@dataclasses.dataclass
class GraphShards:
    """Host-side node/edge partition of ONE graph, padded to multiples
    of the mesh axis size. Ids stay global; masks mark padding."""

    x: jax.Array  # [N_pad, F]
    pos: jax.Array  # [N_pad, 3]
    node_mask: jax.Array  # [N_pad]
    senders: jax.Array  # [E_pad] int32 global ids
    receivers: jax.Array  # [E_pad] int32 global ids
    edge_mask: jax.Array  # [E_pad]
    num_nodes_padded: int

    @staticmethod
    def build(
        x: np.ndarray,
        pos: np.ndarray,
        edge_index: np.ndarray,
        n_shards: int,
        edge_capacity: Optional[int] = None,
    ) -> "GraphShards":
        """``edge_capacity`` pads the edge dimension to a fixed bound so
        successive configurations of the same structure (whose true edge
        counts fluctuate) share one compiled shape."""
        n, e = x.shape[0], edge_index.shape[1]
        e_cap = e
        if edge_capacity is not None:
            if e > edge_capacity:
                raise ValueError(
                    f"{e} edges exceed edge_capacity={edge_capacity}"
                )
            e_cap = edge_capacity
        n_pad = ((n + n_shards - 1) // n_shards) * n_shards
        e_pad = ((e_cap + n_shards - 1) // n_shards) * n_shards
        xp = np.zeros((n_pad, x.shape[1]), np.float32)
        xp[:n] = x
        pp = np.zeros((n_pad, 3), np.float32)
        pp[:n] = pos
        nm = np.zeros(n_pad, bool)
        nm[:n] = True
        snd = np.full(e_pad, n_pad - 1, np.int32)
        rcv = np.full(e_pad, n_pad - 1, np.int32)
        em = np.zeros(e_pad, bool)
        snd[:e] = edge_index[0]
        rcv[:e] = edge_index[1]
        em[:e] = True
        return GraphShards(
            x=jnp.asarray(xp),
            pos=jnp.asarray(pp),
            node_mask=jnp.asarray(nm),
            senders=jnp.asarray(snd),
            receivers=jnp.asarray(rcv),
            edge_mask=jnp.asarray(em),
            num_nodes_padded=n_pad,
        )

    def device_put(self, mesh: Mesh) -> "GraphShards":
        node_s = NamedSharding(mesh, P(AXIS))
        return dataclasses.replace(
            self,
            x=jax.device_put(self.x, node_s),
            pos=jax.device_put(self.pos, node_s),
            node_mask=jax.device_put(self.node_mask, node_s),
            senders=jax.device_put(self.senders, node_s),
            receivers=jax.device_put(self.receivers, node_s),
            edge_mask=jax.device_put(self.edge_mask, node_s),
        )


@dataclasses.dataclass
class HaloShards:
    """Receiver-owned edge partition of ONE graph with halo-exchange
    lists, for ``halo_mpnn_forward``.

    Layout per device d (n_loc = N_pad / D local node rows):
      - node arrays: global [N_pad, *] sharded by rows (d owns
        [d*n_loc, (d+1)*n_loc)).
      - edge arrays: [D * e_loc] sharded — slot d holds exactly the
        edges whose RECEIVER d owns, so ``receivers_local`` is in
        [0, n_loc) and the message scatter is a local segment-sum.
      - ``senders_halo`` indexes the per-device concatenation
        [local rows ; hop-1 halo block ; hop-2 halo block ; ...]: hop
        k's block (static capacity ``caps[k]``) receives, via ONE
        ppermute, the rows device (d-k-1) mod D sends — the rows listed
        in its ``send_idx[:, k, :]`` slice.

    All capacities are host-computed maxima over devices, so every
    shape is static; padded send slots duplicate row 0 (harmless: only
    masked edges can reference padded halo slots).
    """

    x: jax.Array  # [N_pad, F] sharded P(AXIS)
    pos: jax.Array  # [N_pad, 3]
    node_mask: jax.Array  # [N_pad]
    senders_halo: jax.Array  # [D*e_loc] int32, halo-local layout
    receivers_local: jax.Array  # [D*e_loc] int32, [0, n_loc)
    edge_mask: jax.Array  # [D*e_loc]
    send_idx: jax.Array  # [D, K, cap_max] int32 local rows per hop
    caps: Tuple[int, ...]  # static per-hop capacities (len K)
    num_nodes_padded: int
    n_shards: int
    hops: Tuple[int, ...] = ()  # active ring-hop distances minus 1
    e_loc: int = 0  # per-device edge-slot capacity

    @property
    def layout(self) -> tuple:
        """(e_loc, hops, caps): the static shape signature. Successive
        configurations of one structure built with the same layout
        share one compiled executable (``build(..., layout=...)``)."""
        return (self.e_loc, self.hops, self.caps)

    @staticmethod
    def union_layout(shards: "Sequence[HaloShards]") -> tuple:
        """Smallest layout covering every given shards object — build
        probes unconstrained, union them, rebuild with the union."""
        hops_u = sorted(set().union(*[s.hops for s in shards]))
        caps_u = tuple(
            max(
                (
                    s.caps[s.hops.index(k)] if k in s.hops else 8
                    for s in shards
                ),
                default=8,
            )
            for k in hops_u
        )
        return (
            max(s.e_loc for s in shards),
            tuple(hops_u),
            caps_u,
        )

    @property
    def n_loc(self) -> int:
        return self.num_nodes_padded // self.n_shards

    @property
    def halo_rows(self) -> int:
        """Per-device feature rows a layer materializes (vs N_pad for
        the all-gather path) — the memory-model number."""
        return self.n_loc + sum(self.caps)

    @staticmethod
    def build(
        x: np.ndarray,
        pos: np.ndarray,
        edge_index: np.ndarray,
        n_shards: int,
        layout: Optional[tuple] = None,
    ) -> "HaloShards":
        """``layout`` (a ``.layout`` tuple / ``union_layout`` result)
        pins the static shapes so successive configurations of the same
        structure share one compiled executable; raises when this
        graph's needs exceed it."""
        n = x.shape[0]
        d_ = n_shards
        n_pad = ((n + d_ - 1) // d_) * d_
        n_loc = n_pad // d_
        snd = np.asarray(edge_index[0], np.int64)
        rcv = np.asarray(edge_index[1], np.int64)
        owner_r = rcv // n_loc
        owner_s = snd // n_loc

        # Per-device edge slots (receiver-owned), one shared capacity.
        by_dev = [np.nonzero(owner_r == d)[0] for d in range(d_)]
        e_loc = max((len(ix) for ix in by_dev), default=1)
        e_loc = max(((e_loc + 7) // 8) * 8, 8)
        if layout is not None and layout[0] < e_loc:
            raise ValueError(
                f"layout e_loc={layout[0]} < needed {e_loc}"
            )
        if layout is not None:
            e_loc = layout[0]

        # Send lists: rows device s must ship to s+k+1 (sorted global
        # ids -> positions are binary-searchable for the remap below).
        send_lists = [
            [np.zeros(0, np.int64) for _ in range(d_ - 1)]
            for _ in range(d_)
        ]
        for d in range(d_):
            ed = by_dev[d]
            remote = ed[owner_s[ed] != d]
            for s in np.unique(owner_s[remote]):
                k = (d - s) % d_ - 1
                send_lists[int(s)][int(k)] = np.unique(
                    snd[remote[owner_s[remote] == s]]
                )
        cap_by_hop = [
            max(len(send_lists[s][k]) for s in range(d_))
            for k in range(d_ - 1)
        ]
        hops = [k for k in range(d_ - 1) if cap_by_hop[k] > 0]
        caps = tuple(
            max(((cap_by_hop[k] + 7) // 8) * 8, 8) for k in hops
        )
        if layout is not None:
            _, lay_hops, lay_caps = layout
            for k, c in zip(hops, caps):
                if k not in lay_hops:
                    raise ValueError(
                        f"layout lacks required hop {k}"
                    )
                if lay_caps[lay_hops.index(k)] < c:
                    raise ValueError(
                        f"layout cap {lay_caps[lay_hops.index(k)]} < "
                        f"needed {c} at hop {k}"
                    )
            hops = list(lay_hops)
            caps = tuple(lay_caps)
        cap_max = max(caps, default=8)
        send_idx = np.zeros((d_, max(len(hops), 1), cap_max), np.int32)
        for s in range(d_):
            for ki, k in enumerate(hops):
                rows = send_lists[s][k] - s * n_loc  # local ids
                send_idx[s, ki, : len(rows)] = rows

        # Halo-local sender remap + per-device edge arrays.
        offsets = {}
        off = n_loc
        for ki, k in enumerate(hops):
            offsets[k] = off
            off += caps[ki]
        sh = np.zeros(d_ * e_loc, np.int32)
        rl = np.zeros(d_ * e_loc, np.int32)
        em = np.zeros(d_ * e_loc, bool)
        for d in range(d_):
            base = d * e_loc
            for j, e in enumerate(by_dev[d]):
                rl[base + j] = rcv[e] - d * n_loc
                s = int(owner_s[e])
                if s == d:
                    sh[base + j] = snd[e] - d * n_loc
                else:
                    k = (d - s) % d_ - 1
                    lst = send_lists[s][k]
                    sh[base + j] = offsets[k] + int(
                        np.searchsorted(lst, snd[e])
                    )
                em[base + j] = True

        xp = np.zeros((n_pad, x.shape[1]), np.float32)
        xp[:n] = x
        pp = np.zeros((n_pad, 3), np.float32)
        pp[:n] = pos
        nm = np.zeros(n_pad, bool)
        nm[:n] = True
        return HaloShards(
            x=jnp.asarray(xp),
            pos=jnp.asarray(pp),
            node_mask=jnp.asarray(nm),
            senders_halo=jnp.asarray(sh),
            receivers_local=jnp.asarray(rl),
            edge_mask=jnp.asarray(em),
            send_idx=jnp.asarray(send_idx),
            caps=caps,
            num_nodes_padded=n_pad,
            n_shards=d_,
            hops=tuple(hops),
            e_loc=e_loc,
        )

    def device_put(self, mesh: Mesh) -> "HaloShards":
        s = NamedSharding(mesh, P(AXIS))
        return dataclasses.replace(
            self,
            x=jax.device_put(self.x, s),
            pos=jax.device_put(self.pos, s),
            node_mask=jax.device_put(self.node_mask, s),
            senders_halo=jax.device_put(self.senders_halo, s),
            receivers_local=jax.device_put(self.receivers_local, s),
            edge_mask=jax.device_put(self.edge_mask, s),
            send_idx=jax.device_put(self.send_idx, s),
        )


def halo_exchange(
    x_loc: jax.Array,  # [n_loc, F] this device's rows (inside shard_map)
    send_idx: jax.Array,  # [K, cap_max] local rows to send per hop
    caps: Tuple[int, ...],
    hops: Tuple[int, ...],
    n_shards: int,
    axis: str = AXIS,
) -> jax.Array:
    """[n_loc, F] -> [n_loc + sum(caps), F]: local rows followed by one
    received block per active ring-hop distance. One ppermute per hop
    moves only each neighbor's boundary rows; the transpose (for grad)
    is the reverse ppermute, derived automatically."""
    parts = [x_loc]
    for ki, k in enumerate(hops):
        send = x_loc[send_idx[ki, : caps[ki]]]
        perm = [(d, (d + k + 1) % n_shards) for d in range(n_shards)]
        parts.append(jax.lax.ppermute(send, axis, perm))
    return jnp.concatenate(parts, axis=0)


def halo_mpnn_forward(
    params: Dict,
    shards: HaloShards,
    mesh: Mesh,
    *,
    cutoff: float,
    num_gaussians: int,
    num_layers: int,
    attn_heads: int = 0,
) -> jax.Array:
    """``sharded_mpnn_forward`` semantics with halo exchange instead of
    all-gather: per layer each device materializes n_loc + halo rows
    (``shards.halo_rows``), never the full [N, F] array, and the
    message scatter is a LOCAL segment-sum (edges live with their
    receiver). Global attention still rides ``ring_attention`` (which
    never gathers either). Returns a replicated scalar; differentiable.
    """
    n_shards = shards.n_shards
    n_loc = shards.n_loc
    caps, hops = shards.caps, shards.hops

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(),) + (P(AXIS),) * 7,
        out_specs=P(),
    )
    def fwd(params, x, pos, node_mask, snd_halo, rcv_loc, edge_mask, send_idx):
        send_idx = send_idx[0]  # [1, K, cap] -> [K, cap]

        def exchange(arr):
            return halo_exchange(
                arr, send_idx, caps, hops, n_shards
            )

        h = _dense(params["embed"], x)
        pos_h = exchange(pos)
        vec = pos_h[snd_halo] - pos_h[rcv_loc]
        d = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + 1e-12)
        rbf = gaussian_smearing(d, 0.0, cutoff, num_gaussians)
        w_cut = (
            cosine_cutoff(d, cutoff) * edge_mask.astype(h.dtype)
        )[:, None]
        for i in range(num_layers):
            filt = jax.nn.silu(_dense(params[f"filter_{i}"], rbf)) * w_cut
            h_s = exchange(h)[snd_halo]
            agg = jax.ops.segment_sum(
                h_s * filt, rcv_loc, num_segments=n_loc
            )
            h = h + jax.nn.silu(_dense(params[f"update_{i}"], agg))
            if attn_heads:
                ap = params[f"attn_{i}"]
                hidden = h.shape[1]
                dh = hidden // attn_heads

                def heads(p):
                    return _dense(p, h).reshape(n_loc, attn_heads, dh)

                attn = ring_attention(
                    heads(ap["q"]),
                    heads(ap["k"]),
                    heads(ap["v"]),
                    node_mask,
                    n_shards=n_shards,
                )
                attn = _dense(ap["out"], attn.reshape(n_loc, hidden))
                h = h + attn * node_mask.astype(h.dtype)[:, None]
        node_e = _dense(params["readout"], h)[:, 0]
        node_e = node_e * node_mask.astype(node_e.dtype)
        return jax.lax.psum(jnp.sum(node_e), AXIS)

    return fwd(
        params,
        shards.x,
        shards.pos,
        shards.node_mask,
        shards.senders_halo,
        shards.receivers_local,
        shards.edge_mask,
        shards.send_idx,
    )


def gather_nodes(x_shard: jax.Array, idx_global: jax.Array) -> jax.Array:
    """Edge-side gather of node features: all_gather over ICI, then a
    local row gather. [N/D, F], [E/D] -> [E/D, F]."""
    full = jax.lax.all_gather(x_shard, AXIS, axis=0, tiled=True)
    return full[idx_global]


def scatter_nodes(
    msg: jax.Array, idx_global: jax.Array, num_nodes_padded: int
) -> jax.Array:
    """Edge-side scatter back to node shards: local full-range partial
    segment-sum, then reduce-scatter. [E/D, F], [E/D] -> [N/D, F]."""
    partial_sum = jax.ops.segment_sum(
        msg, idx_global, num_segments=num_nodes_padded
    )
    return jax.lax.psum_scatter(
        partial_sum, AXIS, scatter_dimension=0, tiled=True
    )


def ring_attention(
    q: jax.Array,  # [n_loc, H, Dh] local query block
    k: jax.Array,  # [n_loc, H, Dh] local key block
    v: jax.Array,  # [n_loc, H, Dh] local value block
    kv_mask: jax.Array,  # [n_loc] bool, valid rows of the LOCAL kv block
    *,
    n_shards: int,
    axis: str = AXIS,
) -> jax.Array:
    """Exact global attention over ALL nodes of a sharded graph — ring
    attention (the sequence-parallel long-context algorithm), GNN role:
    the GPS global-attention layer for graphs too large for one chip.

    K/V blocks rotate around the mesh axis via ``ppermute`` (one ICI hop
    per step, overlapping the local [n_loc, n_loc] MXU matmul) while
    each device keeps online-softmax accumulators (running max m,
    denominator l, output o) — so no device ever materializes the full
    [N, N] score matrix or the gathered K/V. Must be called inside
    ``shard_map`` over ``axis``. Returns [n_loc, H, Dh].
    """
    scale = q.shape[-1] ** -0.5
    neg = jnp.asarray(jnp.finfo(q.dtype).min, q.dtype)
    # Derive accumulators from q so they carry the same shard_map
    # "varying over axis" type as the per-step outputs (a plain
    # jnp.full would be unvaried and trip scan's carry type check).
    m = jnp.full_like(q[..., 0], neg)  # [n_loc, H]
    l = jnp.zeros_like(q[..., 0])
    o = jnp.zeros_like(q)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def accumulate(m, l, o, k, v, kv_mask):
        s = jnp.einsum("qhd,khd->qhk", q * scale, k)
        s = jnp.where(kv_mask[None, None, :], s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        # masked columns contribute exp(neg - m) ~ 0 but force exact 0
        p = jnp.where(kv_mask[None, None, :], p, 0.0)
        # graftlint: disable-next-line=fp-contract -- online-softmax rescale IS the algorithm: the mul+add runs on every shard's accumulator identically, and ring attention carries no bitwise contract (tests gate vs dense reference at fp tolerance)
        l = l * corr + jnp.sum(p, axis=-1)
        # graftlint: disable-next-line=fp-contract -- same rescale on the output accumulator; hoisting the multiply would materialize the [n_loc, n_loc] score block the ring exists to avoid
        o = o * corr[..., None] + jnp.einsum("qhk,khd->qhd", p, v)
        return m_new, l, o

    def step(carry, _):
        m, l, o, k, v, kv_mask = carry
        m, l, o = accumulate(m, l, o, k, v, kv_mask)
        k = jax.lax.ppermute(k, axis, perm)
        v = jax.lax.ppermute(v, axis, perm)
        kv_mask = jax.lax.ppermute(kv_mask, axis, perm)
        return (m, l, o, k, v, kv_mask), None

    # n_shards-1 (compute, rotate) steps + an epilogue compute on the
    # final block — no wasted trailing ppermute hop.
    (m, l, o, k, v, kv_mask), _ = jax.lax.scan(
        step, (m, l, o, k, v, kv_mask), None, length=n_shards - 1
    )
    m, l, o = accumulate(m, l, o, k, v, kv_mask)
    return o / jnp.maximum(l[..., None], 1e-20)


def init_params(
    key,
    in_dim: int,
    hidden: int,
    num_layers: int,
    num_gaussians: int,
    attn_heads: int = 0,
) -> Dict:
    keys = jax.random.split(key, 3 * num_layers + 2)
    params: Dict = {"embed": _dense_init(keys[0], in_dim, hidden)}
    for i in range(num_layers):
        params[f"filter_{i}"] = _dense_init(
            keys[3 * i + 1], num_gaussians, hidden
        )
        params[f"update_{i}"] = _dense_init(keys[3 * i + 2], hidden, hidden)
        if attn_heads:
            akeys = jax.random.split(keys[3 * i + 3], 4)
            params[f"attn_{i}"] = {
                nm: _dense_init(akeys[j], hidden, hidden)
                for j, nm in enumerate(("q", "k", "v", "out"))
            }
    params["readout"] = _dense_init(keys[-1], hidden, 1)
    return params


def _dense_init(key, fan_in, fan_out):
    w = jax.random.normal(key, (fan_in, fan_out)) / jnp.sqrt(fan_in)
    return {"w": w, "b": jnp.zeros(fan_out)}


def _dense(p, x):
    return x @ p["w"] + p["b"]


def sharded_mpnn_forward(
    params: Dict,
    shards: GraphShards,
    mesh: Mesh,
    *,
    cutoff: float,
    num_gaussians: int,
    num_layers: int,
    attn_heads: int = 0,
) -> jax.Array:
    """Total energy of one sharded graph: SchNet-style CFConv layers +
    node-energy readout, all node/edge tensors sharded over ``AXIS``.

    With ``attn_heads`` > 0 each layer adds a GPS-style GLOBAL attention
    branch computed by ring attention — every node attends to every
    node of the giant graph without any device holding the full K/V
    (the long-context path; see ``ring_attention``).

    Returns a replicated scalar; differentiable (forces = -grad wrt
    shards.pos work through the collectives).
    """
    n_shards = int(mesh.shape[AXIS])

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            P(),  # params replicated
            P(AXIS),  # x
            P(AXIS),  # pos
            P(AXIS),  # node_mask
            P(AXIS),  # senders
            P(AXIS),  # receivers
            P(AXIS),  # edge_mask
        ),
        out_specs=P(),
    )
    def fwd(params, x, pos, node_mask, snd, rcv, edge_mask):
        n_pad = shards.num_nodes_padded
        h = _dense(params["embed"], x)
        # edge geometry from gathered endpoint positions
        pos_s = gather_nodes(pos, snd)
        pos_r = gather_nodes(pos, rcv)
        vec = pos_s - pos_r
        d = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + 1e-12)
        rbf = gaussian_smearing(d, 0.0, cutoff, num_gaussians)
        w_cut = (
            cosine_cutoff(d, cutoff) * edge_mask.astype(h.dtype)
        )[:, None]
        for i in range(num_layers):
            filt = jax.nn.silu(_dense(params[f"filter_{i}"], rbf)) * w_cut
            h_s = gather_nodes(h, snd)
            agg = scatter_nodes(h_s * filt, rcv, n_pad)
            h = h + jax.nn.silu(_dense(params[f"update_{i}"], agg))
            if attn_heads:
                ap = params[f"attn_{i}"]
                n_loc, hidden = h.shape
                dh = hidden // attn_heads

                def heads(p):
                    return _dense(p, h).reshape(n_loc, attn_heads, dh)

                attn = ring_attention(
                    heads(ap["q"]),
                    heads(ap["k"]),
                    heads(ap["v"]),
                    node_mask,
                    n_shards=n_shards,
                )
                attn = _dense(ap["out"], attn.reshape(n_loc, hidden))
                h = h + attn * node_mask.astype(h.dtype)[:, None]
        node_e = _dense(params["readout"], h)[:, 0]
        node_e = node_e * node_mask.astype(node_e.dtype)
        return jax.lax.psum(jnp.sum(node_e), AXIS)

    return fwd(
        params,
        shards.x,
        shards.pos,
        shards.node_mask,
        shards.senders,
        shards.receivers,
        shards.edge_mask,
    )


def reference_mpnn_forward(
    params: Dict,
    x: jax.Array,
    pos: jax.Array,
    node_mask: jax.Array,
    senders: jax.Array,
    receivers: jax.Array,
    edge_mask: jax.Array,
    *,
    cutoff: float,
    num_gaussians: int,
    num_layers: int,
    attn_heads: int = 0,
) -> jax.Array:
    """Single-device computation of the same model (differential test)."""
    n_pad = x.shape[0]
    h = _dense(params["embed"], x)
    vec = pos[senders] - pos[receivers]
    d = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + 1e-12)
    rbf = gaussian_smearing(d, 0.0, cutoff, num_gaussians)
    w_cut = (cosine_cutoff(d, cutoff) * edge_mask.astype(h.dtype))[:, None]
    for i in range(num_layers):
        filt = jax.nn.silu(_dense(params[f"filter_{i}"], rbf)) * w_cut
        agg = jax.ops.segment_sum(
            h[senders] * filt, receivers, num_segments=n_pad
        )
        h = h + jax.nn.silu(_dense(params[f"update_{i}"], agg))
        if attn_heads:
            # dense masked softmax attention — the exact math ring
            # attention must reproduce blockwise
            ap = params[f"attn_{i}"]
            dh = h.shape[1] // attn_heads

            def heads(p):
                return _dense(p, h).reshape(n_pad, attn_heads, dh)

            q, k, v = heads(ap["q"]), heads(ap["k"]), heads(ap["v"])
            s = jnp.einsum("qhd,khd->qhk", q * dh**-0.5, k)
            neg = jnp.asarray(jnp.finfo(s.dtype).min, s.dtype)
            s = jnp.where(node_mask[None, None, :], s, neg)
            p = jax.nn.softmax(s, axis=-1)
            attn = jnp.einsum("qhk,khd->qhd", p, v).reshape(n_pad, -1)
            attn = _dense(ap["out"], attn)
            h = h + attn * node_mask.astype(h.dtype)[:, None]
    node_e = _dense(params["readout"], h)[:, 0]
    return jnp.sum(node_e * node_mask.astype(node_e.dtype))
