"""Static-shape graph batch representation.

Replaces PyG's ragged ``Batch`` (reference: hydragnn relies on
torch_geometric.data.Batch throughout, e.g. hydragnn/models/Base.py:697
``forward(data)``) with a padded, masked, bucket-shaped pytree so that XLA
traces once per bucket and every op tiles onto the MXU.

Conventions
-----------
- Nodes of all graphs in a batch are concatenated, then padded to
  ``num_nodes`` (a bucket size). Padding nodes have ``node_mask == False``
  and belong to trailing "padding graphs" (jraph-style), so segment
  reductions stay correct without per-op masking.
- Edges are directed: ``senders[k] -> receivers[k]``; messages are
  aggregated at ``receivers``. Padding edges connect padding nodes and have
  ``edge_mask == False``.
- Graph slots are padded to ``num_graphs``; at least one trailing slot is a
  padding graph absorbing padded nodes/edges (``graph_mask == False``).
- Targets are stored densely per level: ``y_graph [G, Dg]`` and
  ``y_node [N, Dn]``, where Dg/Dn are the concatenated head dims (the
  reference packs both into a flat ``data.y`` with ``y_loc`` offsets,
  hydragnn/preprocess/graph_samples_checks_and_updates.py:604-645; a dense
  two-level layout is the static-shape equivalent).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct


@struct.dataclass
class GraphBatch:
    """A padded batch of graphs with static shapes.

    Shape glossary: N = padded node count, E = padded edge count,
    G = padded graph count (including >=1 padding graph slot).
    """

    # Node-level
    x: jax.Array  # [N, F] invariant node input features
    pos: Optional[jax.Array]  # [N, 3] positions (None for position-free data)
    node_graph_idx: jax.Array  # [N] int32, graph id of each node
    node_slot: jax.Array  # [N] int32, index of node within its graph
    node_mask: jax.Array  # [N] bool

    # Edge-level
    senders: jax.Array  # [E] int32 source node ids
    receivers: jax.Array  # [E] int32 destination node ids
    edge_mask: jax.Array  # [E] bool

    # Graph-level
    graph_mask: jax.Array  # [G] bool

    # Optional payloads
    edge_attr: Optional[jax.Array] = None  # [E, Fe]
    edge_shifts: Optional[jax.Array] = None  # [E, 3] PBC displacement shifts
    y_graph: Optional[jax.Array] = None  # [G, Dg] packed graph targets
    y_node: Optional[jax.Array] = None  # [N, Dn] packed node targets
    graph_attr: Optional[jax.Array] = None  # [G, Da] graph conditioning attrs
    dataset_id: Optional[jax.Array] = None  # [G] int32 branch/dataset id
    pe: Optional[jax.Array] = None  # [N, pe_dim] Laplacian positional enc.
    rel_pe: Optional[jax.Array] = None  # [E, pe_dim] relative PE
    cell: Optional[jax.Array] = None  # [G, 3, 3] lattice vectors
    energy_weight: Optional[jax.Array] = None  # [G] per-graph loss weight
    energy: Optional[jax.Array] = None  # [G] total energy (MLIP targets)
    forces: Optional[jax.Array] = None  # [N, 3] per-atom forces (MLIP)

    # Angular triplets (DimeNet): for each triplet t, edge t_kj[t] = k->j
    # feeds edge t_ji[t] = j->i (reference triplets(),
    # hydragnn/models/DIMEStack.py:233-283 — computed host-side here so
    # shapes stay static under jit).
    t_kj: Optional[jax.Array] = None  # [T] int32 edge index of k->j
    t_ji: Optional[jax.Array] = None  # [T] int32 edge index of j->i
    triplet_mask: Optional[jax.Array] = None  # [T] bool

    # Optional Pallas sorted-segment plan for receiver aggregation
    # (ops/pallas_segment.py): host-computed block plan shipped as batch
    # data; requires edges sorted by receiver (collate with_segment_plan).
    seg_perm: Optional[jax.Array] = None  # [B*be] int32
    seg_ids: Optional[jax.Array] = None  # [B*be] int32
    seg_valid: Optional[jax.Array] = None  # [B*be] bool
    seg_window: Optional[jax.Array] = None  # [B] int32

    # Collation's promise that ``receivers`` is nondecreasing (padding
    # edges included: they target the first padding node). Static, so a
    # jitted step reads it at trace time: the receiver aggregation then
    # tells XLA its scatter's indices are sorted (ops/segment.py).
    # Whoever writes other receivers into a batch must drop it.
    receivers_sorted: bool = struct.field(pytree_node=False, default=False)
    # Collation's promise that ``t_ji`` is nondecreasing, padding triplets
    # included (they point at the last edge slot, ``fill_triplets``): the
    # triplet reduce then tells XLA its scatter's indices are sorted
    # (ops/segment.py). Whoever writes other triplets into a batch must
    # drop it.
    triplets_sorted: bool = struct.field(pytree_node=False, default=False)

    # ------------------------------------------------------------------
    @property
    def num_nodes(self) -> int:
        return self.x.shape[0]

    @property
    def num_edges(self) -> int:
        return self.senders.shape[0]

    @property
    def num_graphs(self) -> int:
        return self.graph_mask.shape[0]

    @property
    def nodes_per_graph(self) -> jax.Array:
        """[G] number of real nodes in each graph."""
        return jax.ops.segment_sum(
            self.node_mask.astype(jnp.int32),
            self.node_graph_idx,
            num_segments=self.num_graphs,
        )

    @property
    def max_nodes_per_graph(self) -> int:
        """Static upper bound for dense (to_dense_batch-style) layouts.

        Computed over REAL nodes only: padding slots count up to the
        padded remainder, which under bin-packed batches (tail bins)
        can far exceed any real graph's size."""
        slots = np.asarray(jax.device_get(self.node_slot))
        mask = np.asarray(jax.device_get(self.node_mask))
        if not mask.any():
            return 0
        return int(slots[mask].max()) + 1


@struct.dataclass
class MacroBatch:
    """K same-spec batches stacked on a new leading axis — the payload
    of one superstep dispatch (train/loop.make_superstep_fn scans the
    leading axis, running K optimizer steps inside one jitted call).

    ``batch`` is an ordinary GraphBatch whose every array leaf carries
    a leading ``[K]`` dimension; ``k`` is static metadata (not a pytree
    leaf), so ``jax.device_put`` / ``tree_map`` treat a MacroBatch
    exactly like its stacked arrays. Loaders yield MacroBatches for
    full superstep groups and plain GraphBatches for run tails
    (padschedule.superstep_groups defines the grouping)."""

    batch: GraphBatch
    k: int = struct.field(pytree_node=False, default=1)


def stack_batches(batches: Sequence[GraphBatch]) -> MacroBatch:
    """Stack same-spec (numpy-backed) GraphBatches into a MacroBatch.

    All batches must share one padded spec and one optional-field
    presence pattern (guaranteed when they come from the same loader's
    same-spec superstep group); ``tree_map`` enforces matching pytree
    structures loudly otherwise."""
    stacked = jax.tree_util.tree_map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *batches
    )
    return MacroBatch(batch=stacked, k=len(batches))


@dataclasses.dataclass
class GraphSample:
    """One graph on the host (numpy), pre-collation.

    The host-side analog of a PyG ``Data`` object (reference builds these in
    hydragnn/preprocess/serialized_dataset_loader.py:130-204).
    """

    x: np.ndarray  # [n, F]
    pos: Optional[np.ndarray] = None  # [n, 3]
    edge_index: Optional[np.ndarray] = None  # [2, e] (senders, receivers)
    edge_attr: Optional[np.ndarray] = None  # [e, Fe]
    edge_shifts: Optional[np.ndarray] = None  # [e, 3]
    y_graph: Optional[np.ndarray] = None  # [Dg]
    y_node: Optional[np.ndarray] = None  # [n, Dn]
    graph_attr: Optional[np.ndarray] = None  # [Da]
    dataset_id: int = 0
    pe: Optional[np.ndarray] = None  # [n, pe_dim]
    rel_pe: Optional[np.ndarray] = None  # [e, pe_dim]
    cell: Optional[np.ndarray] = None  # [3, 3]
    energy: Optional[float] = None  # total energy (MLIP target)
    forces: Optional[np.ndarray] = None  # [n, 3] per-atom forces (MLIP)

    @property
    def num_nodes(self) -> int:
        return int(self.x.shape[0])

    @property
    def num_edges(self) -> int:
        return 0 if self.edge_index is None else int(self.edge_index.shape[1])


# Input-side optional fields: zero-filling an absent one is semantically
# "no feature" (open boundary, no conditioning attr, no PE), so mixed
# datasets may materialize them everywhere for one pytree structure.
_ZERO_FILL_FIELDS = ("edge_attr", "edge_shifts", "rel_pe", "pe", "graph_attr")
# Fields where zero-filling would silently corrupt training (zero force
# labels, zero positions): presence must be all-or-none over a dataset.
_ALL_OR_NONE_FIELDS = ("pos", "energy", "forces", "y_graph", "y_node")


def optional_field_widths(dataset) -> dict:
    """{optional field -> last-dim width} over a whole dataset — the
    ``ensure_fields`` map for collate, so every batch of a mixed
    dataset materializes the same optional fields (one pytree
    structure). Single pass; validates that widths are consistent and
    that label/position fields are present on all samples or none
    (zero-filled targets would silently train toward 0 — the same
    hazard collate's per-batch partially-labeled check guards).
    ``cell`` maps to None (collate membership-tests the key only).

    Container datasets that can derive the map from their own metadata
    (BinDataset headers, pickle meta) expose ``field_widths()`` and
    skip the scan entirely; otherwise the scan result is cached on the
    dataset object so several loaders over one lazy dataset pay the
    disk pass once (ADIOS attribute-cache parity,
    reference hydragnn/utils/datasets/adiosdataset.py attrs cache)."""
    fw = getattr(dataset, "field_widths", None)
    if callable(fw):
        meta = fw()
        if meta is not None:
            return dict(meta)
    cached = getattr(dataset, "_cached_field_widths", None)
    if cached is not None:
        return dict(cached)
    widths: dict = {}
    present = {f: 0 for f in _ALL_OR_NONE_FIELDS}
    has_cell = False
    n = 0
    for s in dataset:
        n += 1
        for f in _ZERO_FILL_FIELDS + _ALL_OR_NONE_FIELDS:
            v = getattr(s, f)
            if v is None:
                continue
            if f in _ALL_OR_NONE_FIELDS:
                present[f] += 1
            if f == "energy":
                continue  # scalar, no width
            w = int(np.atleast_2d(v).shape[-1])
            if widths.setdefault(f, w) != w:
                raise ValueError(
                    f"Inconsistent {f} widths across the dataset: "
                    f"{widths[f]} vs {w} — homogeneous batches would "
                    "collate to divergent shapes"
                )
        if s.cell is not None:
            has_cell = True
    for f, c in present.items():
        if 0 < c < n:
            raise ValueError(
                f"Partially-labeled dataset: {f} present on {c}/{n} "
                "samples; label and position fields must be present on "
                "all samples or none"
            )
    out = {f: widths[f] for f in _ZERO_FILL_FIELDS if f in widths}
    if has_cell:
        out["cell"] = None
    try:
        dataset._cached_field_widths = dict(out)
    except (AttributeError, TypeError):
        pass  # plain lists/tuples can't carry the cache
    return out


def optional_field_widths_multi(datasets) -> dict:
    """One ``ensure_fields`` map over several datasets (train/val/test
    splits), each resolved through its own metadata fast path
    (``field_widths()`` / cached scan) and merged — so lazy containers
    are NOT concatenated into one materialized list just to compute the
    union. Validates the same hazards the single-dataset scan does:
    width conflicts across datasets, and label/position fields present
    on some splits but not others (checked from one sample per dataset
    — presence is all-or-none within a dataset by construction)."""
    datasets = [d for d in datasets if len(d)]
    out: dict = {}
    for d in datasets:
        m = optional_field_widths(d)
        for k, w in m.items():
            if k in out and out[k] != w:
                raise ValueError(
                    f"Inconsistent {k} widths across datasets: "
                    f"{out[k]} vs {w} — homogeneous batches would "
                    "collate to divergent shapes"
                )
            out.setdefault(k, w)
    def _presence(d):
        lf = getattr(d, "label_fields", None)
        if callable(lf):
            return lf()  # header metadata, no payload decode
        return frozenset(
            f for f in _ALL_OR_NONE_FIELDS if getattr(d[0], f) is not None
        )

    presence = [_presence(d) for d in datasets]
    if presence and any(p != presence[0] for p in presence[1:]):
        raise ValueError(
            "Partially-labeled dataset: label/position fields differ "
            f"across datasets ({[sorted(p) for p in presence]}); "
            "fields must be present on all splits or none"
        )
    return out


def select_input_features(samples, input_cols):
    """Column-select every sample's node features (the reference applies
    Variables_of_interest.input_node_features data-side,
    hydragnn/preprocess/graph_samples_checks_and_updates.py:648-659).

    Returns ``samples`` unchanged (same object — lazy datasets like
    BinDataset stay lazy) when the selection already covers the first
    sample's columns in order; raw-ingested datasets (data/raw.py)
    arrive pre-selected. Otherwise materializes a selected list.
    """
    if input_cols is None or len(samples) == 0:
        return samples
    cols = [int(c) for c in input_cols]
    if not cols:
        return samples
    if min(cols) < 0:
        raise ValueError(
            f"input_node_features {cols} must be non-negative column "
            "indices"
        )
    if cols == list(range(int(samples[0].x.shape[1]))):
        return samples

    out = []
    for s in samples:
        width = int(s.x.shape[1])
        if max(cols) >= width:
            raise ValueError(
                f"input_node_features {cols} out of range for node "
                f"features of width {width}"
            )
        out.append(
            dataclasses.replace(s, x=np.ascontiguousarray(s.x[:, cols]))
        )
    return out


# ----------------------------------------------------------------------
# Bucketing: round padded sizes up a geometric ladder so XLA compiles a
# small, bounded set of shapes (SURVEY.md §7 "bucketed padding").
# ----------------------------------------------------------------------

def bucket_size(n: int, *, base: int = 8, growth: float = 1.25) -> int:
    """Smallest ladder value >= n; ladder = base * growth^k, rounded to 8.

    A multiple-of-8 floor keeps the last dimension lane-friendly on TPU.
    """
    if n <= base:
        return base
    size = float(base)
    while size < n:
        size *= growth
    return int(int(np.ceil(size / 8.0)) * 8)


def build_triplets(
    senders: np.ndarray, receivers: np.ndarray, num_nodes: int
) -> tuple[np.ndarray, np.ndarray]:
    """Enumerate angular triplets: pairs of edges (k->j, j->i), k != i.

    Host-side numpy analog of the reference's ``triplets`` helper
    (hydragnn/models/DIMEStack.py:233-283). Returns (t_kj, t_ji) arrays of
    edge indices.
    """
    E = int(len(senders))
    if E == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z
    order = np.argsort(receivers, kind="stable")
    counts_in = np.bincount(receivers, minlength=num_nodes)
    ptr = np.zeros(num_nodes + 1, dtype=np.int64)
    ptr[1:] = np.cumsum(counts_in)
    deg = counts_in[senders]  # incoming edges at j for each edge j->i
    total = int(deg.sum())
    if total == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z
    t_ji_all = np.repeat(np.arange(E, dtype=np.int64), deg)
    seg_off = np.cumsum(deg) - deg
    local = np.arange(total, dtype=np.int64) - np.repeat(seg_off, deg)
    t_kj_all = order[ptr[senders[t_ji_all]] + local]
    valid = senders[t_kj_all] != receivers[t_ji_all]
    return t_kj_all[valid], t_ji_all[valid]


def count_triplets(sample: "GraphSample") -> int:
    """Number of angular triplets a sample contributes (for PadSpec).

    O(E log E) without materializing the triplets: each edge j->i pairs
    with indeg(j) incoming edges minus one if the reciprocal edge i->j
    exists (the k == i exclusion).
    """
    if sample.edge_index is None or sample.num_edges == 0:
        return 0
    snd = np.asarray(sample.edge_index[0], dtype=np.int64)
    rcv = np.asarray(sample.edge_index[1], dtype=np.int64)
    n = int(sample.num_nodes)
    indeg = np.bincount(rcv, minlength=n)
    total = int(indeg[snd].sum())
    keys = snd * n + rcv
    reciprocal = int(np.isin(rcv * n + snd, keys).sum())
    return total - reciprocal


def sort_edges_by_receiver(
    senders, receivers, edge_mask, edge_payloads, e_real
) -> bool:
    """Bring the REAL edges into receiver order IN PLACE (padding edges
    already target the first padding node, which sorts after every real
    receiver): a check for monotone receivers first, a stable sort of
    every edge-aligned array only when it fails. Returns whether
    anything moved."""
    rcv = receivers[:e_real]
    if e_real < 2 or bool(np.all(rcv[1:] >= rcv[:-1])):
        return False
    order = np.argsort(rcv, kind="stable")
    for arr in (senders, receivers, edge_mask):
        arr[:e_real] = arr[:e_real][order]
    for arr in edge_payloads.values():
        if arr is not None:
            arr[:e_real] = arr[:e_real][order]
    return True


def apply_segment_plan(senders, receivers, edge_mask, edge_payloads, e_real, N):
    """Sort REAL edges by receiver IN PLACE (``sort_edges_by_receiver``)
    and build the static-size block plan for the Pallas aggregation
    kernel. ``N`` is the padded node count; returns (seg_perm, seg_ids,
    seg_valid, seg_window)."""
    from hydragnn_tpu.ops.pallas_segment import (
        plan_blocks_static,
        static_block_bound,
    )

    sort_edges_by_receiver(senders, receivers, edge_mask, edge_payloads, e_real)
    b_max = static_block_bound(receivers.shape[0], N)
    # The edge mask is FOLDED INTO the plan's valid slots: padding
    # edges never enter the in-kernel gather, so the aggregation ops
    # need no pre-masked copy of the edge data (the HBM write the
    # fused kernel exists to avoid).
    return plan_blocks_static(receivers, N, b_max, edge_valid=edge_mask)


def order_and_plan_edges(
    senders, receivers, edge_mask, edge_payloads, e_real, N, *,
    with_segment_plan: bool, sorted_receivers: bool,
) -> dict:
    """What collation does to a batch's edges once they are laid out,
    IN PLACE, as the GraphBatch fields it decides: the block plan
    (``apply_segment_plan``, which sorts) where one is wanted, else the
    sort alone where the spec asks for sorted receivers, and the
    promise ``receivers_sorted`` after either. The ONE implementation
    shared by ``collate`` and the packed collators (data/pipeline.py),
    whose contract is bit-identity with it."""
    plan = (None, None, None, None)
    if with_segment_plan:
        plan = apply_segment_plan(
            senders, receivers, edge_mask, edge_payloads, e_real, N
        )
    elif sorted_receivers:
        sort_edges_by_receiver(
            senders, receivers, edge_mask, edge_payloads, e_real
        )
    return dict(
        zip(("seg_perm", "seg_ids", "seg_valid", "seg_window"), plan),
        receivers_sorted=bool(with_segment_plan or sorted_receivers),
    )


def fill_triplets(t_kj, t_ji, triplet_mask, senders, receivers, e_real, n_real):
    """Build angular triplets into preallocated ``[T]`` buffers (may be
    ``np.empty`` — every slot is written). Padding triplets reference
    the last edge slot (a self-loop at the padding node) and are masked
    out of all reductions. Shared by ``collate`` and the packed
    collators.

    Keeps ``t_ji`` nondecreasing over all ``T`` slots, which the batch
    promises as ``triplets_sorted``: ``build_triplets`` enumerates the
    j->i edges in index order, and the padding's ``E - 1`` is at least
    every real edge index."""
    T = int(t_kj.shape[0])
    E = int(senders.shape[0])
    kj, ji = build_triplets(senders[:e_real], receivers[:e_real], n_real)
    if len(kj) > T:
        raise ValueError(
            f"PadSpec too small: {len(kj)} triplets > {T} slots"
        )
    t_kj[...] = E - 1
    t_ji[...] = E - 1
    triplet_mask[...] = False
    t_kj[: len(kj)] = kj
    t_ji[: len(ji)] = ji
    triplet_mask[: len(kj)] = True


@dataclasses.dataclass(frozen=True)
class PackSpec:
    """A bin-packing budget: one fixed padded batch shape plus the real
    capacities a packed batch may fill (data/padschedule.py fits a small
    set of these from the dataset size histogram; the loader first-fit-
    decreasing packs each epoch's graphs into them).

    ``num_nodes``/``num_graphs`` include the mandatory padding slot
    (collate needs one padding node for edge padding targets and one
    padding graph absorbing padded nodes/edges), so the real capacities
    are one less. Unlike the bucket ladder, a budget is not a ladder
    point — it is rounded only to the lane-friendly multiple of 8, since
    each budget compiles exactly once regardless of its value.
    """

    num_nodes: int
    num_edges: int
    num_graphs: int

    @property
    def capacity_nodes(self) -> int:
        return self.num_nodes - 1

    @property
    def capacity_edges(self) -> int:
        return self.num_edges

    @property
    def capacity_graphs(self) -> int:
        return self.num_graphs - 1

    def fits(self, n_nodes: int, n_edges: int, n_graphs: int) -> bool:
        return (
            n_nodes <= self.capacity_nodes
            and n_edges <= self.capacity_edges
            and n_graphs <= self.capacity_graphs
        )

    def pad_spec(self) -> "PadSpec":
        return PadSpec(
            num_nodes=self.num_nodes,
            num_edges=self.num_edges,
            num_graphs=self.num_graphs,
            num_triplets=None,
        )


@dataclasses.dataclass(frozen=True)
class PadSpec:
    """Static padded sizes for one bucket."""

    num_nodes: int
    num_edges: int
    num_graphs: int
    num_triplets: Optional[int] = None  # None = do not build triplets
    # collation brings the edges into receiver order and the batch says
    # so (GraphBatch.receivers_sorted)
    sorted_receivers: bool = False

    @staticmethod
    def for_samples(
        samples: Sequence[GraphSample],
        *,
        bucketed: bool = True,
        min_nodes: int = 8,
        min_edges: int = 8,
        with_triplets: bool = False,
    ) -> "PadSpec":
        tot_nodes = sum(s.num_nodes for s in samples)
        tot_edges = sum(s.num_edges for s in samples)
        # +1 node/graph slots: guarantee at least one padding node (edge
        # padding targets it) and one padding graph slot.
        n = tot_nodes + 1
        e = max(tot_edges, 1)
        g = len(samples) + 1
        t: Optional[int] = None
        if with_triplets:
            t = max(sum(count_triplets(s) for s in samples), 1)
        if bucketed:
            n = bucket_size(n, base=min_nodes)
            e = bucket_size(e, base=min_edges)
            if t is not None:
                t = bucket_size(t, base=min_edges)
        return PadSpec(num_nodes=n, num_edges=e, num_graphs=g, num_triplets=t)


def collate(
    samples: Sequence[GraphSample],
    pad: Optional[PadSpec] = None,
    *,
    dtype: Any = np.float32,
    with_segment_plan: bool = False,
    ensure_fields: Optional[dict] = None,
    as_numpy: bool = False,
) -> GraphBatch:
    """Concatenate and pad host graphs into a static-shape GraphBatch.

    Padding nodes/edges are assigned to graph slot ``len(samples)`` (the
    first padding graph) and node slot ``tot_nodes`` (the first padding
    node), so unmasked segment ops remain correct.

    ``ensure_fields`` maps optional field names to last-dim widths that
    must materialize (zero-filled) even when EVERY sample in this batch
    lacks them: a mixed dataset (e.g. periodic crystals + gas-phase
    molecules) must produce one pytree STRUCTURE across all its batches
    — presence differences recompile under jit and hard-fail dp device
    stacking. GraphLoader computes the map over its whole dataset.

    ``as_numpy`` keeps every field a host numpy array (no per-field
    device commit): the input pipeline (data/pipeline.py) collates in
    worker threads and performs ONE explicit device transfer later, so
    the jnp conversion here would serialize workers on the device queue.
    """
    if pad is None:
        pad = PadSpec.for_samples(samples)
    n_real = sum(s.num_nodes for s in samples)
    e_real = sum(s.num_edges for s in samples)
    g_real = len(samples)
    if n_real >= pad.num_nodes:
        raise ValueError(
            f"PadSpec too small: {n_real} real nodes need >= {n_real + 1} "
            f"padded slots, got {pad.num_nodes}"
        )
    if e_real > pad.num_edges or g_real >= pad.num_graphs:
        raise ValueError(
            f"PadSpec too small: edges {e_real}/{pad.num_edges}, "
            f"graphs {g_real}/{pad.num_graphs} (need one padding graph slot)"
        )

    N, E, G = pad.num_nodes, pad.num_edges, pad.num_graphs
    f_dim = samples[0].x.shape[1] if samples[0].x.ndim > 1 else 1

    x = np.zeros((N, f_dim), dtype=dtype)
    node_graph_idx = np.full((N,), g_real, dtype=np.int32)
    node_slot = np.zeros((N,), dtype=np.int32)
    node_mask = np.zeros((N,), dtype=bool)
    senders = np.full((E,), n_real, dtype=np.int32)
    receivers = np.full((E,), n_real, dtype=np.int32)
    edge_mask = np.zeros((E,), dtype=bool)
    graph_mask = np.zeros((G,), dtype=bool)
    graph_mask[:g_real] = True

    def _opt(field: str, width_of) -> Optional[np.ndarray]:
        vals = [getattr(s, field) for s in samples]
        if all(v is None for v in vals):
            if ensure_fields and field in ensure_fields:
                return np.zeros(
                    (width_of, int(ensure_fields[field])), dtype=dtype
                )
            return None
        dims = {np.atleast_2d(v).shape[-1] for v in vals if v is not None}
        if len(dims) != 1:
            raise ValueError(f"Inconsistent {field} dims across samples: {dims}")
        return np.zeros((width_of, dims.pop()), dtype=dtype)

    pos = _opt("pos", N)
    forces = _opt("forces", N)
    # Canonical per-edge payload set: every edge-aligned optional array
    # lives in this dict so the segment-plan sort below reorders ALL of
    # them together with senders/receivers — a new [E]-aligned field
    # only needs to be added here to stay aligned.
    edge_payloads = {
        f: _opt(f, E) for f in ("edge_attr", "edge_shifts", "rel_pe")
    }
    edge_attr = edge_payloads["edge_attr"]
    edge_shifts = edge_payloads["edge_shifts"]
    rel_pe = edge_payloads["rel_pe"]
    y_node = _opt("y_node", N)
    pe = _opt("pe", N)
    y_graph = _opt("y_graph", G)
    graph_attr = _opt("graph_attr", G)
    cell = None
    if any(s.cell is not None for s in samples) or (
        ensure_fields and "cell" in ensure_fields
    ):
        cell = np.tile(np.eye(3, dtype=dtype), (G, 1, 1))
    energy = None
    if any(s.energy is not None for s in samples):
        if not all(s.energy is not None for s in samples):
            raise ValueError(
                "Partially-labeled batch: some samples have energy and "
                "some do not (zero-filled targets would silently train "
                "toward 0)."
            )
        energy = np.zeros((G,), dtype=dtype)
    if any(s.forces is not None for s in samples) and not all(
        s.forces is not None for s in samples
    ):
        raise ValueError(
            "Partially-labeled batch: some samples have forces and some "
            "do not."
        )
    dataset_id = np.zeros((G,), dtype=np.int32)

    node_off = 0
    edge_off = 0
    for gi, s in enumerate(samples):
        n = s.num_nodes
        e = s.num_edges
        x[node_off : node_off + n] = np.atleast_2d(s.x.reshape(n, -1))
        node_graph_idx[node_off : node_off + n] = gi
        node_slot[node_off : node_off + n] = np.arange(n)
        node_mask[node_off : node_off + n] = True
        if pos is not None and s.pos is not None:
            pos[node_off : node_off + n] = s.pos
        if forces is not None and s.forces is not None:
            forces[node_off : node_off + n] = s.forces
        if y_node is not None and s.y_node is not None:
            y_node[node_off : node_off + n] = s.y_node.reshape(n, -1)
        if pe is not None and s.pe is not None:
            pe[node_off : node_off + n] = s.pe.reshape(n, -1)
        if e:
            senders[edge_off : edge_off + e] = s.edge_index[0] + node_off
            receivers[edge_off : edge_off + e] = s.edge_index[1] + node_off
            edge_mask[edge_off : edge_off + e] = True
            if edge_attr is not None and s.edge_attr is not None:
                edge_attr[edge_off : edge_off + e] = s.edge_attr.reshape(e, -1)
            if edge_shifts is not None and s.edge_shifts is not None:
                edge_shifts[edge_off : edge_off + e] = s.edge_shifts
            if rel_pe is not None and s.rel_pe is not None:
                rel_pe[edge_off : edge_off + e] = s.rel_pe.reshape(e, -1)
        if y_graph is not None and s.y_graph is not None:
            y_graph[gi] = np.asarray(s.y_graph).reshape(-1)
        if graph_attr is not None and s.graph_attr is not None:
            graph_attr[gi] = np.asarray(s.graph_attr).reshape(-1)
        if cell is not None and s.cell is not None:
            cell[gi] = s.cell
        if energy is not None and s.energy is not None:
            energy[gi] = float(np.asarray(s.energy).reshape(-1)[0])
        dataset_id[gi] = s.dataset_id
        node_off += n
        edge_off += e

    # Padding nodes: consecutive slot ids within the padding graph
    # (masked out of max_nodes_per_graph and dense layouts).
    node_slot[node_off:] = np.arange(N - node_off)

    edge_plans = order_and_plan_edges(
        senders, receivers, edge_mask, edge_payloads, e_real, N,
        with_segment_plan=with_segment_plan,
        sorted_receivers=pad.sorted_receivers,
    )

    t_kj = t_ji = triplet_mask = None
    if pad.num_triplets is not None:
        T = pad.num_triplets
        t_kj = np.empty((T,), dtype=np.int32)
        t_ji = np.empty((T,), dtype=np.int32)
        triplet_mask = np.empty((T,), dtype=bool)
        fill_triplets(
            t_kj, t_ji, triplet_mask, senders, receivers, e_real, n_real
        )

    batch = GraphBatch(
        x=x,
        pos=pos,
        node_graph_idx=node_graph_idx,
        node_slot=node_slot,
        node_mask=node_mask,
        senders=senders,
        receivers=receivers,
        edge_mask=edge_mask,
        graph_mask=graph_mask,
        edge_attr=edge_attr,
        edge_shifts=edge_shifts,
        y_graph=y_graph,
        y_node=y_node,
        graph_attr=graph_attr,
        dataset_id=dataset_id,
        pe=pe,
        rel_pe=rel_pe,
        cell=cell,
        energy=energy,
        forces=forces,
        t_kj=t_kj,
        t_ji=t_ji,
        triplet_mask=triplet_mask,
        triplets_sorted=t_ji is not None,
        **edge_plans,
    )
    if as_numpy:
        return batch
    # One construction for both paths: tree_map skips None leaves, so
    # the device batch keeps exactly the numpy batch's structure.
    return jax.tree_util.tree_map(jnp.asarray, batch)
