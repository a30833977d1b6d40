"""Parallel input pipeline: multi-worker collation, packed batch
assembly, double-buffered device transfer, and feed telemetry.

The round-5 verdict (VERDICT.md) found ``run_training`` two orders of
magnitude behind its own jitted step at a small model: a single collate
thread cannot feed a device step of well under a millisecond. This
module is the fix — the TPU-native
analog of the reference's ThreadPoolExecutor + CPU-affinity loader
(hydragnn/preprocess/load_data.py:94-204), restructured around the
deterministic pad plan the static-shape batching already requires:

- **Plan**: ``GraphLoader.epoch_plan`` yields ``(idx, PadSpec)`` per
  batch from size metadata only — the single source of batch order and
  padded shape for the serial path AND this pipeline, so dp/multibranch
  spec schedules stay valid under parallel collation.
- **Collate pool**: N worker threads pull plan entries from a task
  queue and collate out of order; a sequence-numbered reorder buffer
  delivers strictly in order, so batch sequences are bit-identical to
  the single-thread path for a seeded epoch.
- **Packed assembly**: ``collate_packed`` builds every batch field
  vectorized (``np.concatenate``/``np.repeat`` over the whole batch
  instead of a per-graph Python loop) directly into preallocated
  per-spec numpy buffers reused across steps — no per-step allocation,
  no per-field device commit.
- **Double-buffered transfer**: the host->device put of step k+1 is
  dispatched while step k computes (``to_device=False`` passes host
  batches through for DPLoader-wrapped meshes, which place stacked
  batches themselves).
- **Telemetry**: per-epoch collate latency, H2D latency, reorder-queue
  depth, and a starved-step counter (consumer blocked waiting for the
  next batch), accumulated on ``PipelineStats`` and mirrored into
  ``hydragnn_tpu.utils.tracer`` rows so the trace CSV exposes
  input-boundness directly.

Buffer-reuse contract (packed mode): a yielded batch's arrays stay
valid for at least ``hold`` further deliveries (default 2 — current +
previous), after which the buffers may be overwritten by a later batch.
Device-mode consumers are unaffected on accelerators (H2D copies host
memory before the buffer is recycled), but the XLA:CPU backend's
``device_put`` can ZERO-COPY aligned host buffers — there recycling is
disabled and every batch gets fresh buffers instead (aliasing a
recycled buffer would rewrite already-delivered batches). Host-mode
consumers (DPLoader) must copy within their ``hold`` window —
``wrap_loader`` sizes it to the device-group stack length.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional

import jax
import numpy as np

from hydragnn_tpu.data.graph import GraphBatch, MacroBatch, PadSpec, collate
from hydragnn_tpu.data.prefetch import _pin_affinity
from hydragnn_tpu.utils import tracer as tr

__all__ = [
    "PipelineStats",
    "ParallelPipelineLoader",
    "collate_packed",
    "pipeline_stats",
]


class PipelineStats:
    """Feed-path counters, accumulated consumer-side (no locks).

    ``collate_s`` is measured inside the worker that built the batch
    and attached to its result; everything else is observed at
    delivery. ``starved_steps`` counts deliveries where the consumer
    had to BLOCK because the next in-order batch was not collated yet —
    the direct, per-step visibility of input-boundness the round-5
    verdict asked for (82-158x step-vs-feed gap).
    """

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        self.delivered = 0
        self.starved_steps = 0
        self.collate_s = 0.0
        self.collate_count = 0
        self.collate_max = 0.0
        self.h2d_s = 0.0
        self.h2d_count = 0
        self.queue_depth_sum = 0
        self.queue_depth_samples = 0
        self.epochs = 0

    def record_collate(self, dt: float) -> None:
        self.collate_s += dt
        self.collate_count += 1
        self.collate_max = max(self.collate_max, dt)

    def record_h2d(self, dt: float) -> None:
        self.h2d_s += dt
        self.h2d_count += 1

    def record_delivery(self, queue_depth: int, starved: bool) -> None:
        self.delivered += 1
        self.queue_depth_sum += queue_depth
        self.queue_depth_samples += 1
        if starved:
            self.starved_steps += 1

    def as_dict(self) -> dict:
        d = {
            "delivered_batches": self.delivered,
            "starved_steps": self.starved_steps,
            "epochs": self.epochs,
            "collate_s_total": round(self.collate_s, 6),
            "collate_s_max": round(self.collate_max, 6),
            "h2d_s_total": round(self.h2d_s, 6),
        }
        if self.collate_count:
            d["collate_ms_avg"] = round(
                1e3 * self.collate_s / self.collate_count, 3
            )
        if self.h2d_count:
            d["h2d_ms_avg"] = round(1e3 * self.h2d_s / self.h2d_count, 3)
        if self.queue_depth_samples:
            d["queue_depth_avg"] = round(
                self.queue_depth_sum / self.queue_depth_samples, 2
            )
        return d

    def flush_to_tracer(self, prefix: str = "pipeline") -> None:
        """Mirror the accumulated counters into tracer rows (one
        ``add_sample`` per metric) so the timing CSV carries the feed
        path next to the step regions, AND — when a telemetry stream
        is active (utils/telemetry.py) — emit one structured
        ``pipeline`` row per flush so graftboard's starvation report
        reads the same counters. Idempotent-ish: called per epoch,
        each call contributes one sample per metric."""
        from hydragnn_tpu.utils import telemetry

        if telemetry.active():
            telemetry.emit({"t": "pipeline", **self.as_dict()})
        if not tr.has("RegionTimer"):
            return
        tr.sample(f"{prefix}/collate_s", self.collate_s)
        tr.sample(f"{prefix}/h2d_s", self.h2d_s)
        tr.sample(f"{prefix}/starved_steps", float(self.starved_steps))
        if self.queue_depth_samples:
            tr.sample(
                f"{prefix}/queue_depth_avg",
                self.queue_depth_sum / self.queue_depth_samples,
            )


# ----------------------------------------------------------------------
# Packed collation: vectorized assembly into reusable buffers.
# ----------------------------------------------------------------------

def _buf(out: Dict[str, np.ndarray], name: str, shape, dtype):
    """Fetch a reusable buffer, reallocating on shape/dtype change (a
    pool entry is keyed by PadSpec, so this only triggers when optional
    field widths differ — not on the steady path)."""
    a = out.get(name)
    if a is None or a.shape != tuple(shape) or a.dtype != np.dtype(dtype):
        a = np.empty(tuple(shape), dtype)
        out[name] = a
    return a


def _plans_into_buffers(
    out,
    pad: PadSpec,
    with_segment_plan: bool,
    senders,
    receivers,
    edge_mask,
    edge_payloads,
    e_real: int,
    n_real: int,
    N: int,
):
    """Receiver order, segment plan + triplet padding via the SAME
    graph.py helpers ``collate`` uses (bit-identity by construction);
    triplet buffers come from the reuse pool. Returns the GraphBatch
    fields they fill, by name."""
    from hydragnn_tpu.data.graph import fill_triplets, order_and_plan_edges

    edge_plans = order_and_plan_edges(
        senders, receivers, edge_mask, edge_payloads, e_real, N,
        with_segment_plan=with_segment_plan,
        sorted_receivers=pad.sorted_receivers,
    )
    t_kj = t_ji = triplet_mask = None
    if pad.num_triplets is not None:
        T = pad.num_triplets
        t_kj = _buf(out, "t_kj", (T,), np.int32)
        t_ji = _buf(out, "t_ji", (T,), np.int32)
        triplet_mask = _buf(out, "triplet_mask", (T,), bool)
        fill_triplets(
            t_kj, t_ji, triplet_mask, senders, receivers, e_real, n_real
        )
    return dict(
        edge_plans, t_kj=t_kj, t_ji=t_ji, triplet_mask=triplet_mask,
        triplets_sorted=t_ji is not None,
    )


def _concat_into(dst: np.ndarray, arrs: List[np.ndarray]) -> None:
    """dst = concat(arrs) with assignment-style casting."""
    if len(arrs) == 1:
        dst[...] = arrs[0]
    elif all(getattr(a, "dtype", None) == dst.dtype for a in arrs):
        np.concatenate(arrs, axis=0, out=dst)
    else:
        dst[...] = np.concatenate(arrs, axis=0)


def collate_packed(
    samples,
    pad: PadSpec,
    *,
    dtype: Any = np.float32,
    with_segment_plan: bool = False,
    ensure_fields: Optional[dict] = None,
    out: Optional[Dict[str, np.ndarray]] = None,
) -> GraphBatch:
    """Bit-identical, vectorized ``graph.collate`` writing into the
    reusable buffer dict ``out`` (mutated in place; pass the same dict
    again to reuse the warm buffers). Returns a numpy-backed GraphBatch
    whose arrays ALIAS ``out`` — the pipeline recycles them under its
    ``hold`` contract; standalone callers just pass ``out=None`` for a
    fresh dict per call.

    Replaces the per-graph Python loop (one slice assignment per field
    per sample — ~10 x batch_size tiny numpy ops) with one
    ``np.concatenate``/``np.repeat`` per field over the whole batch;
    padding regions are re-filled explicitly since buffers arrive dirty.
    """
    if out is None:
        out = {}
    g_real = len(samples)
    n_sizes = np.fromiter(
        (s.num_nodes for s in samples), np.int64, count=g_real
    )
    e_sizes = np.fromiter(
        (s.num_edges for s in samples), np.int64, count=g_real
    )
    n_real = int(n_sizes.sum())
    e_real = int(e_sizes.sum())
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
    node_off = np.concatenate(([0], np.cumsum(n_sizes)[:-1]))

    f_dim = samples[0].x.shape[1] if samples[0].x.ndim > 1 else 1
    x = _buf(out, "x", (N, f_dim), dtype)
    if n_real:
        _concat_into(
            x[:n_real],
            [
                s.x if s.x.ndim == 2 else s.x.reshape(int(k), -1)
                for s, k in zip(samples, n_sizes)
            ],
        )
    x[n_real:] = 0

    node_graph_idx = _buf(out, "node_graph_idx", (N,), np.int32)
    node_graph_idx[:n_real] = np.repeat(np.arange(g_real), n_sizes)
    node_graph_idx[n_real:] = g_real
    node_slot = _buf(out, "node_slot", (N,), np.int32)
    node_slot[:n_real] = np.arange(n_real) - np.repeat(node_off, n_sizes)
    node_slot[n_real:] = np.arange(N - n_real)
    node_mask = _buf(out, "node_mask", (N,), bool)
    node_mask[:n_real] = True
    node_mask[n_real:] = False

    senders = _buf(out, "senders", (E,), np.int32)
    receivers = _buf(out, "receivers", (E,), np.int32)
    if e_real:
        edge_shift = np.repeat(node_off, e_sizes)
        with_edges = [
            s.edge_index for s, k in zip(samples, e_sizes) if int(k)
        ]
        _concat_into(senders[:e_real], [ei[0] for ei in with_edges])
        senders[:e_real] += edge_shift
        _concat_into(receivers[:e_real], [ei[1] for ei in with_edges])
        receivers[:e_real] += edge_shift
    senders[e_real:] = n_real
    receivers[e_real:] = n_real
    edge_mask = _buf(out, "edge_mask", (E,), bool)
    edge_mask[:e_real] = True
    edge_mask[e_real:] = False

    graph_mask = _buf(out, "graph_mask", (G,), bool)
    graph_mask[:g_real] = True
    graph_mask[g_real:] = False

    def _widths(field, vals):
        """Distinct last-dim widths over present values — the cheap
        form of collate's ``np.atleast_2d(v).shape[-1]`` probe."""
        dims = set()
        for v in vals:
            if v is not None:
                s = np.shape(v)
                dims.add(int(s[-1]) if s else 1)
        if len(dims) != 1:
            raise ValueError(
                f"Inconsistent {field} dims across samples: {dims}"
            )
        return dims.pop()

    def _opt_rows(field, width_of, sizes, offs, total, reshape):
        """Optional row-aligned field, mirroring collate's ``_opt`` +
        fill loop: None when absent everywhere (unless ensure_fields
        materializes zeros), zero rows for samples lacking it."""
        vals = [getattr(s, field) for s in samples]
        n_present = sum(1 for v in vals if v is not None)
        if n_present == 0:
            if ensure_fields and field in ensure_fields:
                buf = _buf(
                    out, field, (width_of, int(ensure_fields[field])), dtype
                )
                buf[...] = 0
                return buf
            return None
        buf = _buf(out, field, (width_of, _widths(field, vals)), dtype)
        if n_present == g_real:
            if total:
                _concat_into(
                    buf[:total],
                    [
                        reshape(v, int(k))
                        for v, k in zip(vals, sizes)
                        if int(k)
                    ],
                )
            buf[total:] = 0
        else:
            buf[...] = 0
            for v, k, o in zip(vals, sizes, offs):
                if v is not None and int(k):
                    buf[int(o) : int(o) + int(k)] = reshape(v, int(k))
        return buf

    def _r2(v, k):  # row-aligned fields stored flat or [k, d]
        v = np.asarray(v)
        return v if v.ndim == 2 else v.reshape(k, -1)

    _rid = lambda v, k: v  # noqa: E731  (already [k, d]-shaped fields)
    edge_off = np.concatenate(([0], np.cumsum(e_sizes)[:-1]))

    pos = _opt_rows("pos", N, n_sizes, node_off, n_real, _rid)
    forces = _opt_rows("forces", N, n_sizes, node_off, n_real, _rid)
    y_node = _opt_rows("y_node", N, n_sizes, node_off, n_real, _r2)
    pe = _opt_rows("pe", N, n_sizes, node_off, n_real, _r2)
    edge_payloads = {
        "edge_attr": _opt_rows(
            "edge_attr", E, e_sizes, edge_off, e_real, _r2
        ),
        "edge_shifts": _opt_rows(
            "edge_shifts", E, e_sizes, edge_off, e_real, _rid
        ),
        "rel_pe": _opt_rows("rel_pe", E, e_sizes, edge_off, e_real, _r2),
    }
    edge_attr = edge_payloads["edge_attr"]
    edge_shifts = edge_payloads["edge_shifts"]
    rel_pe = edge_payloads["rel_pe"]

    def _opt_graph(field):
        vals = [getattr(s, field) for s in samples]
        n_present = sum(1 for v in vals if v is not None)
        if n_present == 0:
            if ensure_fields and field in ensure_fields:
                buf = _buf(
                    out, field, (G, int(ensure_fields[field])), dtype
                )
                buf[...] = 0
                return buf
            return None
        buf = _buf(out, field, (G, _widths(field, vals)), dtype)
        buf[...] = 0
        if n_present == g_real:
            buf[:g_real] = np.stack(
                [np.asarray(v).reshape(-1) for v in vals]
            )
        else:
            for gi, v in enumerate(vals):
                if v is not None:
                    buf[gi] = np.asarray(v).reshape(-1)
        return buf

    y_graph = _opt_graph("y_graph")
    graph_attr = _opt_graph("graph_attr")

    cell = None
    if any(s.cell is not None for s in samples) or (
        ensure_fields and "cell" in ensure_fields
    ):
        cell = _buf(out, "cell", (G, 3, 3), dtype)
        cell[...] = np.eye(3, dtype=dtype)
        for gi, s in enumerate(samples):
            if s.cell is not None:
                cell[gi] = s.cell

    energy = None
    if any(s.energy is not None for s in samples):
        if not all(s.energy is not None for s in samples):
            raise ValueError(
                "Partially-labeled batch: some samples have energy and "
                "some do not (zero-filled targets would silently train "
                "toward 0)."
            )
        energy = _buf(out, "energy", (G,), dtype)
        energy[...] = 0
        energy[:g_real] = np.fromiter(
            (
                float(np.asarray(s.energy).reshape(-1)[0])
                for s in samples
            ),
            np.float64,
            count=g_real,
        )
    if any(s.forces is not None for s in samples) and not all(
        s.forces is not None for s in samples
    ):
        raise ValueError(
            "Partially-labeled batch: some samples have forces and some "
            "do not."
        )

    dataset_id = _buf(out, "dataset_id", (G,), np.int32)
    dataset_id[...] = 0
    dataset_id[:g_real] = np.fromiter(
        (s.dataset_id for s in samples), np.int64, count=g_real
    )

    plans = _plans_into_buffers(
        out,
        pad,
        with_segment_plan,
        senders,
        receivers,
        edge_mask,
        edge_payloads,
        e_real,
        n_real,
        N,
    )

    return GraphBatch(
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
        **plans,
    )


def _stack_group(batches: List[GraphBatch], out: Dict[str, np.ndarray]) -> MacroBatch:
    """Stack K same-spec batches into pooled ``[K, ...]`` buffers — the
    pipeline's buffer-reusing form of ``graph.stack_batches`` (same
    result bitwise: a straight per-field copy). ``out`` is the macro
    buffer dict, keyed like the per-batch pools but per (spec, K)."""
    import dataclasses as _dc

    k = len(batches)
    fields = {}
    for f in _dc.fields(GraphBatch):
        xs = [getattr(b, f.name) for b in batches]
        if not f.metadata.get("pytree_node", True):
            # static metadata (receivers_sorted, triplets_sorted): one
            # value for the group
            if any(x != xs[0] for x in xs):
                raise ValueError(
                    f"superstep group mixes values of `{f.name}`"
                )
            fields[f.name] = xs[0]
            continue
        if xs[0] is None:
            if any(x is not None for x in xs):
                raise ValueError(
                    f"superstep group mixes presence of `{f.name}` — "
                    "same-spec batches of one loader must share field "
                    "structure"
                )
            fields[f.name] = None
            continue
        a0 = np.asarray(xs[0])
        buf = _buf(out, f.name, (k,) + a0.shape, a0.dtype)
        buf[0] = a0
        for i in range(1, k):
            buf[i] = xs[i]
        fields[f.name] = buf
    return MacroBatch(batch=GraphBatch(**fields), k=k)


# ----------------------------------------------------------------------
# Dataset-level packed store: per-field column tables + span starts, so
# batch assembly is a handful of vectorized gathers with NO per-sample
# Python. collate/collate_packed cost scales with the NUMBER of python
# ops (~10 per sample per batch); the store costs one dataset pass up
# front and then ~20 numpy calls per batch regardless of batch size.
# ----------------------------------------------------------------------

_NODE_TABLE_FIELDS = ("pos", "forces", "y_node", "pe")
_EDGE_TABLE_FIELDS = ("edge_attr", "edge_shifts", "rel_pe")
_GRAPH_TABLE_FIELDS = ("y_graph", "graph_attr")


class PackedStore:
    """Column tables over an in-memory dataset for vectorized collation.

    Eligibility (``build`` returns None otherwise, and the pipeline
    falls back to per-sample ``collate_packed``):
    - the dataset is a materialized list (packing a lazy/mmap container
      would pull it wholesale into RAM — exactly what GraphLoader's
      container pass-through exists to avoid);
    - every optional field is present on ALL samples or NONE (mixed
      presence keeps collate's per-batch zero-fill semantics, which the
      table gather cannot reproduce);
    - node-feature widths are consistent.

    Tables are stored in the COLLATED dtypes (float32/int32 casts paid
    once at build), so assembled batches are bit-identical to
    ``graph.collate`` output. Costs one packed copy of the dataset in
    host RAM — ``HYDRAGNN_TPU_PIPELINE_STORE=0`` disables it.
    """

    def __init__(self, dtype=np.float32):
        self.dtype = dtype
        self.tables: Dict[str, np.ndarray] = {}
        self.n_sizes: np.ndarray = None
        self.e_sizes: np.ndarray = None
        self.node_start: np.ndarray = None
        self.edge_start: np.ndarray = None
        self.f_dim = 1

    @staticmethod
    def build(dataset, dtype=np.float32) -> Optional["PackedStore"]:
        import os

        if os.environ.get("HYDRAGNN_TPU_PIPELINE_STORE", "1") in (
            "0", "false",
        ):
            return None
        if not isinstance(dataset, list) or not dataset:
            return None
        st = PackedStore(dtype)
        n = len(dataset)
        st.n_sizes = np.fromiter(
            (s.num_nodes for s in dataset), np.int64, count=n
        )
        st.e_sizes = np.fromiter(
            (s.num_edges for s in dataset), np.int64, count=n
        )
        st.node_start = np.concatenate(
            ([0], np.cumsum(st.n_sizes)[:-1])
        )
        st.edge_start = np.concatenate(
            ([0], np.cumsum(st.e_sizes)[:-1])
        )
        s0 = dataset[0]
        st.f_dim = s0.x.shape[1] if s0.x.ndim > 1 else 1
        try:
            st.tables["x"] = np.concatenate(
                [
                    s.x if s.x.ndim == 2 else s.x.reshape(s.num_nodes, -1)
                    for s in dataset
                ]
            ).astype(dtype, copy=False)
        except ValueError:
            return None  # inconsistent widths: per-sample path raises
        if st.tables["x"].shape[1] != st.f_dim:
            return None

        def _presence(field):
            c = sum(
                1 for s in dataset if getattr(s, field) is not None
            )
            return "all" if c == n else ("none" if c == 0 else "mixed")

        for field in (
            _NODE_TABLE_FIELDS
            + _EDGE_TABLE_FIELDS
            + _GRAPH_TABLE_FIELDS
            + ("cell", "energy", "edge_index")
        ):
            if _presence(field) == "mixed":
                return None
        try:
            if s0.edge_index is not None:
                # int32 tables: edge endpoints are sample-local (< 2^31
                # always) and the collated buffers are int32 anyway —
                # half the gather bandwidth.
                st.tables["snd"] = np.concatenate(
                    [s.edge_index[0] for s in dataset if s.num_edges]
                    or [np.zeros(0, np.int64)]
                ).astype(np.int32)
                st.tables["rcv"] = np.concatenate(
                    [s.edge_index[1] for s in dataset if s.num_edges]
                    or [np.zeros(0, np.int64)]
                ).astype(np.int32)
            for field in _NODE_TABLE_FIELDS:
                v0 = getattr(s0, field)
                if v0 is None:
                    continue
                st.tables[field] = np.concatenate(
                    [
                        np.asarray(getattr(s, field)).reshape(
                            s.num_nodes, -1
                        )
                        for s in dataset
                    ]
                ).astype(dtype, copy=False)
            for field in _EDGE_TABLE_FIELDS:
                v0 = getattr(s0, field)
                if v0 is None:
                    continue
                st.tables[field] = np.concatenate(
                    [
                        np.asarray(getattr(s, field)).reshape(
                            s.num_edges, -1
                        )
                        for s in dataset
                        if s.num_edges
                    ]
                    or [np.zeros((0, 1), dtype)]
                ).astype(dtype, copy=False)
            for field in _GRAPH_TABLE_FIELDS:
                v0 = getattr(s0, field)
                if v0 is None:
                    continue
                st.tables[field] = np.stack(
                    [
                        np.asarray(getattr(s, field)).reshape(-1)
                        for s in dataset
                    ]
                ).astype(dtype, copy=False)
            if s0.cell is not None:
                st.tables["cell"] = np.stack(
                    [s.cell for s in dataset]
                ).astype(dtype, copy=False)
            if s0.energy is not None:
                st.tables["energy"] = np.fromiter(
                    (
                        float(np.asarray(s.energy).reshape(-1)[0])
                        for s in dataset
                    ),
                    np.float64,
                    count=n,
                ).astype(dtype)
            st.tables["dataset_id"] = np.fromiter(
                (s.dataset_id for s in dataset), np.int64, count=n
            ).astype(np.int32)
        except ValueError:
            return None  # ragged widths -> let the per-sample path raise
        return st

    # -- assembly -------------------------------------------------------
    def assemble(
        self,
        idx: np.ndarray,
        pad: PadSpec,
        *,
        with_segment_plan: bool = False,
        ensure_fields: Optional[dict] = None,
        out: Optional[Dict[str, np.ndarray]] = None,
    ) -> GraphBatch:
        """Vectorized equivalent of ``collate([dataset[i] for i in
        idx], pad, ...)`` — same buffers-reuse contract as
        ``collate_packed``."""
        if out is None:
            out = {}
        dtype = self.dtype
        g_real = len(idx)
        n_sizes = self.n_sizes[idx]
        e_sizes = self.e_sizes[idx]
        n_real = int(n_sizes.sum())
        e_real = int(e_sizes.sum())
        if n_real >= pad.num_nodes:
            raise ValueError(
                f"PadSpec too small: {n_real} real nodes need >= "
                f"{n_real + 1} padded slots, got {pad.num_nodes}"
            )
        if e_real > pad.num_edges or g_real >= pad.num_graphs:
            raise ValueError(
                f"PadSpec too small: edges {e_real}/{pad.num_edges}, "
                f"graphs {g_real}/{pad.num_graphs} (need one padding "
                "graph slot)"
            )
        N, E, G = pad.num_nodes, pad.num_edges, pad.num_graphs
        node_off = np.concatenate(([0], np.cumsum(n_sizes)[:-1]))
        intra_n = np.arange(n_real) - np.repeat(node_off, n_sizes)
        node_rows = np.repeat(self.node_start[idx], n_sizes) + intra_n

        x = _buf(out, "x", (N, self.f_dim), dtype)
        x[:n_real] = self.tables["x"][node_rows]
        x[n_real:] = 0
        node_graph_idx = _buf(out, "node_graph_idx", (N,), np.int32)
        node_graph_idx[:n_real] = np.repeat(np.arange(g_real), n_sizes)
        node_graph_idx[n_real:] = g_real
        node_slot = _buf(out, "node_slot", (N,), np.int32)
        node_slot[:n_real] = intra_n
        node_slot[n_real:] = np.arange(N - n_real)
        node_mask = _buf(out, "node_mask", (N,), bool)
        node_mask[:n_real] = True
        node_mask[n_real:] = False

        senders = _buf(out, "senders", (E,), np.int32)
        receivers = _buf(out, "receivers", (E,), np.int32)
        if e_real:
            edge_off = np.concatenate(([0], np.cumsum(e_sizes)[:-1]))
            intra_e = np.arange(e_real) - np.repeat(edge_off, e_sizes)
            edge_rows = np.repeat(self.edge_start[idx], e_sizes) + intra_e
            shift = np.repeat(node_off, e_sizes)
            senders[:e_real] = self.tables["snd"][edge_rows] + shift
            receivers[:e_real] = self.tables["rcv"][edge_rows] + shift
        senders[e_real:] = n_real
        receivers[e_real:] = n_real
        edge_mask = _buf(out, "edge_mask", (E,), bool)
        edge_mask[:e_real] = True
        edge_mask[e_real:] = False
        graph_mask = _buf(out, "graph_mask", (G,), bool)
        graph_mask[:g_real] = True
        graph_mask[g_real:] = False

        def _rows(field, width_of, total, rows):
            tab = self.tables.get(field)
            if tab is None:
                if ensure_fields and field in ensure_fields:
                    buf = _buf(
                        out,
                        field,
                        (width_of, int(ensure_fields[field])),
                        dtype,
                    )
                    buf[...] = 0
                    return buf
                return None
            buf = _buf(out, field, (width_of, tab.shape[1]), dtype)
            buf[:total] = tab[rows]
            buf[total:] = 0
            return buf

        pos = _rows("pos", N, n_real, node_rows)
        forces = _rows("forces", N, n_real, node_rows)
        y_node = _rows("y_node", N, n_real, node_rows)
        pe = _rows("pe", N, n_real, node_rows)
        if e_real:
            edge_payloads = {
                f: _rows(f, E, e_real, edge_rows)
                for f in _EDGE_TABLE_FIELDS
            }
        else:
            edge_payloads = {
                f: _rows(f, E, 0, np.zeros(0, np.int64))
                for f in _EDGE_TABLE_FIELDS
            }
        y_graph = _rows("y_graph", G, g_real, idx)
        graph_attr = _rows("graph_attr", G, g_real, idx)

        cell = None
        if "cell" in self.tables or (
            ensure_fields and "cell" in ensure_fields
        ):
            cell = _buf(out, "cell", (G, 3, 3), dtype)
            cell[...] = np.eye(3, dtype=dtype)
            if "cell" in self.tables:
                cell[:g_real] = self.tables["cell"][idx]
        energy = None
        if "energy" in self.tables:
            energy = _buf(out, "energy", (G,), dtype)
            energy[g_real:] = 0
            energy[:g_real] = self.tables["energy"][idx]
        dataset_id = _buf(out, "dataset_id", (G,), np.int32)
        dataset_id[g_real:] = 0
        dataset_id[:g_real] = self.tables["dataset_id"][idx]

        plans = _plans_into_buffers(
            out,
            pad,
            with_segment_plan,
            senders,
            receivers,
            edge_mask,
            edge_payloads,
            e_real,
            n_real,
            N,
        )

        return GraphBatch(
            x=x,
            pos=pos,
            node_graph_idx=node_graph_idx,
            node_slot=node_slot,
            node_mask=node_mask,
            senders=senders,
            receivers=receivers,
            edge_mask=edge_mask,
            graph_mask=graph_mask,
            edge_attr=edge_payloads["edge_attr"],
            edge_shifts=edge_payloads["edge_shifts"],
            y_graph=y_graph,
            y_node=y_node,
            graph_attr=graph_attr,
            dataset_id=dataset_id,
            pe=pe,
            rel_pe=edge_payloads["rel_pe"],
            cell=cell,
            energy=energy,
            forces=forces,
            **plans,
        )


# ----------------------------------------------------------------------
# The pipeline loader.
# ----------------------------------------------------------------------

_SPEC_KEY = lambda s: (  # noqa: E731
    s.num_nodes, s.num_edges, s.num_graphs, s.num_triplets
)


def _segment_plan_enabled(loader, spec) -> bool:
    """Per-spec segment-plan resolution (GraphLoader grew
    ``segment_plan_enabled`` for the ``"auto"`` crossover mode; older
    duck-typed loaders fall back to the plain flag)."""
    fn = getattr(loader, "segment_plan_enabled", None)
    if fn is not None:
        return bool(fn(spec))
    return bool(getattr(loader, "with_segment_plan", False))


class ParallelPipelineLoader:
    """Parallel feed path over a ``GraphLoader``: collation pool +
    in-order reorder delivery + (optionally) double-buffered device
    transfer. Drop-in for ``PrefetchLoader`` where the wrapped loader
    is a GraphLoader (it needs the loader's ``epoch_plan``); batch
    sequences are bit-identical to serial iteration of the same loader.

    ``workers=0`` is NOT accepted here — the caller (``wrap_loader``)
    keeps the single-thread ``PrefetchLoader`` fallback for that.

    Parameters
    ----------
    workers: collation pool size (affinity-pinned when
        ``affinity_offset`` is given, reference HYDRAGNN_AFFINITY).
        Effective concurrency is ``min(workers, depth)`` — surplus
        workers sleep, so a large configured pool cannot thrash a
        small host.
    depth: max chunks in flight (flow control + the reorder buffer's
        slack for out-of-order completion + the worker-concurrency
        gate).
    packed: pooled-buffer packed collation — the dataset-level
        ``PackedStore`` column gather when the dataset is eligible,
        per-sample ``collate_packed`` otherwise; off = plain
        ``collate(as_numpy=True)`` per batch in the workers.
    to_device: transfer delivered batches: each chunk's batches go up
        in ONE ``jax.device_put`` dispatched from the worker, so the
        H2D of batches k+1.. overlaps the consumer's compute on batch
        k. ``False`` passes host batches through for DPLoader-wrapped
        meshes.
    hold: packed-buffer validity window — a yielded batch's buffers are
        recycled only after ``hold`` further deliveries. DPLoader
        consumers need ``hold >= device-group size + 1``.
    chunk: batches per worker task / per H2D dispatch (amortizes
        thread-handoff and per-leaf transfer-dispatch overhead).
    superstep_k: > 1 folds the epoch plan into same-spec runs of K
        (padschedule.superstep_groups — the same pure grouping the
        serial SuperstepLoader applies, so delivery stays
        bit-identical): workers collate each full run, stack it into a
        pooled ``[K, ...]`` macro buffer, and the chunked H2D ships the
        whole macro-batch in one transfer. Run tails are delivered as
        plain per-step batches. 1 (default) = today's behavior exactly.
    """

    def __init__(
        self,
        loader,
        *,
        workers: int = 4,
        depth: int = 4,
        packed: bool = True,
        to_device: bool = True,
        device=None,
        hold: int = 2,
        chunk: int = 4,
        superstep_k: int = 1,
        affinity_offset: Optional[int] = None,
        affinity_width: int = 1,
        stats: Optional[PipelineStats] = None,
    ):
        if workers < 1:
            raise ValueError(
                "ParallelPipelineLoader needs workers >= 1; use "
                "PrefetchLoader for the single-thread fallback"
            )
        if not hasattr(loader, "epoch_plan"):
            raise TypeError(
                "ParallelPipelineLoader wraps a GraphLoader (it drives "
                f"collation from loader.epoch_plan); got {type(loader)}"
            )
        self.loader = loader
        self.workers = int(workers)
        self.depth = max(1, int(depth))
        self.packed = bool(packed)
        self.to_device = bool(to_device)
        self.device = device
        self.hold = max(2, int(hold))
        # Chunked dispatch: each task covers ``chunk`` consecutive
        # batches and posts ONE reorder-buffer result, so the per-batch
        # thread handoff cost (notify + GIL switch + wakeup, the
        # dominant overhead once collation is vectorized) is amortized
        # by the chunk factor. Delivery order is unchanged: chunks are
        # sequence-numbered and batches within a chunk stay ordered.
        self.chunk = max(1, int(chunk))
        self.superstep_k = max(1, int(superstep_k))
        self.affinity_offset = affinity_offset
        self.affinity_width = int(affinity_width)
        self.stats = stats if stats is not None else PipelineStats()
        self._skip_next = 0
        self._keep_host = False  # set per epoch when populating a cache
        self._store: Optional[PackedStore] = None
        self._store_tried = False
        self._pool: Dict[tuple, List[dict]] = {}
        self._pool_lock = threading.Lock()
        # XLA:CPU ``device_put`` ZERO-COPIES suitably-aligned host
        # buffers — a recycled packed buffer would alias live device
        # arrays and silently rewrite already-delivered batches (packed
        # bins recur on few budget shapes, making the reuse constant).
        # TPU/GPU H2D always copies, so recycling stays on there; in
        # host mode (to_device=False) consumers copy within ``hold``.
        self._recycle = not (
            self.to_device and jax.default_backend() == "cpu"
        )

    # -- loader protocol ------------------------------------------------
    def set_epoch(self, epoch: int) -> None:
        if hasattr(self.loader, "set_epoch"):
            self.loader.set_epoch(epoch)
        self._skip_next = 0  # a cursor never outlives its epoch

    def skip_to(self, step: int) -> None:
        """One-shot mid-epoch resume cursor (steps): the next iteration
        drops the plan entries/groups the cursor covers BEFORE any task
        reaches the collation pool — consumed batches are never
        collated, and superstep groups are cut from the full plan first
        so the resumed deliveries are the uninterrupted run's exact
        suffix (docs/DURABILITY.md)."""
        self._skip_next = max(0, int(step))

    def __len__(self) -> int:
        """Delivered items this epoch (superstep groups when stacking)."""
        if self.superstep_k > 1:
            from hydragnn_tpu.data.padschedule import superstep_groups

            return len(
                superstep_groups(
                    self.loader.epoch_plan(
                        int(getattr(self.loader, "_epoch", 0))
                    ),
                    self.superstep_k,
                )
            )
        return len(self.loader)

    def pipeline_stats(self) -> PipelineStats:
        return self.stats

    # -- buffer pool ----------------------------------------------------
    def _pool_acquire(self, key: tuple) -> dict:
        with self._pool_lock:
            free = self._pool.get(key)
            if free:
                return free.pop()
        return {}

    def _pool_release(self, key: Optional[tuple], buf: Optional[dict]):
        if buf is None or key is None or not self._recycle:
            return
        with self._pool_lock:
            self._pool.setdefault(key, []).append(buf)

    # -- worker ---------------------------------------------------------
    def _worker_main(self, widx, tasks, results, cond, tokens, stop):
        if self.affinity_offset is not None:
            _pin_affinity(
                self.affinity_offset + widx * self.affinity_width,
                self.affinity_width,
            )
        loader = self.loader
        ds = loader.dataset
        while not stop.is_set():
            # Flow control: at most ``depth`` chunks in flight — also
            # the worker-CONCURRENCY gate (surplus workers sleep here
            # instead of thrashing an oversubscribed host). The token
            # is acquired BEFORE claiming a task: tasks are queued in
            # delivery order, so token holders are always the next
            # chunks the consumer needs. (Claim-then-acquire would
            # deadlock with workers > depth: a worker holding chunk k
            # can lose the token race to chunks k+1.., whose tokens
            # only free when the consumer pops chunk k — which is never
            # collated.) Stop-aware polling, so shutdown never hangs.
            acquired = False
            while not stop.is_set():
                if tokens.acquire(timeout=0.05):
                    acquired = True
                    break
            if not acquired:
                return
            try:
                task = tasks.get_nowait()
            except queue.Empty:
                task = None
            if task is None:
                # Sentinel (or drained queue): hand the token back so
                # sibling workers can reach their own sentinels.
                tokens.release()
                return
            cseq, groups = task
            items = []
            for group in groups:
                if stop.is_set():
                    break
                # spans of the feed's threads on the profiler's clock
                # (no-ops without a live capture): what the feed was
                # doing while the loop waited in <region>/feed_wait
                with tr.span("feed/collate"):
                    items.append(self._collate_group(ds, loader, group))
                if items[-1][0] == "err":
                    break  # later batches of the chunk are unreachable
            if self.to_device:
                try:
                    with tr.span("feed/h2d"):
                        items = self._transfer_chunk(items)
                except BaseException as e:
                    # A failed transfer must still post the chunk, or
                    # the consumer would wait on it forever while other
                    # workers stay alive.
                    for it in items:
                        if it[0] == "ok":
                            self._pool_release(it[2], it[3])
                    items = [("err", e, None, None, 0.0, 0.0, None)]
            with cond:
                results[cseq] = items
                cond.notify_all()

    def _transfer_chunk(self, items: list) -> list:
        """ONE ``jax.device_put`` for the whole chunk: the per-leaf
        python/PJRT dispatch overhead dominates small-array H2D, so
        batching the chunk's pytrees into a single call amortizes it.
        Overlaps the consumer's compute on earlier batches (JAX
        dispatch is thread-safe); delivery order is enforced by the
        reorder buffer."""
        ok = [it for it in items if it[0] == "ok"]
        if not ok:
            return items
        t1 = time.perf_counter()
        hosts = [it[1] for it in ok]
        devs = (
            jax.device_put(hosts, self.device)
            if self.device is not None
            else jax.device_put(hosts)
        )
        dt = (time.perf_counter() - t1) / len(ok)
        out = []
        di = iter(devs)
        for it in items:
            if it[0] == "ok":
                out.append(
                    ("ok", next(di), it[2], it[3], it[4], dt, it[6])
                )
            else:
                out.append(it)
        return out

    def _collate_group(self, ds, loader, group) -> tuple:
        """Collate one superstep group (worker side): a singleton group
        is exactly today's per-batch path; a full K-group collates its
        K same-spec batches, stacks them into a pooled ``[K, ...]``
        macro buffer (one copy — the per-batch buffers go straight back
        to the pool) and returns a MacroBatch item under the same
        reorder/recycle contract as single batches."""
        if len(group) == 1:
            return self._collate_one(ds, loader, *group[0])
        t0 = time.perf_counter()
        key = bufs = None
        sub_bufs = []
        try:
            subs = []
            for idx, spec in group:
                item = self._collate_one(ds, loader, idx, spec)
                if item[0] == "err":
                    for k2, b2 in sub_bufs:
                        self._pool_release(k2, b2)
                    return item
                subs.append(item[1])
                sub_bufs.append((item[2], item[3]))
            key = (
                "macro",
                len(subs),
                subs[0].num_nodes,
                subs[0].num_edges,
                subs[0].num_graphs,
            )
            bufs = self._pool_acquire(key)
            macro = _stack_group(subs, bufs)
            # The stack COPIED every field: per-batch buffers are free
            # immediately (no hold window — they never reach device_put).
            for k2, b2 in sub_bufs:
                self._pool_release(k2, b2)
            sub_bufs = []
            collate_dt = time.perf_counter() - t0
            host = macro if self._keep_host else None
            return ("ok", macro, key, bufs, collate_dt, 0.0, host)
        except BaseException as e:  # delivered in order, then raised
            self._pool_release(key, bufs)
            for k2, b2 in sub_bufs:
                self._pool_release(k2, b2)
            return ("err", e, None, None, 0.0, 0.0, None)

    def _collate_one(self, ds, loader, idx, spec) -> tuple:
        """Collate one planned batch (worker side): returns the reorder
        item ("ok", batch, key, bufs, collate_s, h2d_s, host_batch) or
        ("err", exc, ...)."""
        t0 = time.perf_counter()
        key = bufs = None
        try:
            samples = None
            if spec is None:
                samples = [ds[i] for i in idx]
                spec = loader.batch_spec(samples)
            # Worker-side sorted-segment planning: the edge sort + block
            # plan happens HERE (inside collate/assemble) when the
            # loader wants it for this spec — the jitted step then
            # consumes pre-permuted edges with zero per-step host work.
            seg_plan = _segment_plan_enabled(loader, spec)
            if self.packed:
                key = _SPEC_KEY(spec)
                bufs = self._pool_acquire(key)
                if self._store is not None:
                    batch = self._store.assemble(
                        idx,
                        spec,
                        with_segment_plan=seg_plan,
                        ensure_fields=loader._ensure_fields,
                        out=bufs,
                    )
                else:
                    if samples is None:
                        samples = [ds[i] for i in idx]
                    batch = collate_packed(
                        samples,
                        spec,
                        with_segment_plan=seg_plan,
                        ensure_fields=loader._ensure_fields,
                        out=bufs,
                    )
            else:
                if samples is None:
                    samples = [ds[i] for i in idx]
                batch = collate(
                    samples,
                    spec,
                    with_segment_plan=seg_plan,
                    ensure_fields=loader._ensure_fields,
                    as_numpy=True,
                )
            collate_dt = time.perf_counter() - t0
            host = batch if self._keep_host else None
            return ("ok", batch, key, bufs, collate_dt, 0.0, host)
        except BaseException as e:  # delivered in order, then raised
            self._pool_release(key, bufs)
            return ("err", e, None, None, 0.0, 0.0, None)

    # -- consumer helpers -----------------------------------------------
    def _pop_chunk(self, results, cond, tokens, threads, cseq):
        """Take the in-order chunk ``cseq`` (blocking). Starvation +
        reorder-queue depth are recorded here."""
        starved = False
        with cond:
            while cseq not in results:
                starved = True
                cond.wait(timeout=0.5)
                if cseq not in results and not any(
                    t.is_alive() for t in threads
                ):
                    raise RuntimeError(
                        "input pipeline workers exited without "
                        f"producing chunk {cseq}"
                    )
            items = results.pop(cseq)
            depth = len(results)
        tokens.release()
        self.stats.record_delivery(depth, starved)
        return items

    def _transfer(self, batch):
        import jax

        t0 = time.perf_counter()
        with tr.span("feed/h2d"):
            out = (
                jax.device_put(batch, self.device)
                if self.device is not None
                else jax.device_put(batch)
            )
        self.stats.record_h2d(time.perf_counter() - t0)
        return out

    # -- iteration ------------------------------------------------------
    def __iter__(self) -> Iterator[GraphBatch]:
        from hydragnn_tpu.data.loader import (
            skip_delivered_items,
            superstep_cache_get,
        )

        skip = self._skip_next
        self._skip_next = 0
        loader = self.loader
        # Superstep mode replays the GROUPED cache shared on the base
        # loader (macro items must never land in _batch_cache, whose
        # replay contract is per-step batches; a shared eval loader's
        # several wrappers collate + hold the epoch once either way).
        cache_ready = (
            superstep_cache_get(loader, self.superstep_k)
            if self.superstep_k > 1
            else getattr(loader, "_batch_cache", None)
        )
        if cache_ready is not None:
            # Fixed-order eval loaders replay their collated cache; the
            # pipeline only adds the per-epoch device transfer (still
            # counted as an epoch and flushed, so replay epochs' H2D
            # time reaches the tracer like collated epochs' does).
            try:
                for b in skip_delivered_items(cache_ready, skip):
                    yield self._transfer(b) if self.to_device else b
                self.stats.epochs += 1
            finally:
                self.stats.flush_to_tracer()
            return
        epoch = int(getattr(loader, "_epoch", 0))
        plan = list(loader.epoch_plan(epoch))
        if self.superstep_k > 1:
            from hydragnn_tpu.data.loader import drop_consumed_groups
            from hydragnn_tpu.data.padschedule import superstep_groups

            groups = drop_consumed_groups(
                superstep_groups(plan, self.superstep_k), skip
            )
        else:
            groups = [[entry] for entry in plan[skip:]]
        want_cache = (
            bool(getattr(loader, "cache_batches", False)) and not skip
        )
        cache: Optional[list] = [] if want_cache else None
        self._keep_host = want_cache and self.to_device
        if self.packed and not self._store_tried:
            # One dataset pass builds the column store; ineligible
            # datasets (lazy containers, mixed field presence) fall
            # back to per-sample packed collation permanently.
            self._store = PackedStore.build(loader.dataset)
            self._store_tried = True
        n = len(groups)
        if n == 0:
            return
        stop = threading.Event()
        tasks: "queue.SimpleQueue" = queue.SimpleQueue()
        # One group per task under superstep: a K-group already
        # amortizes the per-task thread-handoff by K, and chunking
        # macros would multiply in-flight host buffers AND
        # time-to-first-delivery by chunk*K (the depth tokens bound
        # in-flight macro buffers at ``depth``).
        eff_chunk = 1 if self.superstep_k > 1 else self.chunk
        n_chunks = 0
        for start in range(0, n, eff_chunk):
            tasks.put((n_chunks, groups[start : start + eff_chunk]))
            n_chunks += 1
        for _ in range(self.workers):
            tasks.put(None)
        results: Dict[int, list] = {}
        cond = threading.Condition()
        # ``depth`` gates chunks in flight AND effective worker
        # concurrency (surplus workers sleep on the semaphore) — on an
        # oversubscribed host, extra threads would only thrash the GIL.
        tokens = threading.BoundedSemaphore(self.depth)
        threads = [
            threading.Thread(
                target=self._worker_main,
                args=(w, tasks, results, cond, tokens, stop),
                daemon=True,
                name=f"hgtpu-pipeline-w{w}",
            )
            for w in range(self.workers)
        ]
        for t in threads:
            t.start()
        recycle: deque = deque()
        try:
            delivered = 0
            for cseq in range(n_chunks):
                items = self._pop_chunk(
                    results, cond, tokens, threads, cseq
                )
                for item in items:
                    if item[0] == "err":
                        raise item[1]
                    _, batch, key, bufs, collate_dt, h2d_dt, host = item
                    self.stats.record_collate(collate_dt)
                    if self.to_device:
                        self.stats.record_h2d(h2d_dt)
                    if cache is not None:
                        cache.append(
                            _host_copy(host if host is not None else batch)
                        )
                    recycle.append((key, bufs))
                    while len(recycle) > self.hold:
                        self._pool_release(*recycle.popleft())
                    delivered += 1
                    yield batch
            if delivered != n:  # a worker stopped a chunk short
                raise RuntimeError(
                    f"input pipeline delivered {delivered}/{n} batches"
                )
            if cache is not None:
                if self.superstep_k > 1:
                    from hydragnn_tpu.data.loader import (
                        superstep_cache_put,
                    )

                    superstep_cache_put(loader, self.superstep_k, cache)
                else:
                    loader._batch_cache = cache
            self.stats.epochs += 1
        finally:
            stop.set()
            for t in threads:
                try:
                    t.join(timeout=5.0)
                except Exception:
                    pass  # interpreter teardown: threading already gone
            for key, bufs in recycle:
                self._pool_release(key, bufs)
            with cond:
                leftovers = [
                    it for items in results.values() for it in items
                ]
                results.clear()
            for item in leftovers:
                if item[0] == "ok":
                    self._pool_release(item[2], item[3])
            self.stats.flush_to_tracer()


def _host_copy(batch: GraphBatch) -> GraphBatch:
    """Deep host copy (packed buffers are recycled; a cache entry must
    own its memory)."""
    import jax

    return jax.tree_util.tree_map(
        lambda a: np.array(a, copy=True), batch
    )


def pipeline_stats(loader) -> Optional[PipelineStats]:
    """Find the ParallelPipelineLoader inside a wrapper chain
    (PrefetchLoader / DPLoader / pipeline in any nesting) and return its
    stats, or None when the chain has no pipeline."""
    from hydragnn_tpu.data.loader import iter_loader_chain

    for ld in iter_loader_chain(loader):
        if isinstance(ld, ParallelPipelineLoader):
            return ld.pipeline_stats()
    return None
