"""Segment reductions — the scatter/gather core of message passing.

TPU-native replacement for torch_scatter/torch_sparse segment ops
(reference dep: requirements-pyg.txt; used by every PyG conv in
hydragnn/models/*). Built on ``jax.ops.segment_*`` with static
``num_segments``. On the v5e XLA lowers those to a sort of the indices
(once a step and index array) and a scatter-add fusion that reads the
update rows through the sort's permutation and adds them one after
another, at 38-56 GB/s (PERF.md sections 5 and 6). Told that the
indices are sorted it skips the sort and reads the rows in place: the
same additions in the same order in two thirds of the time, so the
receiver aggregation tells it where collation has sorted the edges
(``_sum_at_receivers``), and DimeNet's triplet reduce where collation
has built the triplets in ``t_ji`` order (``sum_over_triplets``).
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp

from hydragnn_tpu.utils import tracer as tr


@tr.scoped("segment/sum")
def segment_sum(
    data: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    mask: Optional[jax.Array] = None,
    *,
    indices_are_sorted: bool = False,
) -> jax.Array:
    if mask is not None:
        data = jnp.where(_bcast(mask, data), data, 0)
    return jax.ops.segment_sum(
        data, segment_ids, num_segments=num_segments,
        indices_are_sorted=indices_are_sorted,
    )


@tr.scoped("segment/mean")
def segment_mean(
    data: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    total = segment_sum(data, segment_ids, num_segments, mask)
    ones = jnp.ones(data.shape[0], dtype=data.dtype)
    if mask is not None:
        ones = jnp.where(mask, ones, 0)
    count = jax.ops.segment_sum(ones, segment_ids, num_segments=num_segments)
    count = jnp.maximum(count, 1)
    return total / _bcast_trailing(count, total)


@tr.scoped("segment/max")
def segment_max(
    data: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    mask: Optional[jax.Array] = None,
    *,
    empty_value: float = 0.0,
) -> jax.Array:
    neg = jnp.finfo(data.dtype).min if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
    if mask is not None:
        data = jnp.where(_bcast(mask, data), data, neg)
    out = jax.ops.segment_max(data, segment_ids, num_segments=num_segments)
    # Segments with no (unmasked) contributions come back as -inf/min;
    # normalize them to empty_value so padding graphs stay finite.
    return jnp.where(out <= neg, jnp.asarray(empty_value, out.dtype), out)


@tr.scoped("segment/min")
def segment_min(
    data: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    mask: Optional[jax.Array] = None,
    *,
    empty_value: float = 0.0,
) -> jax.Array:
    pos = jnp.finfo(data.dtype).max if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).max
    if mask is not None:
        data = jnp.where(_bcast(mask, data), data, pos)
    out = jax.ops.segment_min(data, segment_ids, num_segments=num_segments)
    return jnp.where(out >= pos, jnp.asarray(empty_value, out.dtype), out)


@tr.scoped("segment/std")
def segment_std(
    data: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    mask: Optional[jax.Array] = None,
    *,
    eps: float = 1e-5,
) -> jax.Array:
    """Per-segment standard deviation (PNA 'std' aggregator)."""
    mean = segment_mean(data, segment_ids, num_segments, mask)
    sq_mean = segment_mean(data * data, segment_ids, num_segments, mask)
    var = jnp.maximum(sq_mean - mean * mean, 0.0)
    return jnp.sqrt(var + eps)


@tr.scoped("segment/softmax")
def segment_softmax(
    logits: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Numerically-stable softmax within segments (GAT attention)."""
    seg_max = segment_max(logits, segment_ids, num_segments, mask)
    shifted = logits - seg_max[segment_ids]
    exp = jnp.exp(shifted)
    if mask is not None:
        exp = jnp.where(_bcast(mask, exp), exp, 0)
    denom = jax.ops.segment_sum(exp, segment_ids, num_segments=num_segments)
    denom = jnp.maximum(denom, 1e-16)
    return exp / denom[segment_ids]


def planned_path_wanted(num_edges: int, num_segments: int) -> bool:
    """THE dispatch policy for the planned sorted-segment kernels on a
    padded (E, N) shape: the shape must sit on the winning side of the
    regenerable crossover table (tools/segment_crossover.json via
    ops/pallas_segment.planned_profitable / fused_profitable — only
    TPU-measured rows count; oc20-class shapes measured 0.48-0.77x vs
    the XLA scatter and must never take the kernel silently) and the
    backend must be TPU. HYDRAGNN_TPU_SEGMENT_IMPL=pallas[_fused]
    forces the planned path anywhere (interpret mode off-TPU); =xla
    forces the scatter — the override ladder lives ONCE in
    ``_impl_gate``, composed by this attach-level policy (the loader's
    decision to pay the host-side edge sort,
    GraphLoader.segment_plan_enabled), by the per-call-site dispatch
    (``_plan_dispatch``, which adds the feature width and the call
    site's kernel-flavor capability), and by the flavor choice
    (``fused_path_wanted``) — one grammar, so plans are attached
    exactly where they can be consumed."""
    gate = _impl_gate()
    if gate is not None:
        return gate
    from hydragnn_tpu.ops.pallas_segment import (
        fused_profitable,
        planned_profitable,
    )

    # ATTACH-level vote: optimistic across the table's feature-width
    # rows (a plan is cheap and harmless if the per-call dispatch —
    # which knows F — declines; a pessimistic veto here would make an
    # F-specific measured fused win permanently unreachable).
    return planned_profitable(
        num_edges, num_segments, optimistic_ties=True
    ) or fused_profitable(num_edges, num_segments, optimistic_ties=True)


def _impl_gate() -> Optional[bool]:
    """THE env/backend override ladder, in one place: True = planned
    path forced on (HYDRAGNN_TPU_SEGMENT_IMPL=pallas[_fused]; interpret
    mode off-TPU), False = forced off (=xla, or a non-TPU backend),
    None = no override — consult the crossover table."""
    impl = _segment_impl()
    if impl.startswith("pallas"):
        return True
    if impl == "xla" or jax.default_backend() != "tpu":
        return False
    return None


def fused_path_wanted(
    num_edges: int,
    num_segments: int,
    feature_dim: Optional[int] = None,
) -> bool:
    """Kernel FLAVOR policy, subordinate to ``planned_path_wanted``:
    given that the planned path runs, should the fused edge-pipeline
    kernel (in-kernel gather/multiply/matmul) be taken over the
    reduce-only planned kernel? True only where the crossover table
    carries a TPU-MEASURED fused win (WHAT-IF rows never dispatch —
    graftboard's no-fabrication rule), or when
    HYDRAGNN_TPU_SEGMENT_IMPL=pallas_fused forces it for measurement
    (interpret mode off-TPU)."""
    impl = _segment_impl()
    if impl == "pallas_fused":
        return True
    if impl == "xla":
        return False
    from hydragnn_tpu.ops.pallas_segment import fused_profitable

    return fused_profitable(
        num_edges, num_segments, feature_dim=feature_dim
    )


def fused_bwd_wanted(
    num_edges: int,
    num_segments: int,
    feature_dim: Optional[int] = None,
) -> bool:
    """BACKWARD flavor policy (ISSUE 18), the pullback analogue of
    ``fused_path_wanted``: given that the forward ran
    ``edge_pipeline_planned`` (any flavor — its vjp is where this is
    consulted), should the symmetric one-pass Pallas backward kernel
    replace the XLA gather/scatter pullback? True only where the
    crossover table carries a TPU-MEASURED ``bwd_wins`` row (WHAT-IF
    rows never dispatch — gradients get no fabrication exemption), or
    when HYDRAGNN_TPU_SEGMENT_IMPL=pallas_fused forces it for
    measurement (interpret mode off-TPU). A non-TPU backend without
    the force stays on XLA: CPU/CI never takes the kernel silently."""
    impl = _segment_impl()
    if impl == "pallas_fused":
        return True
    if impl == "xla" or jax.default_backend() != "tpu":
        return False
    from hydragnn_tpu.ops.pallas_segment import bwd_profitable

    return bwd_profitable(
        num_edges, num_segments, feature_dim=feature_dim
    )


def _plan_dispatch(
    batch,
    feature_dim: Optional[int] = None,
    fused_capable: bool = False,
) -> bool:
    """Planned-kernel dispatch for a batch: a block plan must be
    present (collate with_segment_plan) AND the shared shape/backend
    policy must want THIS call site's kernel flavor. Reduce-only call
    sites (``aggregate_receivers`` — no fused variant exists for a
    plain sum) dispatch on the PLANNED verdict alone; fused-capable
    sites (product/pipeline) also dispatch where only the fused
    verdict wins. This is what keeps the acceptance rule honest: a
    shape where the reduce-only kernel measured a LOSS but the fused
    kernel a win must not drag plain sums onto the losing kernel.
    Shapes are trace-time constants, so the decision compiles away."""
    if batch.seg_window is None:
        return False
    gate = _impl_gate()
    if gate is not None:
        return gate
    from hydragnn_tpu.ops.pallas_segment import (
        fused_profitable,
        planned_profitable,
    )

    if planned_profitable(
        batch.num_edges, batch.num_nodes, feature_dim=feature_dim
    ):
        return True
    return fused_capable and fused_profitable(
        batch.num_edges, batch.num_nodes, feature_dim=feature_dim
    )


# Receiver-aggregation and triplet-reduce call sites by the scatter they
# lowered to, counted while a program is traced (the choice compiles
# away): ``dispatch_counter`` around a jitted function's body writes them
# as one telemetry row.
_DISPATCH = {
    "sorted_scatter": 0,
    "scatter": 0,
    "triplet_sorted_scatter": 0,
    "triplet_scatter": 0,
}


@contextlib.contextmanager
def dispatch_counter(program: str):
    """Trace-time: count the receiver sums (``_sum_at_receivers``) and
    the triplet reduces (``sum_over_triplets``) inside by whether they
    could promise XLA's scatter sorted indices, and write one row
    ``{"t": "setup", "phase": "segment_dispatch", "program": ...,
    "sorted_scatter": n, "scatter": m, "triplet_sorted_scatter": p,
    "triplet_scatter": q}`` to the live telemetry stream."""
    from hydragnn_tpu.utils import telemetry

    before = dict(_DISPATCH)
    try:
        yield
    finally:
        if telemetry.active():
            telemetry.emit(
                {
                    "t": "setup",
                    "phase": "segment_dispatch",
                    "program": program,
                    **{k: _DISPATCH[k] - before[k] for k in _DISPATCH},
                }
            )


def _sum_at_receivers(msg: jax.Array, batch) -> jax.Array:
    """The masked receiver sum by XLA's scatter-add, told that the
    indices are sorted where the batch promises it
    (``GraphBatch.receivers_sorted``, collation's word). On the v5e the
    sorted scatter adds each node's rows in the same order as the plain
    one, bit for bit, in 7.9 against 13.7 ms at E=790,776 F=128 and 6.8
    against 13.7 ms at E=568,056 F=256 (my chip run 4, PR 34): the one
    faster aggregation found that keeps that order, which the
    benchmark's ``correct`` holds the program to (PERF.md section 6)."""
    # a batch-like that says nothing (a hand-made namespace) promises nothing
    promised = bool(getattr(batch, "receivers_sorted", False))
    _DISPATCH["sorted_scatter" if promised else "scatter"] += 1
    return segment_sum(
        msg, batch.receivers, batch.num_nodes, mask=batch.edge_mask,
        indices_are_sorted=promised,
    )


def sum_over_triplets(trip: jax.Array, batch) -> jax.Array:
    """DimeNet's triplet reduce ``[T, F] -> [E, F]``: each triplet's row
    summed into its j->i edge ``t_ji``, padding triplets masked, by XLA's
    scatter-add told that the indices are sorted where the batch
    promises it (``GraphBatch.triplets_sorted``, collation's word). The
    same additions in the same order as the plain scatter, which sorts
    the ``T`` indices first and then reads the rows through the
    permutation."""
    # a batch-like that says nothing (a hand-made namespace) promises nothing
    promised = bool(getattr(batch, "triplets_sorted", False))
    _DISPATCH["triplet_sorted_scatter" if promised else "triplet_scatter"] += 1
    return segment_sum(
        trip, batch.t_ji, batch.num_edges, mask=batch.triplet_mask,
        indices_are_sorted=promised,
    )


@tr.scoped("edge_aggregate")
def aggregate_receivers(
    msg: jax.Array, batch, *, use_plan: Optional[bool] = None
) -> jax.Array:
    """Receiver-side message aggregation [E, F] -> [N, F].

    Dispatches to the Pallas sorted-segment kernel when the batch
    carries a block plan (collate with_segment_plan=True), we're on
    TPU, AND the padded shape is on the kernel's winning side of the
    measured crossover table (``_plan_dispatch``) — or anywhere when
    HYDRAGNN_TPU_SEGMENT_IMPL=pallas[_fused] forces it (interpret mode
    off-TPU); falls back to the XLA scatter path otherwise
    (``_sum_at_receivers``: the sorted scatter where the batch promises
    sorted receivers). Both apply the edge mask — on the planned path
    it is FOLDED INTO the plan's ``valid`` slots at collate time
    (apply_segment_plan), so no masked copy of ``msg`` is materialized
    ahead of the in-kernel gather.
    """
    if use_plan is None:
        use_plan = _plan_dispatch(batch, feature_dim=msg.shape[-1])
    if use_plan and batch.seg_window is not None:
        from hydragnn_tpu.ops.pallas_segment import segment_sum_planned

        return segment_sum_planned(
            msg,
            batch.seg_perm,
            batch.seg_ids,
            batch.seg_valid,
            batch.seg_window,
            batch.num_nodes,
        )
    return _sum_at_receivers(msg, batch)


@tr.scoped("edge_aggregate")
def aggregate_receivers_product(
    a: jax.Array, b: jax.Array, batch, *, use_plan: Optional[bool] = None
) -> jax.Array:
    """Receiver aggregation of an elementwise product: segment_sum(a*b)
    where a is typically gathered sender features and b the per-edge
    filter (the SchNet message pipeline). With a batch block plan the
    reduce runs through the planned Pallas kernel; the fused variant
    (gather AND multiply inside the kernel — one HBM pass) dispatches
    through ``fused_path_wanted`` (TPU-measured table rows, or forced
    by HYDRAGNN_TPU_SEGMENT_IMPL=pallas_fused)."""
    if use_plan is None:
        use_plan = _plan_dispatch(
            batch, feature_dim=a.shape[-1], fused_capable=True
        )
    if use_plan and batch.seg_window is not None:
        if fused_path_wanted(
            batch.num_edges, batch.num_nodes, feature_dim=a.shape[-1]
        ):
            from hydragnn_tpu.ops.pallas_segment import (
                segment_sum_product_planned,
            )

            # padding edges are invalid plan slots (edge_mask folded
            # into seg_valid at collate) — NO pre-masked copy of the
            # operands, that is the traffic the fusion removes
            return segment_sum_product_planned(
                a,
                b,
                batch.seg_perm,
                batch.seg_ids,
                batch.seg_valid,
                batch.seg_window,
                batch.num_nodes,
            )
        return aggregate_receivers(a * b, batch, use_plan=True)
    return _sum_at_receivers(a * b, batch)


@tr.scoped("edge_aggregate")
def aggregate_receivers_pipeline(
    a: jax.Array,
    b: Optional[jax.Array],
    batch,
    *,
    weight: Optional[jax.Array] = None,
    mean: bool = False,
    use_plan: Optional[bool] = None,
) -> jax.Array:
    """The FULL edge pipeline as one dispatched op:

        out = segment_sum((a * b) @ weight)        [N, F_out]

    (``b`` may be None to drop the filter multiply, ``weight`` None to
    drop the matmul; ``mean=True`` divides by the masked in-degree).
    On the fused planned path (``fused_path_wanted``) the whole chain
    runs in one Pallas pass over the batch's block plan — gather,
    multiply, matmul, reduce with no HBM intermediate, and the mean's
    per-node degree scale divides AFTER the fused sum (it commutes
    with the matmul mathematically; the reorder is inside the fused
    path's documented ulp tolerance). The fallback decomposes into the
    dispatched product/sum aggregation, the mean division, then the
    XLA matmul — the EXACT op order of the Dense-after-aggregate call
    sites it replaces."""
    if use_plan is None:
        use_plan = _plan_dispatch(
            batch, feature_dim=a.shape[-1], fused_capable=True
        )
    count = None
    if mean:
        count = jnp.maximum(
            degree(
                batch.receivers, batch.num_nodes, mask=batch.edge_mask,
                dtype=a.dtype,
            ),
            1,
        )
    if (
        use_plan
        and batch.seg_window is not None
        and fused_path_wanted(
            batch.num_edges, batch.num_nodes, feature_dim=a.shape[-1]
        )
    ):
        from hydragnn_tpu.ops.pallas_segment import edge_pipeline_planned

        out = edge_pipeline_planned(
            a,
            b,
            weight,
            batch.seg_perm,
            batch.seg_ids,
            batch.seg_valid,
            batch.seg_window,
            batch.num_nodes,
        )
        if count is not None:
            out = out / _bcast_trailing(count.astype(out.dtype), out)
        return out
    if b is not None:
        out = aggregate_receivers_product(a, b, batch, use_plan=use_plan)
    else:
        out = aggregate_receivers(a, batch, use_plan=use_plan)
    if count is not None:
        out = out / _bcast_trailing(count.astype(out.dtype), out)
    if weight is not None:
        out = out @ weight
    return out


@tr.scoped("edge_aggregate")
def aggregate_receivers_mean(
    msg: jax.Array, batch, *, use_plan: Optional[bool] = None
) -> jax.Array:
    """Receiver-side MEAN aggregation [E, F] -> [N, F] through the same
    planned-kernel dispatch as ``aggregate_receivers`` (sum via the
    winning path, then divide by the masked in-degree). Bit-identical
    to ``segment_mean(msg, batch.receivers, ...)`` on the scatter path
    — same masked sum, same count clamp."""
    total = aggregate_receivers(msg, batch, use_plan=use_plan)
    count = degree(
        batch.receivers, batch.num_nodes, mask=batch.edge_mask,
        dtype=msg.dtype,
    )
    count = jnp.maximum(count, 1)
    return total / _bcast_trailing(count, total)


@tr.scoped("edge_aggregate")
def segment_multi_aggregate(
    h: jax.Array,
    batch,
    *,
    eps: float = 1e-5,
    use_plan: Optional[bool] = None,
):
    """PNA's (mean, min, max, std) aggregator stack in TWO passes over
    the receiver-sorted edge array instead of four independent segment
    ops (ISSUE 18). The moment pass reduces ``concat([h, h*h])``
    through ``aggregate_receivers`` — ONE planned-dispatchable
    segment sum at feature width 2F that yields mean and std (the
    same ``sqrt(max(E[x^2]-E[x]^2, 0) + eps)`` arithmetic as
    ``segment_std``). The extreme pass reduces ``concat([h, -h])``
    through ONE ``segment_min`` (max = -min(-h); min and max have no
    sum decomposition, so they cannot ride the planned kernel — but
    they can share a scatter). Empty segments: the min-of-(-h)
    normalization yields -0.0 for the max half, which equals the 0.0
    ``empty_value`` of the separate ops. Numerically identical to the
    old four-op decomposition — same formulas, same clamp, same eps —
    just batched."""
    f = h.shape[-1]
    moments = aggregate_receivers(
        jnp.concatenate([h, h * h], axis=-1), batch, use_plan=use_plan
    )
    count = jnp.maximum(
        degree(
            batch.receivers, batch.num_nodes, mask=batch.edge_mask,
            dtype=h.dtype,
        ),
        1,
    )
    moments = moments / _bcast_trailing(count.astype(moments.dtype), moments)
    mean, sq_mean = moments[:, :f], moments[:, f:]
    var = jnp.maximum(sq_mean - mean * mean, 0.0)
    std = jnp.sqrt(var + eps)
    ext = segment_min(
        jnp.concatenate([h, -h], axis=-1),
        batch.receivers,
        batch.num_nodes,
        mask=batch.edge_mask,
    )
    mn, mx = ext[:, :f], -ext[:, f:]
    return mean, mn, mx, std


_IMPL_OVERRIDE = ""


def set_segment_impl_override(value: Optional[str]) -> None:
    """Config-surface kernel-flavor override (Training.segment_impl),
    last-set-wins. ``run_training`` calls this on EVERY run — an
    absent config key CLEARS it — so back-to-back runs in one process
    cannot leak each other's flavor (an env setdefault would latch the
    first run's value forever). The env var still takes precedence:
    one grammar, shell wins over config."""
    global _IMPL_OVERRIDE
    _IMPL_OVERRIDE = value or ""


def _segment_impl() -> str:
    import os

    return os.environ.get("HYDRAGNN_TPU_SEGMENT_IMPL") or _IMPL_OVERRIDE


def degree(
    segment_ids: jax.Array,
    num_segments: int,
    mask: Optional[jax.Array] = None,
    dtype=jnp.float32,
) -> jax.Array:
    ones = jnp.ones(segment_ids.shape[0], dtype=dtype)
    if mask is not None:
        ones = jnp.where(mask, ones, 0)
    return jax.ops.segment_sum(ones, segment_ids, num_segments=num_segments)


def _bcast(mask: jax.Array, data: jax.Array) -> jax.Array:
    """Reshape a [K] mask to broadcast against [K, ...] data."""
    return mask.reshape(mask.shape + (1,) * (data.ndim - mask.ndim))


def _bcast_trailing(vec: jax.Array, data: jax.Array) -> jax.Array:
    return vec.reshape(vec.shape + (1,) * (data.ndim - vec.ndim))
