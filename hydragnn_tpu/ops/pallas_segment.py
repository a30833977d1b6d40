"""Pallas TPU kernel: the fused edge pipeline over sorted-segment plans.

The message-passing hot op (SURVEY.md §7 step 2: "Pallas kernels for
gather→MLP→segment-reduce fusion") — receiver aggregation computed as a
chain of small one-hot matmuls on the MXU instead of a scatter-add, with
the whole edge pipeline (plan gather, filter multiply, dense matmul,
segment reduce) in ONE HBM pass:

  for each edge block b (an ALIGNED be-row tile of the receiver-sorted
  edge array, all of whose valid rows fall inside one BN-aligned node
  window w_b):
      msg   = where(valid, a_tile * b_tile, 0)        # VPU, in VMEM
      h     = msg @ W                                 # MXU [BE,Fi]@[Fi,Fo]
      onehot[n, e] = (seg[e] - BN * w_b == n)         # VPU compare
      out[window w_b] += onehot @ h                   # MXU [BN,BE]@[BE,Fo]

Host-side ``plan_sorted_blocks`` splits the sorted edge list into such
aligned tiles and emits per-block (tile, window) ids — prefetched
scalars that drive the input AND output BlockSpec index_maps. The input
tile index stages the plan gather through the Pallas pipeline itself
(HBM→VMEM tile copies), so the gathered ``[B*be, F]`` operand and the
``[E, F]`` message intermediate never materialize in HBM — this is the
dense-hardware formulation of arxiv 1906.11786 and the coarse-grained
fusion of FlashSchNet (arxiv 2602.13140). The aligned layout invariant
(``perm[b*be] % be == 0``; each block's slots are one contiguous tile)
is what makes the gather expressible as a BlockSpec: collate sorts
edges by receiver, so plan slots cover contiguous runs.

bf16 end-to-end: both matmuls run at DEFAULT precision for bf16 inputs
(native MXU passes, exact into the f32 accumulator) and HIGHEST for
f32; the output tile is ALWAYS f32 — a window's partial sums revisit
the tile across consecutive blocks — and is cast once at the end.
Bitwise identity with the XLA scatter is explicitly NOT the contract
here (ROADMAP "Attack single-digit MFU"): the gate is documented ulp
tolerances (tests/test_pallas_segment.py) + converged-loss parity
(tests/test_optimizer_precision_losses.py).

The backward is SYMMETRIC (ISSUE 18): the pullback of a segment sum
is a gather ``g[seg[e]]``, and under the aligned-block plan every
valid edge of a block reduces into one BN-aligned window — so the
cotangent gather is a window-tile READ (the forward's output
BlockSpec, flipped to an input), the message is recomputed in VMEM
from the already-staged input tiles, ``d_a``/``d_b`` are emitted at
their aligned input tile (``perm`` is a permutation: each edge row
owns exactly one slot, so the scatter-back is a conflict-free tile
write), and ``d_w`` accumulates into one f32 [F_in, F_out] tile
across blocks. The ``[E, F]`` message re-materialization and the
``[S, F_out]`` slot-cotangent of the XLA pullback never touch HBM.
Dispatch-gated exactly like the forward: only ``bwd_measured``
crossover rows take it (CPU/CI stays on the XLA pullback);
HYDRAGNN_TPU_SEGMENT_IMPL=pallas_fused forces it for measurement.

Dispatch is shape-keyed through a REGENERABLE crossover table
(tools/segment_crossover.json, written by tools/roofline_segment.py
--write-table): rows measured on a real TPU carry verdicts the
dispatcher trusts; rows derived off-TPU are labeled WHAT-IF and are
never dispatched on (graftboard's no-fabrication rule). Enable via
segment_sum_sorted or HYDRAGNN_TPU_SEGMENT_IMPL=pallas[_fused] (see
ops/segment.py — ``planned_path_wanted`` stays THE single policy).
"""

from __future__ import annotations

import functools
import json
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_BE = 512  # edges per block
DEFAULT_BN = 256  # node window (output tile rows)

# ----------------------------------------------------------------------
# Shape-keyed Pallas-vs-XLA crossover: a JSON table regenerated by
# ``tools/roofline_segment.py --write-table`` (the checked-in seed is
# the CPU/CI fallback). Verdict rows carry provenance: ``*_measured``
# is True only for rows timed on a real TPU; everything else is a
# WHAT-IF prediction (modeled traffic or off-TPU timing) that the
# dispatcher ignores — the kernel is never silently taken on a shape
# whose win was not measured.
# ----------------------------------------------------------------------
CROSSOVER_TABLE_ENV = "HYDRAGNN_TPU_SEGMENT_TABLE"
_DEFAULT_TABLE_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "tools",
    "segment_crossover.json",
)
_TABLE_CACHE: Dict[str, Tuple[dict, ...]] = {}


def crossover_table_path() -> str:
    return os.environ.get(CROSSOVER_TABLE_ENV, _DEFAULT_TABLE_PATH)


def load_crossover_table(path: Optional[str] = None) -> Tuple[dict, ...]:
    """Rows of the dispatch table (cached per path). Missing/corrupt
    table -> empty tuple: no basis for claims means the XLA scatter
    path, never a guess."""
    p = path or crossover_table_path()
    if p in _TABLE_CACHE:
        return _TABLE_CACHE[p]
    rows: Tuple[dict, ...] = ()
    try:
        with open(p) as f:
            doc = json.load(f)
        rows = tuple(
            r
            for r in doc.get("rows", [])
            if isinstance(r.get("num_edges"), int)
            and isinstance(r.get("num_segments"), int)
        )
    except (OSError, ValueError):
        rows = ()
    _TABLE_CACHE[p] = rows
    return rows


def reload_crossover_table(path: Optional[str] = None) -> Tuple[dict, ...]:
    """Drop the cached rows and re-read the table from disk. The cache
    is keyed by path, so a table regenerated in-process (``tools/
    roofline_segment.py --write-table``) or swapped via
    HYDRAGNN_TPU_SEGMENT_TABLE was never re-read before this existed —
    dispatch kept trusting the stale verdicts for the life of the
    process. ``path=None`` invalidates EVERY cached path (the env var
    may have changed, which changes which file subsequent loads
    resolve to)."""
    if path is None:
        _TABLE_CACHE.clear()
    else:
        _TABLE_CACHE.pop(path, None)
    return load_crossover_table(path)


def _measured_verdicts(kind: str, path: Optional[str] = None):
    """[(num_edges, num_segments, feature_dim_or_None, wins)] for rows
    whose ``kind`` verdict was measured on a real device
    (``{kind}_measured``) — WHAT-IF rows are excluded from dispatch by
    construction."""
    out = []
    for r in load_crossover_table(path):
        if r.get(f"{kind}_measured") and f"{kind}_wins" in r:
            out.append(
                (
                    r["num_edges"],
                    r["num_segments"],
                    r.get("feature_dim"),
                    bool(r[f"{kind}_wins"]),
                )
            )
    return out


def planned_profitable(
    num_edges: int,
    num_segments: int,
    table=None,
    feature_dim: Optional[int] = None,
    optimistic_ties: bool = False,
) -> bool:
    """True when the planned sorted-segment kernel WINS for a padded
    (E, N[, F]) shape — a nearest-neighbor lookup in log space over
    the MEASURED rows of the crossover table (measured verdicts
    generalize to nearby shapes by log-distance, exactly as the PR-3
    two-anchor table always did; only UNMEASURED/WHAT-IF rows are
    barred from dispatch). Backend and HYDRAGNN_TPU_SEGMENT_IMPL
    overrides live in ONE place, ``ops.segment`` (the production
    dispatch policies) — keep this function env-free so the two can
    never disagree on the grammar. ``table`` accepts pre-resolved
    (E, N, wins) or (E, N, F, wins) rows (tests); default loads
    tools/segment_crossover.json. ``optimistic_ties`` is for
    ATTACH-level policy (see ``_nearest_verdict``)."""
    rows = table if table is not None else _measured_verdicts("planned")
    return _nearest_verdict(
        rows, num_edges, num_segments, feature_dim, optimistic_ties
    )


def fused_profitable(
    num_edges: int,
    num_segments: int,
    table=None,
    feature_dim: Optional[int] = None,
    optimistic_ties: bool = False,
) -> bool:
    """True when the FUSED edge-pipeline kernel was measured to beat
    the best unfused path at (the nearest measured shape to) this
    shape. Seed tables carry only WHAT-IF fused rows (the fused
    kernel is new and unmeasured on TPU), so this returns False until
    ``tools/roofline_segment.py --write-table`` is re-run on the
    target chip — WHAT-IF rows never dispatch (the no-fabrication
    rule); measured verdicts DO generalize to nearby shapes by
    log-distance, like every crossover verdict since PR 3.
    HYDRAGNN_TPU_SEGMENT_IMPL=pallas_fused forces it anywhere for
    measurement."""
    rows = table if table is not None else _measured_verdicts("fused")
    return _nearest_verdict(
        rows, num_edges, num_segments, feature_dim, optimistic_ties
    )


def bwd_profitable(
    num_edges: int,
    num_segments: int,
    table=None,
    feature_dim: Optional[int] = None,
    optimistic_ties: bool = False,
) -> bool:
    """True when the symmetric Pallas BACKWARD kernel was measured to
    beat the XLA pullback at (the nearest measured shape to) this
    shape — the ``bwd_wins``/``bwd_measured`` columns of the crossover
    table, same grammar as ``planned_profitable``/``fused_profitable``.
    Seed tables carry only WHAT-IF bwd rows (modeled traffic), so this
    returns False until ``tools/roofline_segment.py --write-table``
    runs on the target chip: unmeasured shapes keep the XLA pullback
    (the no-fabrication rule applies to gradients exactly as it does
    to the forward). Env-free by design — backend and
    HYDRAGNN_TPU_SEGMENT_IMPL overrides live in ``ops.segment``
    (``fused_bwd_wanted``)."""
    rows = table if table is not None else _measured_verdicts("bwd")
    return _nearest_verdict(
        rows, num_edges, num_segments, feature_dim, optimistic_ties
    )


def _nearest_verdict(
    rows,
    num_edges: int,
    num_segments: int,
    feature_dim: Optional[int] = None,
    optimistic_ties: bool = False,
) -> bool:
    """Nearest measured row in log-shape space. Rows are (E, N, wins)
    or (E, N, F, wins); a regenerated table carries one row per
    feature width at the same (E, N), so F joins the distance when
    both the query and the row have it. EQUIDISTANT rows (e.g. an
    F-less query against an F-gridded table) vote conservatively by
    default: the kernel is taken only if EVERY tied row wins — never
    silently on a losing shape. ``optimistic_ties=True`` flips ties
    to ANY-wins: the ATTACH-level vote (loader plan attachment),
    where a plan is cheap and harmless if the per-call dispatch —
    which knows the feature width — later declines, and a veto would
    make an F-specific measured win permanently unreachable."""
    norm = []
    for r in rows:
        if isinstance(r, tuple):
            norm.append(r if len(r) == 4 else (r[0], r[1], None, r[2]))
        else:
            norm.append(r)
    if not norm:
        return False
    le = np.log(max(int(num_edges), 1))
    ln = np.log(max(int(num_segments), 1))
    lf = (
        np.log(max(int(feature_dim), 1)) if feature_dim else None
    )

    def dist(row):
        d = (le - np.log(max(row[0], 1))) ** 2 + (
            ln - np.log(max(row[1], 1))
        ) ** 2
        if lf is not None and row[2]:
            d += (lf - np.log(max(row[2], 1))) ** 2
        return d

    dmin = min(dist(r) for r in norm)
    tied = [r for r in norm if dist(r) <= dmin + 1e-12]
    vote = any if optimistic_ties else all
    return vote(bool(r[3]) for r in tied)


def plan_sorted_blocks(
    seg_sorted: np.ndarray,
    num_segments: int,
    be: int = DEFAULT_BE,
    bn: int = DEFAULT_BN,
    edge_valid: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Split sorted segment ids into window-aligned TILE blocks.

    Each block covers one be-ALIGNED tile of the sorted edge array
    (rows [t*be, (t+1)*be)); a window's run [a, b) maps to the tiles it
    touches, with rows outside the run masked invalid (boundary tiles
    are visited once per adjacent window). The alignment is the fused
    kernel's gather contract: block b's rows are exactly tile
    ``perm[b*be] // be``, so a BlockSpec index_map can stage the plan
    gather through the Pallas HBM→VMEM pipeline — no gathered operand
    ever materializes in HBM.

    ``edge_valid`` (optional [E] bool) masks padding edges out of the
    plan itself, replacing the pre-kernel ``where(edge_mask, ...)``
    data copy the in-kernel gather exists to avoid. It must leave each
    block's valid rows ONE contiguous run (collate's mask does: padding
    edges sort after every real one) — the kernels rebuild the mask
    from the run's two end points (``_block_runs``); a mask with holes
    raises here.

    Returns (perm, seg_padded, valid, window_id):
      perm      [B*be] int32 — edge row per slot (tile arange, clamped
                to E-1 past the array end; masked by ``valid``)
      seg_padded[B*be] int32 — segment id per slot (window start for
                invalid slots)
      valid     [B*be] bool
      window_id [B]    int32 — output tile row-block per edge block
    """
    seg = np.asarray(seg_sorted, np.int64)
    e = len(seg)
    n_windows = max((num_segments + bn - 1) // bn, 1)
    windows = seg // bn
    ev = None if edge_valid is None else np.asarray(edge_valid, bool)
    # Edge run per window (sorted ids -> contiguous runs; empty windows
    # still get one all-padding block so their output tile is zeroed).
    starts = np.searchsorted(windows, np.arange(n_windows), side="left")
    ends = np.searchsorted(windows, np.arange(n_windows), side="right")
    perm_l, seg_l, val_l, win_l = [], [], [], []
    for w in range(n_windows):
        a, b = int(starts[w]), int(ends[w])
        tiles = range(a // be, (b - 1) // be + 1) if b > a else (0,)
        for t in tiles:
            rows = np.arange(t * be, (t + 1) * be)
            valid = (rows >= a) & (rows < b)
            rows_c = np.minimum(rows, max(e - 1, 0))
            if ev is not None:
                valid &= ev[rows_c]
                hit = np.flatnonzero(valid)
                if len(hit) and hit[-1] - hit[0] + 1 != len(hit):
                    raise ValueError(
                        "edge_valid leaves holes inside a block's run "
                        f"(window {w}, tile {t}): the planned kernels "
                        "need each block's valid rows contiguous"
                    )
            segs = np.full(be, w * bn, np.int64)
            segs[valid] = seg[rows_c[valid]]
            perm_l.append(rows_c)
            seg_l.append(segs)
            val_l.append(valid)
            win_l.append(w)
    return (
        np.concatenate(perm_l).astype(np.int32),
        np.concatenate(seg_l).astype(np.int32),
        np.concatenate(val_l),
        np.asarray(win_l, np.int32),
    )


def _interpret() -> bool:
    """Off-TPU there is no Mosaic backend: interpret mode keeps the
    kernels differentially testable on the CPU mesh. On a TPU the
    kernels always go through Mosaic, and a refusal raises — nothing
    falls back to the XLA path on a compile error."""
    return jax.default_backend() != "tpu"


def _block_runs(valid: jax.Array, n_blocks: int, be: int):
    """Per-block ``[lo, hi)`` of the valid slots. A block's valid rows
    are ONE contiguous run of its tile (``plan_sorted_blocks`` enforces
    it), so two scalars per block carry the whole mask — they ride
    scalar prefetch and the kernels rebuild the mask from iotas in
    whichever orientation (lane- or sublane-major) each use needs,
    instead of relayouting a 1-D mask vector inside the kernel."""
    v = valid.reshape(n_blocks, be)
    lo = jnp.argmax(v, axis=1).astype(jnp.int32)
    hi = lo + jnp.sum(v, axis=1, dtype=jnp.int32)
    return lo, hi


def _block_masks(lo, hi, be: int):
    """(lane-major [1, be], sublane-major [be, 1]) masks of the valid
    run ``[lo, hi)`` of one block, both built 2-D from iotas."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, be), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (be, 1), 0)
    return (lane >= lo) & (lane < hi), (row >= lo) & (row < hi)


def _edge_pipeline_kernel(
    tile_ref,  # [B] scalar prefetch (drives the input index_maps)
    window_ref,  # [B] scalar prefetch (drives the output index_map)
    lo_ref,  # [B] scalar prefetch — first valid slot of the block
    hi_ref,  # [B] scalar prefetch — one past the last valid slot
    seg_ref,
    a_ref,
    *rest,
    bn,
    be,
    has_b,
    has_w,
):
    from jax.experimental import pallas as pl

    out_ref = rest[-1]
    b_ref = rest[0] if has_b else None
    w_ref = rest[1 if has_b else 0] if has_w else None

    i = pl.program_id(0)
    node_base = window_ref[i] * bn
    lane_valid, row_valid = _block_masks(lo_ref[i], hi_ref[i], be)
    # invalid slots match no window row
    local = jnp.where(lane_valid, seg_ref[0:1, :] - node_base, -1)  # [1, be]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bn, be), 0)
    onehot = local == rows
    # f32 data must not round through the MXU's bf16 multiplies; bf16
    # data multiplies natively (exact into the f32 MXU accumulator) —
    # including the h operand of the reduce matmul, which matches the
    # unfused reference's bf16 materialization of the dense output.
    precision = (
        jax.lax.Precision.HIGHEST
        if a_ref.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    block = a_ref[:].astype(jnp.float32)
    if has_b:
        # the filter multiply happens here in VMEM, so the [E, F]
        # message intermediate never exists in HBM
        block = block * b_ref[:].astype(jnp.float32)
    # SELECT (not multiply) against valid: rows past the array end of a
    # boundary tile are Pallas padding with unspecified values — a
    # select kills them even if they read back NaN.
    block = jnp.where(row_valid, block, 0.0)
    if has_w:
        # the dense layer's matmul in the same pass — this is what adds
        # real MXU flops per HBM byte (arithmetic intensity)
        block = jax.lax.dot(
            block, w_ref[:].astype(jnp.float32), precision=precision
        )
    acc = jax.lax.dot(onehot.astype(jnp.float32), block, precision=precision)

    is_first = jnp.logical_or(
        i == 0, window_ref[i] != window_ref[jnp.maximum(i - 1, 0)]
    )

    @pl.when(is_first)
    def _():
        out_ref[:] = acc.astype(out_ref.dtype)

    @pl.when(jnp.logical_not(is_first))
    def _():
        out_ref[:] = out_ref[:] + acc.astype(out_ref.dtype)


def _seg_tiles(seg_padded: jax.Array, n_blocks: int, be: int) -> jax.Array:
    """1-D int operands trip Mosaic's layout rules; ship per-block ids
    as (8, be) tiles (sublane dim must be a multiple of 8) — each
    block's ids replicated across the 8 sublanes."""
    return jnp.repeat(
        seg_padded.reshape(n_blocks, 1, be), 8, axis=1
    ).reshape(n_blocks * 8, be)


@functools.partial(jax.jit, static_argnames=("num_segments", "bn", "be"))
def _pallas_edge_pipeline(
    a: jax.Array,  # [E, F_in] receiver-sorted edge operand (FULL array)
    b: Optional[jax.Array],  # [E, F_in] second operand (filter), or None
    w: Optional[jax.Array],  # [F_in, F_out] dense weight, or None
    tile_id: jax.Array,  # [B] int32 — input tile per block
    seg_padded: jax.Array,  # [B*be]
    valid: jax.Array,  # [B*be]
    window_id: jax.Array,  # [B]
    *,
    num_segments: int,
    bn: int,
    be: int,
):
    """One pallas_call for the whole edge pipeline. The gathered
    operand never exists in HBM: ``tile_id`` drives the input
    BlockSpec index_maps, so the Pallas pipeline DMAs each block's
    aligned tile straight into VMEM. The output tile is ALWAYS f32
    (window partials revisit it across consecutive blocks; bf16
    accumulation would lose precision for high-degree receivers) and
    is cast once at the end."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_blocks = window_id.shape[0]
    f_in = a.shape[1]
    f_out = w.shape[1] if w is not None else f_in
    n_pad = ((num_segments + bn - 1) // bn) * bn
    lo, hi = _block_runs(valid, n_blocks, be)

    # index maps see (grid index, *scalar-prefetch refs); (lo, hi) ride
    # along for the kernel body only
    def by_block(i, *_):
        return (i, 0)

    def by_tile(i, tile, *_):
        return (tile[i], 0)

    def by_window(i, tile, win, *_):
        return (win[i], 0)

    def resident(i, *_):
        return (0, 0)

    in_specs = [
        pl.BlockSpec((8, be), by_block),
        pl.BlockSpec((be, f_in), by_tile),
    ]
    operands = [_seg_tiles(seg_padded, n_blocks, be), a]
    if b is not None:
        in_specs.append(pl.BlockSpec((be, f_in), by_tile))
        operands.append(b)
    if w is not None:
        # constant index_map: the weight tile stays VMEM-resident
        in_specs.append(pl.BlockSpec((f_in, f_out), resident))
        operands.append(w)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # (tile_id, window_id) drive index_maps; (lo, hi) carry the mask
        num_scalar_prefetch=4,
        grid=(n_blocks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, f_out), by_window),
    )
    kernel = functools.partial(
        _edge_pipeline_kernel,
        bn=bn,
        be=be,
        has_b=b is not None,
        has_w=w is not None,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_pad, f_out), jnp.float32),
        grid_spec=grid_spec,
        interpret=_interpret(),
        # a stable kernel name for the trace's readers (the op itself
        # is a tpu_custom_call whatever the scope around it says)
        name="edge_pipeline_forward",
    )(tile_id, window_id, lo, hi, *operands)
    out_dtype = (
        jnp.promote_types(a.dtype, w.dtype) if w is not None else a.dtype
    )
    return out[:num_segments].astype(out_dtype)


def _plan_tiles(perm: jax.Array, be: int) -> jax.Array:
    """Per-block input tile index from the plan's slot permutation —
    the aligned-layout invariant makes this exact: slot 0 of every
    block holds its tile's first row (``perm[b*be] % be == 0``)."""
    return perm.reshape(-1, be)[:, 0] // be


def _edge_pipeline_bwd_kernel(
    tile_ref,  # [B] scalar prefetch — input tile per block
    out_tile_ref,  # [B] scalar prefetch — cummax(tile): d_a/d_b output tile
    window_ref,  # [B] scalar prefetch — cotangent window tile per block
    lo_ref,  # [B] scalar prefetch — first valid slot of the block
    hi_ref,  # [B] scalar prefetch — one past the last valid slot
    seg_ref,
    g_ref,  # [bn, F_out] cotangent window tile (the gather, as a read)
    a_ref,
    *rest,
    bn,
    be,
    has_b,
    has_w,
):
    from jax.experimental import pallas as pl

    n_in = int(has_b) + int(has_w)
    b_ref = rest[0] if has_b else None
    w_ref = rest[1 if has_b else 0] if has_w else None
    outs = rest[n_in:]
    da_ref = outs[0]
    db_ref = outs[1] if has_b else None
    dw_ref = outs[-1] if has_w else None

    i = pl.program_id(0)
    node_base = window_ref[i] * bn
    lane_valid, row_valid = _block_masks(lo_ref[i], hi_ref[i], be)
    # invalid slots match no window row
    local = jnp.where(lane_valid, seg_ref[0:1, :] - node_base, -1)  # [1, be]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bn, be), 0)
    onehot = (local == rows).astype(jnp.float32)
    precision = (
        jax.lax.Precision.HIGHEST
        if a_ref.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    # The cotangent gather slot_g[e] = g[seg[e]] as ONE MXU pass over
    # the staged window tile — the transpose of the forward's reduce
    # matmul. Invalid slots get exactly-zero rows (their onehot column
    # is all zeros), so downstream products need no extra select.
    g_tile = g_ref[:].astype(jnp.float32)
    slot_g = jax.lax.dot_general(
        onehot, g_tile, (((0,), (0,)), ((), ())), precision=precision
    )  # [be, F_out]
    if has_w:
        # d msg = slot_g @ W^T — contract F_out against F_out so the
        # weight tile is used as-staged (no in-VMEM transpose).
        d_h = jax.lax.dot_general(
            slot_g,
            w_ref[:].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            precision=precision,
        )  # [be, F_in]
    else:
        d_h = slot_g
    # SELECT (not multiply) against valid AFTER the products: rows past
    # the array end of a boundary tile are Pallas padding with
    # unspecified values, and 0 * NaN = NaN.
    a_t = a_ref[:].astype(jnp.float32)
    if has_b:
        b_t = b_ref[:].astype(jnp.float32)
        da = jnp.where(row_valid, d_h * b_t, 0.0)
        db = jnp.where(row_valid, d_h * a_t, 0.0)
    else:
        da = jnp.where(row_valid, d_h, 0.0)

    # d_a/d_b land at their ALIGNED input tile (perm is a permutation:
    # each edge row owns exactly one slot). Boundary tiles are revisited
    # by consecutive blocks of adjacent windows, so init on the first
    # visit and accumulate after — the same revisit discipline as the
    # forward's output tile, on the input side.
    is_first = jnp.logical_or(
        i == 0, out_tile_ref[i] != out_tile_ref[jnp.maximum(i - 1, 0)]
    )

    @pl.when(is_first)
    def _():
        da_ref[:] = da
        if has_b:
            db_ref[:] = db

    @pl.when(jnp.logical_not(is_first))
    def _():
        da_ref[:] = da_ref[:] + da
        if has_b:
            db_ref[:] = db_ref[:] + db

    if has_w:
        # d_w += msg^T @ slot_g into the one f32 [F_in, F_out] tile —
        # the message recomputed in VMEM from the already-staged tiles,
        # never written to HBM.
        msg = a_t * b_t if has_b else a_t
        msg = jnp.where(row_valid, msg, 0.0)
        dw = jax.lax.dot_general(
            msg, slot_g, (((0,), (0,)), ((), ())), precision=precision
        )  # [F_in, F_out]

        @pl.when(i == 0)
        def _():
            dw_ref[:] = dw

        @pl.when(i != 0)
        def _():
            dw_ref[:] = dw_ref[:] + dw


@functools.partial(jax.jit, static_argnames=("num_segments", "bn", "be"))
def _pallas_edge_pipeline_bwd(
    g: jax.Array,  # [num_segments, F_out] output cotangent
    a: jax.Array,  # [E, F_in] forward residual (FULL array)
    b: Optional[jax.Array],  # [E, F_in] or None
    w: Optional[jax.Array],  # [F_in, F_out] or None
    tile_id: jax.Array,  # [B] int32 — input tile per block
    out_tile: jax.Array,  # [B] int32 — cummax(tile_id): d_a/d_b tile
    seg_padded: jax.Array,  # [B*be]
    valid: jax.Array,  # [B*be]
    window_id: jax.Array,  # [B]
    *,
    num_segments: int,
    bn: int,
    be: int,
):
    """One pallas_call for the whole pullback. ``window_id`` drives the
    cotangent BlockSpec (the gather g[seg[e]] is a window-tile read),
    ``tile_id`` re-stages the forward's input tiles for the in-VMEM
    message recomputation, and ``out_tile`` places d_a/d_b at their
    aligned edge tile. ``out_tile`` must be the running max of
    ``tile_id``: real blocks visit input tiles in non-decreasing order
    (cummax is the identity for them), while padding and empty-window
    blocks READ tile 0 — routing their all-zero contribution at tile 0
    would re-initialize it mid-grid, so they accumulate zeros into
    whichever tile is already open instead. d_a/d_b/d_w tiles are
    ALWAYS f32 (boundary tiles and d_w are revisited across blocks)
    and are cast once at the end."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_blocks = window_id.shape[0]
    e, f_in = a.shape
    f_out = w.shape[1] if w is not None else f_in
    n_pad = ((num_segments + bn - 1) // bn) * bn
    t_tiles = (e + be - 1) // be
    # Zero-pad the cotangent to whole window tiles: the last window's
    # tile otherwise reads unspecified rows, and they reach slot_g
    # through the onehot matmul where a select cannot reject them.
    g_pad = jnp.pad(g, ((0, n_pad - num_segments), (0, 0)))
    lo, hi = _block_runs(valid, n_blocks, be)

    # index maps see (grid index, *scalar-prefetch refs); (lo, hi) ride
    # along for the kernel body only
    def by_block(i, *_):
        return (i, 0)

    def by_tile(i, tile, *_):
        return (tile[i], 0)

    def by_out_tile(i, tile, otile, *_):
        return (otile[i], 0)

    def by_window(i, tile, otile, win, *_):
        return (win[i], 0)

    def resident(i, *_):
        return (0, 0)

    in_specs = [
        pl.BlockSpec((8, be), by_block),
        pl.BlockSpec((bn, f_out), by_window),
        pl.BlockSpec((be, f_in), by_tile),
    ]
    operands = [_seg_tiles(seg_padded, n_blocks, be), g_pad, a]
    out_specs = [pl.BlockSpec((be, f_in), by_out_tile)]
    out_shapes = [jax.ShapeDtypeStruct((t_tiles * be, f_in), jnp.float32)]
    if b is not None:
        in_specs.append(pl.BlockSpec((be, f_in), by_tile))
        operands.append(b)
        out_specs.append(pl.BlockSpec((be, f_in), by_out_tile))
        out_shapes.append(
            jax.ShapeDtypeStruct((t_tiles * be, f_in), jnp.float32)
        )
    if w is not None:
        in_specs.append(pl.BlockSpec((f_in, f_out), resident))
        operands.append(w)
        out_specs.append(pl.BlockSpec((f_in, f_out), resident))
        out_shapes.append(jax.ShapeDtypeStruct((f_in, f_out), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # (tile_id, out_tile, window_id) drive index_maps; (lo, hi)
        # carry the mask
        num_scalar_prefetch=5,
        grid=(n_blocks,),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    kernel = functools.partial(
        _edge_pipeline_bwd_kernel,
        bn=bn,
        be=be,
        has_b=b is not None,
        has_w=w is not None,
    )
    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shapes),
        grid_spec=grid_spec,
        interpret=_interpret(),
        name="edge_pipeline_pullback",
    )(tile_id, out_tile, window_id, lo, hi, *operands)
    d_a = outs[0][:e].astype(a.dtype)
    d_b = outs[1][:e].astype(b.dtype) if b is not None else None
    d_w = outs[-1].astype(w.dtype) if w is not None else None
    return d_a, d_b, d_w


def edge_pipeline_bwd_planned(
    g: jax.Array,  # [num_segments, F_out] output cotangent
    a: jax.Array,
    b: Optional[jax.Array],
    w: Optional[jax.Array],
    perm: jax.Array,
    seg_padded: jax.Array,
    valid: jax.Array,
    window_id: jax.Array,
    num_segments: int,
    bn: int = DEFAULT_BN,
    be: int = DEFAULT_BE,
) -> Tuple[jax.Array, Optional[jax.Array], Optional[jax.Array]]:
    """Symmetric one-pass Pallas pullback of ``edge_pipeline_planned``
    — returns (d_a, d_b, d_w) with the same None-for-absent-operand
    shape as the XLA pullback. Equivalent within the documented ulp
    tolerances (tests/test_pallas_segment.py), NOT bitwise: the block
    decomposition changes the f32 accumulation grouping of d_w and of
    boundary-tile rows, exactly as in the forward."""
    tile = _plan_tiles(perm, be)
    # Running max, NOT tile itself: padding / empty-window blocks read
    # tile 0 and must not re-open the d_a/d_b tile 0 mid-grid (see
    # ``_pallas_edge_pipeline_bwd``).
    out_tile = jax.lax.cummax(tile, axis=0)
    return _pallas_edge_pipeline_bwd(
        g, a, b, w, tile, out_tile, seg_padded, valid, window_id,
        num_segments=num_segments, bn=bn, be=be,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def edge_pipeline_planned(
    a: jax.Array,  # [E, F_in] e.g. gathered sender features, edge order
    b: Optional[jax.Array],  # [E, F_in] per-edge filter, or None
    w: Optional[jax.Array],  # [F_in, F_out] dense weight, or None
    perm: jax.Array,  # [B*be] plan slot -> edge row (aligned tiles)
    seg_padded: jax.Array,  # [B*be]
    valid: jax.Array,  # [B*be] bool
    window_id: jax.Array,  # [B]
    num_segments: int,
    bn: int = DEFAULT_BN,
    be: int = DEFAULT_BE,
) -> jax.Array:
    """Differentiable fused edge pipeline over a block plan:

        out = segment_sum((a * b) @ w)     [N, F_out]

    with gather, multiply, matmul and reduce in ONE Pallas pass (``b``
    and/or ``w`` may be None to drop a stage). Equivalent to the
    XLA-scatter reference within the documented ulp tolerances
    (tests/test_pallas_segment.py) — NOT bitwise: block decomposition
    changes the f32 accumulation grouping. Edge masking lives in the
    plan's ``valid`` (see ``plan_sorted_blocks(edge_valid=...)``), so
    callers must NOT pre-mask — that would materialize the copy this
    kernel exists to avoid.
    """
    if a.shape[0] == 0:
        f_out = w.shape[1] if w is not None else a.shape[1]
        dt = jnp.promote_types(a.dtype, w.dtype) if w is not None else a.dtype
        return jnp.zeros((num_segments, f_out), dt)
    return _pallas_edge_pipeline(
        a, b, w, _plan_tiles(perm, be), seg_padded, valid, window_id,
        num_segments=num_segments, bn=bn, be=be,
    )


def _edge_pipeline_fwd(
    a, b, w, perm, seg_padded, valid, window_id, num_segments, bn, be
):
    out = edge_pipeline_planned(
        a, b, w, perm, seg_padded, valid, window_id, num_segments, bn, be
    )
    return out, (a, b, w, perm, seg_padded, valid, window_id)


def _edge_pipeline_bwd(num_segments, bn, be, res, g):
    """Pullback dispatch: the symmetric Pallas kernel when the policy
    says it was measured to win at this shape (or
    HYDRAGNN_TPU_SEGMENT_IMPL=pallas_fused forces it), the XLA
    gather/scatter pullback otherwise — CPU/CI and every unmeasured
    shape stay on XLA."""
    a, b, w, perm, seg_padded, valid, window_id = res
    if a.shape[0]:
        from hydragnn_tpu.ops.segment import fused_bwd_wanted

        if fused_bwd_wanted(a.shape[0], num_segments, a.shape[1]):
            d_a, d_b, d_w = edge_pipeline_bwd_planned(
                g, a, b, w, perm, seg_padded, valid, window_id,
                num_segments, bn, be,
            )
            return (d_a, d_b, d_w, None, None, None, None)
    d_a, d_b, d_w = _edge_pipeline_bwd_xla(
        a, b, w, perm, seg_padded, valid, g
    )
    return (d_a, d_b, d_w, None, None, None, None)


def _edge_pipeline_bwd_xla(a, b, w, perm, seg_padded, valid, g):
    """XLA pullback: d msg[e] = g[seg[e]] @ w^T, split across the
    product operands; d w re-materializes the message once. All
    arithmetic runs in the promoted dtype with ONE terminal cast per
    cotangent — bf16 operands otherwise round at every intermediate
    and drift whole ulps away from the reference autodiff."""
    ct = jnp.promote_types(a.dtype, g.dtype)
    vm = valid[:, None]
    slot_g = jnp.where(vm, g[seg_padded], 0).astype(ct)  # [S, F_out]
    d_w = None
    if w is not None:
        a_s = jnp.where(vm, a[perm], 0).astype(ct)
        msg_s = a_s * b[perm].astype(ct) if b is not None else a_s
        d_w = (msg_s.T @ slot_g).astype(w.dtype)
        d_h = slot_g @ w.T.astype(ct)  # [S, F_in]
    else:
        d_h = slot_g
    d_b = None
    if b is not None:
        d_a = (
            jnp.zeros(a.shape, ct)
            .at[perm]
            .add(jnp.where(vm, d_h * b[perm].astype(ct), 0))
            .astype(a.dtype)
        )
        d_b = (
            jnp.zeros(b.shape, ct)
            .at[perm]
            .add(jnp.where(vm, d_h * a[perm].astype(ct), 0))
            .astype(b.dtype)
        )
    else:
        d_a = (
            jnp.zeros(a.shape, ct)
            .at[perm]
            .add(jnp.where(vm, d_h, 0))
            .astype(a.dtype)
        )
    return (d_a, d_b, d_w)


edge_pipeline_planned.defvjp(_edge_pipeline_fwd, _edge_pipeline_bwd)


def segment_sum_planned(
    data: jax.Array,  # [E, F] edge data in the (receiver-sorted) order
    perm: jax.Array,  # [B*be] plan slot -> edge row
    seg_padded: jax.Array,  # [B*be]
    valid: jax.Array,  # [B*be] bool
    window_id: jax.Array,  # [B]
    num_segments: int,
    bn: int = DEFAULT_BN,
    be: int = DEFAULT_BE,
) -> jax.Array:
    """Sorted-segment sum with the block plan as RUNTIME inputs — plans
    become batch fields (collate computes them host-side), so one
    compiled step serves every batch of a bucket. The plan gather runs
    INSIDE the kernel (aligned tiles staged by the Pallas pipeline);
    nothing materializes in HBM between the edge array and the output
    tiles. Gradient = plan-scatter of g[seg] (via
    ``edge_pipeline_planned``'s vjp)."""
    return edge_pipeline_planned(
        data, None, None, perm, seg_padded, valid, window_id,
        num_segments, bn, be,
    )


def segment_sum_product_planned(
    a: jax.Array,  # [E, F] e.g. gathered sender features, edge order
    b: jax.Array,  # [E, F] e.g. filter weights, edge order
    perm: jax.Array,  # [B*be] plan slot -> edge row
    seg_padded: jax.Array,  # [B*be]
    valid: jax.Array,  # [B*be] bool
    window_id: jax.Array,  # [B]
    num_segments: int,
    bn: int = DEFAULT_BN,
    be: int = DEFAULT_BE,
) -> jax.Array:
    """Differentiable fused segment_sum(a * b) over a block plan — the
    multiply AND the plan gather inside the Pallas kernel (one HBM
    pass; both operands stream as aligned tiles)."""
    return edge_pipeline_planned(
        a, b, None, perm, seg_padded, valid, window_id,
        num_segments, bn, be,
    )


class SortedSegmentPlan:
    """Host-side reusable plan for a fixed (sorted) edge layout.

    The padded batches produced by ``collate`` have a static edge
    layout per bucket, so one plan serves every batch of that shape.
    """

    def __init__(
        self,
        seg_sorted: np.ndarray,
        num_segments: int,
        be: int = DEFAULT_BE,
        bn: int = DEFAULT_BN,
    ):
        perm, seg_p, valid, window = plan_sorted_blocks(
            seg_sorted, num_segments, be, bn
        )
        self.num_segments = int(num_segments)
        self.be, self.bn = be, bn
        self.perm = jnp.asarray(perm)
        self.seg_padded = jnp.asarray(seg_p)
        self.valid = jnp.asarray(valid)
        self.window_id = jnp.asarray(window)

    def __call__(self, data: jax.Array) -> jax.Array:
        """segment-sum of [E, F] edge data laid out as planned (the
        plan gather runs inside the kernel)."""
        return segment_sum_planned(
            data,
            self.perm,
            self.seg_padded,
            self.valid,
            self.window_id,
            self.num_segments,
            self.bn,
            self.be,
        )

    def reduce_product(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """Fused segment-sum of ``a * b`` (gather + multiply
        in-kernel)."""
        return segment_sum_product_planned(
            a, b, self.perm, self.seg_padded, self.valid, self.window_id,
            self.num_segments, self.bn, self.be,
        )

    def pipeline(
        self, a: jax.Array, b: Optional[jax.Array], w: jax.Array
    ) -> jax.Array:
        """Full fused pipeline segment_sum((a * b) @ w) — gather,
        filter multiply, dense matmul and reduce in one pass."""
        return edge_pipeline_planned(
            a, b, w, self.perm, self.seg_padded, self.valid,
            self.window_id, self.num_segments, self.bn, self.be,
        )


def plan_blocks_static(
    seg_sorted: np.ndarray,
    num_segments: int,
    n_blocks_static: int,
    be: int = DEFAULT_BE,
    bn: int = DEFAULT_BN,
    edge_valid: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """plan_sorted_blocks padded to a STATIC block count so plans can be
    batch data inside one compiled step (bucketed loaders have static
    E/N, and B <= ceil(E/be) + ceil(N/bn) =: n_blocks_static — the
    aligned-tile layout keeps the same bound: each window adds at most
    one boundary tile revisit, <= one extra block per window). Padding
    blocks repeat the last window, read tile 0, and have no valid slots
    (accumulate zeros)."""
    perm, seg_p, valid, window = plan_sorted_blocks(
        seg_sorted, num_segments, be, bn, edge_valid=edge_valid
    )
    b = len(window)
    if b > n_blocks_static:
        raise ValueError(
            f"plan needs {b} blocks > static bound {n_blocks_static}"
        )
    pad = n_blocks_static - b
    if pad:
        perm = np.concatenate([perm, np.zeros(pad * be, np.int32)])
        seg_p = np.concatenate(
            [seg_p, np.full(pad * be, int(window[-1]) * bn, np.int32)]
        )
        valid = np.concatenate([valid, np.zeros(pad * be, bool)])
        window = np.concatenate(
            [window, np.full(pad, window[-1], np.int32)]
        )
    return perm, seg_p, valid, window


def static_block_bound(
    num_edges: int, num_segments: int, be: int = DEFAULT_BE, bn: int = DEFAULT_BN
) -> int:
    return (num_edges + be - 1) // be + (num_segments + bn - 1) // bn


def modeled_pipeline_traffic(
    num_edges: int,
    num_segments: int,
    f_in: int,
    f_out: Optional[int] = None,
    *,
    fused: bool,
    dtype_bytes: int = 2,
    be: int = DEFAULT_BE,
    bn: int = DEFAULT_BN,
) -> Dict[str, float]:
    """Modeled HBM traffic + model flops of the edge pipeline
    ``segment_sum((x[snd] * filt) @ W)`` on a padded (E, N) shape —
    pure size arithmetic, no devices (docs/ROOFLINE.md "Fused edge
    pipeline" documents the per-stage accounting). The unfused
    comparator models the IMPLEMENTED decomposition (the
    ``aggregate_receivers_pipeline`` fallback): reduce FIRST through
    the planned kernel (which also gathers in-kernel), then the dense
    matmul on the [N, F] node tensor — not a per-edge-matmul strawman.
    Both variants share the XLA sender gather (read E*Fi + write
    E*Fi); the fused kernel's saving is the [E, Fi] message
    round-trip (write + tile re-read becomes tile streams of the raw
    operands) and the [N, Fi] reduce-output round-trip into the
    matmul, in exchange for streaming the filter via boundary-
    revisiting tiles (S >= E slots). ``model_flops`` is the same
    algorithmic count for both (multiply + matmul + reduce-adds), so
    ``bytes_per_flop`` ordering is decided by traffic alone — the
    quantity ``graftboard roofline`` calls arithmetic intensity
    (inverted)."""
    e, n = int(num_edges), int(num_segments)
    fo = int(f_out) if f_out else int(f_in)
    fi = int(f_in)
    n_blocks = static_block_bound(e, n, be, bn)
    s = n_blocks * be  # plan slots (boundary tiles revisit rows)
    n_pad = ((n + bn - 1) // bn) * bn
    plan_ints = s * (4 + 4) + n_blocks * (4 + 4)  # seg+valid, tile+win
    gather = 2 * e * fi * dtype_bytes  # x[snd]: read + materialize
    matmul = bool(f_out)
    if fused:
        hbm = (
            gather
            + s * fi * dtype_bytes  # a tiles streamed into VMEM
            + s * fi * dtype_bytes  # filter tiles
            + (fi * fo * dtype_bytes if matmul else 0)  # W, VMEM-resident
            + n_pad * fo * 4  # f32 output tiles
            + plan_ints
        )
    else:
        hbm = (
            gather
            + e * fi * dtype_bytes  # filt read
            + e * fi * dtype_bytes  # msg materialize (kernel operand)
            + s * fi * dtype_bytes  # kernel streams msg tiles
            + n_pad * fi * 4  # f32 reduce-output tiles
            + (
                (n * fi + fi * fo + n * fo) * dtype_bytes  # [N,Fi] @ W
                if matmul
                else 0
            )
            + plan_ints
        )
    flops = 2.0 * e * fi + (2.0 * e * fi * fo if matmul else 0.0) + 2.0 * e * fo
    return {
        "hbm_bytes": float(hbm),
        "model_flops": flops,
        "bytes_per_flop": float(hbm) / flops,
    }


def modeled_pipeline_bwd_traffic(
    num_edges: int,
    num_segments: int,
    f_in: int,
    f_out: Optional[int] = None,
    *,
    fused: bool,
    dtype_bytes: int = 2,
    be: int = DEFAULT_BE,
    bn: int = DEFAULT_BN,
) -> Dict[str, float]:
    """Modeled HBM traffic + model flops of the edge-pipeline PULLBACK
    on a padded (E, N) shape — pure size arithmetic, no devices
    (docs/ROOFLINE.md "Backward traffic"). The unfused comparator
    models the IMPLEMENTED XLA pullback (``_edge_pipeline_bwd_xla``):
    gather the slot cotangent g[seg] ([S, Fo] materialized), RE-
    MATERIALIZE the [S, Fi] message for d_w, run the two transposed
    matmuls through HBM-resident [S, Fi]/[S, Fo] intermediates, and
    scatter d_h back to edge rows. The fused kernel replaces all of
    that with tile streams: the cotangent gather is a window-tile
    read, the message is recomputed in VMEM, and d_a/d_b are emitted
    at their aligned tiles — ``msg_remat_bytes`` and
    ``slot_ct_bytes`` are reported separately so the roofline tool
    can show exactly those terms disappearing. ``model_flops`` is the
    same algorithmic count for both, so ``bytes_per_flop`` ordering
    is decided by traffic alone."""
    e, n = int(num_edges), int(num_segments)
    fo = int(f_out) if f_out else int(f_in)
    fi = int(f_in)
    n_blocks = static_block_bound(e, n, be, bn)
    s = n_blocks * be  # plan slots (boundary tiles revisit rows)
    n_pad = ((n + bn - 1) // bn) * bn
    plan_ints = s * (4 + 4) + n_blocks * (4 + 4)
    matmul = bool(f_out)
    if fused:
        msg_remat = 0.0
        slot_ct = 0.0
        hbm = (
            n_blocks * bn * fo * dtype_bytes  # g window tiles (per block)
            + 2 * s * fi * dtype_bytes  # a + b tiles re-staged
            + (fi * fo * dtype_bytes if matmul else 0)  # W, VMEM-resident
            + 2 * 2 * e * fi * 4  # d_a, d_b f32 tiles (write) -> cast
            + (fi * fo * 4 if matmul else 0)  # the ONE d_w tile
            + plan_ints
        )
    else:
        # g[seg] materializes [S, Fo]; d_w re-materializes the [S, Fi]
        # message (gather a, gather b, multiply, write, re-read for the
        # transposed matmul); d_h materializes [S, Fi] and scatters.
        slot_ct = 2.0 * s * fo * dtype_bytes  # write + re-read g[seg]
        msg_remat = (
            (2 * s * fi * dtype_bytes + 2.0 * s * fi * dtype_bytes)
            if matmul
            else 0.0
        )  # operand gathers + msg write/re-read
        hbm = (
            n * fo * dtype_bytes  # g read (gather source)
            + slot_ct
            + msg_remat
            + (fi * fo * dtype_bytes if matmul else 0)  # W read
            + 2 * s * fi * dtype_bytes  # d_h materialize + re-read
            + 2 * s * fi * dtype_bytes  # residual gathers for d_a/d_b
            + 2 * 2 * e * fi * dtype_bytes  # scatter d_a, d_b
            + (fi * fo * dtype_bytes if matmul else 0)  # d_w write
            + plan_ints
        )
    # slot_g matmul-free gather flops are ~0; count the two transposed
    # matmuls (d_h = slot_g @ W^T, d_w = msg^T @ slot_g) plus the
    # elementwise splits — identical for both variants.
    flops = (
        (4.0 * e * fi * fo if matmul else 0.0)
        + 4.0 * e * fi  # d_a = d_h * b, d_b = d_h * a
        + 2.0 * e * fo  # cotangent gather-adds
    )
    return {
        "hbm_bytes": float(hbm),
        "msg_remat_bytes": float(msg_remat),
        "slot_ct_bytes": float(slot_ct),
        "model_flops": flops,
        "bytes_per_flop": float(hbm) / flops,
    }


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def segment_sum_sorted(
    data: jax.Array, seg_sorted: jax.Array, num_segments: int
) -> jax.Array:
    """Differentiable sorted-segment sum via the Pallas kernel.

    ``seg_sorted`` must be non-decreasing. The block plan is built
    host-side per unique id layout (cheap for bucketed batches), so this
    entry point must be called OUTSIDE jit; inside a jitted step,
    pre-build a ``SortedSegmentPlan`` and call it directly (its arrays
    become compile-time constants).
    """
    return _fwd_impl(data, seg_sorted, num_segments)


def _fwd_impl(data, seg_sorted, num_segments):
    plan = _plan_cache(
        np.asarray(jax.device_get(seg_sorted), np.int32).tobytes(),
        int(data.shape[0]),
        int(num_segments),
    )
    return plan(data)


@functools.lru_cache(maxsize=64)
def _plan_cache(seg_bytes: bytes, e: int, num_segments: int):
    seg = np.frombuffer(seg_bytes, np.int32, count=e)
    return SortedSegmentPlan(seg, num_segments)


def _vjp_fwd(data, seg_sorted, num_segments):
    return _fwd_impl(data, seg_sorted, num_segments), seg_sorted


def _vjp_bwd(num_segments, seg_sorted, g):
    # d/d data of a segment sum = broadcast back: gather rows.
    return (g[seg_sorted], None)


segment_sum_sorted.defvjp(_vjp_fwd, _vjp_bwd)
