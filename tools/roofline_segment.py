#!/usr/bin/env python
"""Roofline measurement + crossover-table generator for the
message-passing edge pipeline.

Measures, over a SHAPE GRID covering the packed-budget classes
(zinc / qm9 / oc20 scales x feature width), bf16 and f32:

  xla_reduce            out[n] = sum_{rcv[e]=n} msg[e]     (XLA scatter)
  pallas_reduce         same, via the planned one-hot MXU kernel
                        (plan gather in-kernel)
  xla_pipeline          segment_sum(x[snd] * filt)         (XLA fusion)
  pallas_pipeline       XLA gather+multiply, planned Pallas reduce
  pallas_fused          gather AND multiply inside the kernel
  xla_pipeline_w        segment_sum(x[snd] * filt) @ W     (full edge op)
  pallas_pipeline_w     unfused planned reduce, then @ W   (full edge op)
  pallas_fused_pipeline gather+multiply+matmul+reduce in ONE pass
                        (ops/pallas_segment.edge_pipeline_planned)
  xla_bwd               the XLA pullback of the full edge op (gathers
                        g[seg], RE-MATERIALIZES the [E, F] message for
                        d_w, scatters d_h back)
  pallas_fused_bwd      the symmetric one-pass Pallas pullback
                        (edge_pipeline_bwd_planned): cotangent gather
                        as a window-tile read, message recomputed in
                        VMEM, d_a/d_b at aligned tiles

Each shape also prints MODELED bwd bytes (modeled_pipeline_bwd_traffic)
with the message-rematerialization and slot-cotangent terms broken out
— the fused column shows both terms at exactly 0 (they never touch
HBM); that is the traffic the symmetric backward exists to delete.

and reports achieved HBM bandwidth against the chip's peak — the
reduce-only rows are memory-bound so %peak IS their utilization
measure; the `_w` rows add real MXU flops per HBM byte, which is the
arithmetic-intensity raise `graftboard roofline` attributes
(docs/ROOFLINE.md).

Run on the real chip:   python tools/roofline_segment.py
Refresh the dispatch table (tools/segment_crossover.json):
                        python tools/roofline_segment.py --write-table

Table refresh MERGES by (num_edges, num_segments, feature_dim): rows
measured on a TPU get ``planned_measured``/``fused_measured``/
``bwd_measured`` = true and become dispatch verdicts; rows produced
off-TPU are labeled WHAT-IF (``*_measured`` = false) and are NEVER
dispatched on (graftboard's no-fabrication rule) — the checked-in
seed therefore stays the CPU/CI fallback with only its two measured
planned anchors active. After a write the
in-process table cache is invalidated (reload_crossover_table), so a
refreshed table takes effect without a process restart.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Peak HBM bandwidth by device_kind (public specs, bytes/sec).
PEAK_BW = {
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,
    "TPU v6e": 1640e9,
}

# Shape grid: the packed-budget classes x feature width. num_filters
# for zinc/qm9-class models is 64-128; oc20-class runs wider. The
# anchors (qm9_b128_f128, oc20_b32_f256) coincide with the seed
# table's two measured shapes so the historical planned verdicts stay
# attached to real rows.
SHAPES = {
    # name: (num_nodes, num_edges, feature_dim)
    "zinc_b64_f64": (1408, 3456, 64),
    "zinc_b64_f128": (1408, 3456, 128),
    "qm9_b128_f64": (4224, 33792, 64),
    "qm9_b128_f128": (4224, 33792, 128),
    "qm9_b128_f256": (4224, 33792, 256),
    "oc20_b32_f128": (8192, 327680, 128),
    "oc20_b32_f256": (8192, 327680, 256),
}

# HYDRAGNN_ROOFLINE_SHAPES=small: tiny shapes for validating the tool
# itself (e.g. CPU interpret mode) — numbers are meaningless there.
_shapes_env = os.environ.get("HYDRAGNN_ROOFLINE_SHAPES")
if _shapes_env == "small":
    SHAPES = {"tiny_f64": (512, 4096, 64)}
elif _shapes_env:
    raise SystemExit(
        f"HYDRAGNN_ROOFLINE_SHAPES={_shapes_env!r} not recognized "
        "(only 'small'); unset it for the full-scale shapes"
    )


def _graph(n, e, seed=0):
    rng = np.random.default_rng(seed)
    rcv = np.sort(rng.integers(0, n, e)).astype(np.int32)
    snd = rng.integers(0, n, e).astype(np.int32)
    return snd, rcv


def _time(fn, *args, iters=30):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def measure():
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.ops.pallas_segment import (
        SortedSegmentPlan,
        _edge_pipeline_bwd_xla,
        edge_pipeline_bwd_planned,
        modeled_pipeline_bwd_traffic,
    )

    kind = jax.devices()[0].device_kind
    peak = PEAK_BW.get(kind)
    print(f"device: {kind}  peak HBM: {peak/1e9 if peak else '?'} GB/s")
    results = {}
    for name, (n, e, f) in SHAPES.items():
        snd, rcv = _graph(n, e)
        for dtype in (jnp.bfloat16, jnp.float32):
            sz = np.dtype(dtype).itemsize
            rng = np.random.default_rng(1)
            msg = jnp.asarray(rng.normal(size=(e, f)), dtype)
            x = jnp.asarray(rng.normal(size=(n, f)), dtype)
            filt = jnp.asarray(rng.normal(size=(e, f)), dtype)
            # The dense weight stays f32 (master-weight discipline);
            # under bf16 the MXU rounds it per pass exactly like the
            # model's Dense layers.
            wmat = jnp.asarray(rng.normal(size=(f, f)), jnp.float32)
            rcv_d = jnp.asarray(rcv)
            snd_d = jnp.asarray(snd)
            plan = SortedSegmentPlan(rcv, n)

            xla_reduce = jax.jit(
                lambda m: jax.ops.segment_sum(m, rcv_d, num_segments=n)
            )
            pallas_reduce = jax.jit(lambda m: plan(m))
            xla_pipe = jax.jit(
                lambda xx, ff: jax.ops.segment_sum(
                    xx[snd_d] * ff, rcv_d, num_segments=n
                )
            )
            pallas_pipe = jax.jit(lambda xx, ff: plan(xx[snd_d] * ff))
            # gather + multiply inside the reduce kernel (one HBM pass
            # over aligned plan tiles)
            pallas_fused = jax.jit(
                lambda xx, ff: plan.reduce_product(xx[snd_d], ff)
            )
            # the FULL edge op: + the dense matmul. BOTH unfused
            # comparators must include @W — comparing the fused
            # full-op time against a matmul-less row would bias the
            # verdict against the kernel this tool exists to judge.
            xla_pipe_w = jax.jit(
                lambda xx, ff: jax.ops.segment_sum(
                    xx[snd_d] * ff, rcv_d, num_segments=n
                )
                @ wmat
            )
            pallas_pipe_w = jax.jit(
                lambda xx, ff: plan(xx[snd_d] * ff) @ wmat
            )
            pallas_fused_pipe = jax.jit(
                lambda xx, ff: plan.pipeline(xx[snd_d], ff, wmat)
            )

            # Correctness cross-check (documented ulp tolerances:
            # tests/test_pallas_segment.py is the gate; this is a
            # tool-level sanity net).
            ref = np.asarray(xla_pipe(x, filt), np.float32)
            for fn_, nm in ((pallas_pipe, "pipe"), (pallas_fused, "fused")):
                got = np.asarray(fn_(x, filt), np.float32)
                err = np.abs(ref - got).max() / max(np.abs(ref).max(), 1e-6)
                assert err < (2e-2 if dtype == jnp.bfloat16 else 1e-5), (nm, err)
            ref_w = np.asarray(xla_pipe_w(x, filt), np.float32)
            got_w = np.asarray(pallas_fused_pipe(x, filt), np.float32)
            err_w = np.abs(ref_w - got_w).max() / max(np.abs(ref_w).max(), 1e-6)
            assert err_w < (3e-2 if dtype == jnp.bfloat16 else 1e-4), err_w

            # BACKWARD of the full edge op: both pullbacks run over the
            # SAME residuals the vjp holds (the gathered edge operand,
            # the filter, the f32 weight) and the same cotangent.
            a_edge = jax.jit(lambda xx: xx[snd_d])(x)
            gvec = jnp.asarray(
                rng.normal(size=(n, f)),
                jnp.promote_types(dtype, jnp.float32),
            )
            pargs = (plan.perm, plan.seg_padded, plan.valid)
            xla_bwd = jax.jit(
                lambda gg: _edge_pipeline_bwd_xla(
                    a_edge, filt, wmat, *pargs, gg
                )
            )
            pallas_bwd = jax.jit(
                lambda gg: edge_pipeline_bwd_planned(
                    gg, a_edge, filt, wmat, *pargs, plan.window_id, n
                )
            )
            ref_g = [np.asarray(t, np.float32) for t in xla_bwd(gvec)]
            got_g = [np.asarray(t, np.float32) for t in pallas_bwd(gvec)]
            for rg, gg in zip(ref_g, got_g):
                err_b = np.abs(rg - gg).max() / max(np.abs(rg).max(), 1e-6)
                assert err_b < (3e-2 if dtype == jnp.bfloat16 else 1e-4), err_b

            mb_u = modeled_pipeline_bwd_traffic(
                e, n, f, f, fused=False, dtype_bytes=sz
            )
            mb_f = modeled_pipeline_bwd_traffic(
                e, n, f, f, fused=True, dtype_bytes=sz
            )
            print(
                f"{name:14s} {np.dtype(dtype).name:8s} bwd modeled bytes: "
                f"unfused {mb_u['hbm_bytes']/1e6:7.1f} MB "
                f"(msg_remat {mb_u['msg_remat_bytes']/1e6:.1f} MB, "
                f"slot_ct {mb_u['slot_ct_bytes']/1e6:.1f} MB) -> "
                f"fused {mb_f['hbm_bytes']/1e6:7.1f} MB "
                f"(msg_remat {mb_f['msg_remat_bytes']/1e6:.1f} MB, "
                f"slot_ct {mb_f['slot_ct_bytes']/1e6:.1f} MB)"
            )

            rows = {}
            reduce_bytes = (e * f + n * f) * sz
            pipe_bytes = (2 * e * f + n * f + e * f) * sz  # gather read,
            # filt read, msg materialize/stream, out write (upper bound
            # assumes the gather+mul fuses into one stream)
            pipe_w_bytes = pipe_bytes + (f * f + n * f) * sz
            for label, fn, args, bts in (
                ("xla_reduce", xla_reduce, (msg,), reduce_bytes),
                ("pallas_reduce", pallas_reduce, (msg,), reduce_bytes),
                ("xla_pipeline", xla_pipe, (x, filt), pipe_bytes),
                ("pallas_pipeline", pallas_pipe, (x, filt), pipe_bytes),
                ("pallas_fused", pallas_fused, (x, filt), pipe_bytes),
                ("xla_pipeline_w", xla_pipe_w, (x, filt), pipe_w_bytes),
                ("pallas_pipeline_w", pallas_pipe_w, (x, filt), pipe_w_bytes),
                (
                    "pallas_fused_pipeline",
                    pallas_fused_pipe,
                    (x, filt),
                    pipe_w_bytes,
                ),
                ("xla_bwd", xla_bwd, (gvec,), mb_u["hbm_bytes"]),
                ("pallas_fused_bwd", pallas_bwd, (gvec,), mb_f["hbm_bytes"]),
            ):
                dt = _time(fn, *args)
                bw = bts / dt
                rows[label] = (dt, bw)
                pct = f"{100*bw/peak:.0f}%" if peak else "n/a"
                print(
                    f"{name:14s} {np.dtype(dtype).name:8s} {label:22s} "
                    f"{dt*1e6:8.1f} us  {bw/1e9:7.1f} GB/s  ({pct} peak)"
                )
            results[(name, np.dtype(dtype).name)] = rows
            r = rows
            print(
                f"{name:14s} {np.dtype(dtype).name:8s} "
                f"pallas/xla reduce: {r['xla_reduce'][0]/r['pallas_reduce'][0]:.2f}x   "
                f"pipeline: {r['xla_pipeline'][0]/r['pallas_pipeline'][0]:.2f}x   "
                f"fused: {r['xla_pipeline'][0]/r['pallas_fused'][0]:.2f}x   "
                f"fused_w: {r['xla_pipeline_w'][0]/r['pallas_fused_pipeline'][0]:.2f}x   "
                f"bwd: {r['xla_bwd'][0]/r['pallas_fused_bwd'][0]:.2f}x"
            )
    return results


def default_table_path():
    from hydragnn_tpu.ops.pallas_segment import crossover_table_path

    return crossover_table_path()


def build_rows(results, device_kind: str, measured: bool):
    """Verdict rows from the bf16 measurements (the production
    precision): planned verdict from the unfused pipeline pair, fused
    verdict = the one-pass kernel beats the BEST unfused full-op path,
    bwd verdict = the symmetric pullback beats the XLA pullback."""
    rows = []
    for (name, dtname), r in results.items():
        if dtname != "bfloat16":
            continue
        n, e, f = SHAPES[name]
        planned_ratio = r["xla_pipeline"][0] / r["pallas_pipeline"][0]
        # fused verdict: the one-pass kernel vs the best UNFUSED
        # full-op path (both comparators include the dense matmul)
        best_unfused_w = min(
            r["xla_pipeline_w"][0], r["pallas_pipeline_w"][0]
        )
        fused_ratio = best_unfused_w / r["pallas_fused_pipeline"][0]
        bwd_ratio = r["xla_bwd"][0] / r["pallas_fused_bwd"][0]
        rows.append(
            {
                "name": name,
                "num_edges": int(e),
                "num_segments": int(n),
                "feature_dim": int(f),
                "planned_wins": bool(planned_ratio > 1.0),
                "planned_measured": bool(measured),
                "planned_ratio": round(float(planned_ratio), 3),
                "fused_wins": bool(fused_ratio > 1.0),
                "fused_measured": bool(measured),
                "fused_ratio": round(float(fused_ratio), 3),
                "bwd_wins": bool(bwd_ratio > 1.0),
                "bwd_measured": bool(measured),
                "bwd_ratio": round(float(bwd_ratio), 3),
                "dtype": "bfloat16",
                "basis": (
                    f"timed on {device_kind}"
                    if measured
                    else f"WHAT-IF: timed off-TPU ({device_kind}) — "
                    "not a dispatch basis"
                ),
            }
        )
    return rows


def write_table(results, path=None):
    import jax

    path = path or default_table_path()
    kind = jax.devices()[0].device_kind
    measured = jax.devices()[0].platform == "tpu"
    new_rows = build_rows(results, kind, measured)
    doc = {"version": 1, "rows": []}
    if os.path.exists(path):
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except ValueError:
            pass
    key = lambda r: (r["num_edges"], r["num_segments"], r.get("feature_dim"))  # noqa: E731
    merged = {key(r): r for r in doc.get("rows", [])}
    for r in new_rows:
        old = merged.get(key(r))
        if old and not measured and (
            old.get("planned_measured")
            or old.get("fused_measured")
            or old.get("bwd_measured")
        ):
            # never downgrade a measured row with a WHAT-IF re-run
            continue
        merged[key(r)] = r
    doc.update(
        version=1,
        generated_by="tools/roofline_segment.py --write-table",
        device_kind=kind,
        what_if_note=(
            "rows with *_measured=false are WHAT-IF (modeled or timed "
            "off-TPU) and are never used for dispatch "
            "(ops/pallas_segment._measured_verdicts)"
        ),
        rows=sorted(
            merged.values(),
            key=lambda r: (r["num_edges"], r["num_segments"]),
        ),
    )
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=1)
        fh.write("\n")
    # The dispatch table is cached per path in-process; a regenerated
    # table must take effect immediately (e.g. measure -> write -> run
    # in one process), not at the next interpreter start.
    from hydragnn_tpu.ops.pallas_segment import reload_crossover_table

    reload_crossover_table(path)
    print(f"wrote {len(doc['rows'])} rows -> {path} (measured={measured})")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--write-table",
        action="store_true",
        help="merge verdict rows into tools/segment_crossover.json",
    )
    ap.add_argument("--table", default=None, help="table path override")
    args = ap.parse_args(argv)
    results = measure()
    if args.write_table:
        write_table(results, args.table)
    return results


if __name__ == "__main__":
    main()
