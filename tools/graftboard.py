#!/usr/bin/env python
"""graftboard — render a run report from a telemetry JSONL stream.

Stdlib-only companion CLI to the run-telemetry subsystem
(hydragnn_tpu/utils/telemetry.py, docs/OBSERVABILITY.md): reads the
structured step stream a training run emitted (plus, when present, the
tracer timing CSVs next to it) and renders what the ROADMAP's perf work
needs to see — step-time composition (input-wait / host-dispatch /
sampled device-complete), per-spec live MFU against the roofline peak,
the recompile log with retrace-leak flags, pipeline starvation, and the
checkpoint writer's cost rows.

Usage:
    graftboard.py report <run>   [--json] [--csv PATH]
    graftboard.py roofline <run> [--json]
    graftboard.py diff <runA> <runB> [--json]
    graftboard.py fleet <run>    [--json]

``<run>`` is a ``telemetry.jsonl`` path or a run directory containing
one (e.g. ``logs/<log_name>``). ``diff`` renders an A/B comparison of
two runs (throughput, MFU, phase shares, recompiles) — the harness for
"did the optimization work" questions.

``fleet`` (ISSUE 14, docs/OBSERVABILITY.md "Fleet observability")
merges one run's per-process shards (``telemetry.jsonl`` +
``telemetry.proc<i>.jsonl``) and renders what single-stream reports
cannot see: per-process step-time skew per epoch, per-site
barrier-wait decomposition naming the LAST ARRIVER (the process its
peers waited on — identified by minimum ``barrier_ms``, which needs no
cross-host clock), a straggler verdict per epoch, and dead/stalled
process detection from heartbeat gaps. Partial fleets degrade LOUDLY:
a missing shard, a shard with no close row (killed process) or a
truncated tail each produce a warning in the report, never a crash.

``roofline`` renders the per-spec attribution table (ISSUE 8): analytic
vs counted flops, HBM bytes, arithmetic intensity, the roofline
ceiling ``min(peak_flops, intensity * peak_bw)``, the fraction of that
ceiling achieved, and a memory-bound / compute-bound verdict — the
measurement frame the bf16 + fused-Pallas work is judged in
(ROADMAP "Attack single-digit MFU"). Everything comes from the
stream's own emitted fields (``executable`` + ``spec_rollup`` rows and
the header's peak basis); a spec with no executable row renders with
no verdict — the tool never fabricates a bound-ness claim. When the
peak basis is ``roofline_anchor`` (CPU-captured streams) the table is
labeled a what-if on the anchor chip.

Robust parsing: a SIGKILL mid-write leaves at most one truncated tail
line (the stream writer appends whole lines); unparseable lines are
SKIPPED and counted (``skipped_lines``), never fatal — a killed run's
stream must still render.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

STREAM_NAME = "telemetry.jsonl"


# ----------------------------------------------------------------------
# Parsing
# ----------------------------------------------------------------------


def resolve_stream(path: str) -> str:
    if os.path.isdir(path):
        cand = os.path.join(path, STREAM_NAME)
        if os.path.exists(cand):
            return cand
        raise FileNotFoundError(
            f"{path} has no {STREAM_NAME} — was the run started with "
            "Training.Telemetry.enabled?"
        )
    return path


def read_stream(path: str) -> Tuple[List[dict], int]:
    """(rows, skipped_lines). Unparseable lines — the truncated tail a
    kill leaves, stray text — are skipped and counted, never fatal."""
    rows: List[dict] = []
    skipped = 0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                skipped += 1
                continue
            if isinstance(row, dict):
                rows.append(row)
            else:
                skipped += 1
    return rows, skipped


def _health_summary(health: List[dict], checkpoints: List[dict]) -> dict:
    """Aggregate the divergence guard's ``health`` rows
    (docs/OBSERVABILITY.md schema) into the numbers the report/diff
    sections render: skip/rollback/halt counts, the grad-norm envelope,
    bad-step provenance, and the writer's rejected (non-finite) saves.
    Empty rows → an all-zero summary so ``diff`` can compare runs with
    and without the guard.

    Rows are CUMULATIVE within an epoch (the monitor resets its
    grad-norm/bad-step accounting at epoch start, and an escalation
    row duplicates the epoch row's running stats), so the grad-norm
    envelope takes ONE row per epoch — the one with the most resolved
    samples — and combines across epochs; summing every row would
    double-count each escalated epoch. Bad steps are epoch-LOCAL
    indices in the rows, so they are summarized as ``[epoch, step]``
    pairs — e0:s3 and e1:s3 are different skipped batches, and
    ``diff`` must see them differ."""
    bad_steps = set()
    actions = {"epoch": 0, "rollback": 0, "halt": 0}
    fault_plans = set()
    skipped_total = rollbacks = 0
    per_epoch_gn: Dict[int, dict] = {}
    for r in health:
        ep = int(r.get("epoch", 0))
        actions[r.get("action", "epoch")] = (
            actions.get(r.get("action", "epoch"), 0) + 1
        )
        for b in r.get("bad_steps") or []:
            bad_steps.add((ep, int(b)))
        skipped_total = max(skipped_total, int(r.get("skipped_total", 0)))
        rollbacks = max(rollbacks, int(r.get("rollbacks", 0)))
        if r.get("gnorm_steps"):
            prev = per_epoch_gn.get(ep)
            if prev is None or int(r["gnorm_steps"]) >= int(
                prev["gnorm_steps"]
            ):
                per_epoch_gn[ep] = r
        if r.get("fault_plan"):
            fault_plans.add(r["fault_plan"])
    gn_min = gn_max = None
    gn_sum = 0.0
    gn_steps = 0
    for r in per_epoch_gn.values():
        n = int(r["gnorm_steps"])
        gn_steps += n
        gn_sum += float(r.get("gnorm_mean", 0.0)) * n
        lo, hi = r.get("gnorm_min"), r.get("gnorm_max")
        if lo is not None:
            gn_min = lo if gn_min is None else min(gn_min, lo)
        if hi is not None:
            gn_max = hi if gn_max is None else max(gn_max, hi)
    rejected = sum(
        1 for r in checkpoints if r.get("event") == "rejected"
    )
    return {
        "rows": len(health),
        "skipped_total": skipped_total,
        "bad_steps": [list(p) for p in sorted(bad_steps)],
        "rollbacks": rollbacks,
        "halts": actions.get("halt", 0),
        "rejected_saves": rejected,
        "gnorm_min": gn_min,
        "gnorm_max": gn_max,
        "gnorm_mean": (gn_sum / gn_steps) if gn_steps else None,
        "gnorm_steps": gn_steps,
        "fault_plans": sorted(fault_plans),
    }


def _serve_summary(serve: List[dict], rollups: List[dict]) -> dict:
    """Aggregate the serving rows (docs/SERVING.md "Telemetry"): the
    LAST ``serve_rollup`` carries the run's p50/p99/slot-waste
    headline; the per-bin ``serve`` rows contribute the per-spec
    dispatch breakdown and the queue-depth envelope. Empty rows → an
    all-empty summary so ``report`` on a pure-training stream renders
    no serving section."""
    per_spec: Dict[str, dict] = {}
    depth_max = 0
    for r in serve:
        spec = r.get("spec", "?")
        agg = per_spec.setdefault(
            spec,
            {
                "dispatches": 0,
                "graphs": 0,
                "nodes": 0,
                "edges": 0,
                "reasons": {},
            },
        )
        agg["dispatches"] += 1
        agg["graphs"] += int(r.get("graphs", 0))
        agg["nodes"] += int(r.get("nodes", 0))
        agg["edges"] += int(r.get("edges", 0))
        reason = r.get("reason", "?")
        agg["reasons"][reason] = agg["reasons"].get(reason, 0) + 1
        depth_max = max(depth_max, int(r.get("queue_depth", 0) or 0))
    return {
        "bins": len(serve),
        "queue_depth_max": depth_max,
        "per_spec": per_spec,
        "rollup": rollups[-1] if rollups else None,
    }


def _rollout_summary(rollout: List[dict], events: List[dict]) -> dict:
    """Aggregate the MD ``rollout`` rows (docs/SIMULATION.md,
    docs/OBSERVABILITY.md schema): committed steps, macro dispatches,
    rebuild totals, containment events (overflow / non-finite / policy
    actions), the energy-drift envelope and the throughput headline.
    Empty rows → an all-zero summary so ``report`` on a pure-training
    stream renders no simulation section."""
    actions = {}
    for e in events:
        a = e.get("action", "?")
        actions[a] = actions.get(a, 0) + 1
    last = rollout[-1] if rollout else {}
    drift_max = 0.0
    overflow_events = nonfinite_events = 0
    per_spec: Dict[str, int] = {}
    for r in rollout:
        drift_max = max(drift_max, abs(float(r.get("drift", 0.0) or 0.0)))
        if int(r.get("overflow", 0) or 0) > 0:
            overflow_events += 1
        if r.get("nonfinite"):
            nonfinite_events += 1
        spec = r.get("spec", "?")
        per_spec[spec] = per_spec.get(spec, 0) + 1
    return {
        "macros": len(rollout),
        "steps": int(last.get("step", 0) or 0),
        "k": last.get("k"),
        "dt": last.get("dt"),
        "rebuilds": int(last.get("rebuilds", 0) or 0),
        "overflow_events": overflow_events,
        "nonfinite_events": nonfinite_events,
        "actions": actions,
        "halts": actions.get("halt", 0),
        "drift_last": last.get("drift"),
        "drift_max": drift_max,
        "steps_per_sec": last.get("steps_per_sec"),
        "ns_per_day": last.get("ns_per_day"),
        "per_spec": per_spec,
    }


def build_report(path: str) -> dict:
    """Aggregate a stream into the report dict ``render_report`` prints
    (and tests/the telemetry_smoke entry leg assert on)."""
    path = resolve_stream(path)
    rows, skipped = read_stream(path)
    return _report_from_rows(path, rows, skipped)


def _report_from_rows(path: str, rows: List[dict], skipped: int) -> dict:
    """The aggregation core of ``build_report``, factored so ``fleet``
    can reuse it on shards it already read (one pass per shard)."""
    header = next((r for r in rows if r.get("t") == "header"), {})
    close = next((r for r in rows if r.get("t") == "close"), None)

    epochs = [r for r in rows if r.get("t") == "epoch"]
    epochs.sort(key=lambda r: r.get("epoch", 0))

    # Step-time breakdown per (region, feed, scheme, spec).
    breakdown: Dict[tuple, dict] = {}
    for r in rows:
        if r.get("t") != "step":
            continue
        key = (
            r.get("region", "?"),
            r.get("feed", "?"),
            r.get("scheme", "?"),
            r.get("spec", "?"),
        )
        agg = breakdown.setdefault(
            key,
            {
                "dispatches": 0,
                "steps": 0,
                "input_wait_ms": 0.0,
                "dispatch_ms": 0.0,
                "wall_ms": 0.0,
                "device_complete_ms": 0.0,
                "device_samples": 0,
                "device_sampled_steps": 0,
                "graphs": 0.0,
            },
        )
        agg["dispatches"] += 1
        agg["steps"] += int(r.get("k", 1))
        agg["input_wait_ms"] += float(r.get("input_wait_ms", 0.0))
        agg["dispatch_ms"] += float(r.get("dispatch_ms", 0.0))
        agg["wall_ms"] += float(r.get("wall_ms", 0.0))
        if "device_complete_ms" in r:
            agg["device_complete_ms"] += float(r["device_complete_ms"])
            agg["device_samples"] += 1
            # a superstep macro's fence covers k optimizer steps —
            # per-step division must use the steps the samples cover
            agg["device_sampled_steps"] += int(r.get("k", 1))
        agg["graphs"] += float(
            r.get("graphs", r.get("graphs_plan", 0.0)) or 0.0
        )

    # Per-step loss curve (ordered) — the bit-exact reconstruction
    # hook: epoch rollup losses are the loop's History floats verbatim.
    step_losses = [
        (r.get("epoch", 0), r.get("step", 0), r["loss"])
        for r in rows
        if r.get("t") == "step"
        and r.get("region") == "train"
        and "loss" in r
    ]
    step_losses.sort(key=lambda x: (x[0], x[1]))

    mfu_rows = [r for r in rows if r.get("t") == "spec_rollup"]
    executables = [r for r in rows if r.get("t") == "executable"]
    memory = [r for r in rows if r.get("t") == "memory"]
    profile = [r for r in rows if r.get("t") == "profile"]
    compiles = [r for r in rows if r.get("t") == "compile"]
    compile_summary = next(
        (r for r in rows if r.get("t") == "compile_summary"), None
    )
    post_warmup = [r for r in compiles if r.get("retrace_leak")]
    pipeline = [r for r in rows if r.get("t") == "pipeline"]
    checkpoints = [r for r in rows if r.get("t") == "checkpoint"]
    health = [r for r in rows if r.get("t") == "health"]
    serve = [r for r in rows if r.get("t") == "serve"]
    serve_rollups = [r for r in rows if r.get("t") == "serve_rollup"]
    rollout = [r for r in rows if r.get("t") == "rollout"]
    rollout_events = [r for r in rows if r.get("t") == "rollout_event"]
    barriers = [r for r in rows if r.get("t") == "barrier"]
    heartbeats = [r for r in rows if r.get("t") == "heartbeat"]

    return {
        "path": path,
        "header": header,
        "schema": header.get("schema"),
        "skipped_lines": skipped,
        "rows": len(rows),
        "epochs": epochs,
        "train_loss_by_epoch": [r.get("train_loss") for r in epochs],
        "val_loss_by_epoch": [r.get("val_loss") for r in epochs],
        "step_losses": step_losses,
        "breakdown": {
            "|".join(k): v for k, v in sorted(breakdown.items())
        },
        "mfu": mfu_rows,
        "executables": executables,
        "memory": memory,
        "profile": profile,
        "compiles": compiles,
        "compile_summary": compile_summary,
        "post_warmup_compiles": len(post_warmup),
        "retrace_leaks": post_warmup,
        "pipeline": pipeline,
        "checkpoints": checkpoints,
        "health": health,
        "health_summary": _health_summary(health, checkpoints),
        "serve": serve,
        "serve_rollups": serve_rollups,
        "serve_summary": _serve_summary(serve, serve_rollups),
        "rollout": rollout,
        "rollout_events": rollout_events,
        "rollout_summary": _rollout_summary(rollout, rollout_events),
        "barriers": barriers,
        "heartbeats": heartbeats,
        "barrier_summary": _barrier_site_summary(barriers),
        "process_index": header.get("process_index", 0),
        "drops": (close or {}).get("dropped"),
        "write_errors": (close or {}).get("write_errors"),
        "close": close,
    }


def _barrier_site_summary(barriers: List[dict]) -> dict:
    """Per-site aggregates of this stream's ``barrier`` rows — the
    single-shard view (the cross-process decomposition lives in
    ``fleet``): crossings, total/max ``wait_ms``, max ``barrier_ms``
    (rendezvous park only)."""
    sites: Dict[str, dict] = {}
    for r in barriers:
        s = sites.setdefault(
            r.get("site", "?"),
            {
                "crossings": 0,
                "wait_ms_total": 0.0,
                "wait_ms_max": 0.0,
                "barrier_ms_max": 0.0,
            },
        )
        s["crossings"] += 1
        w = float(r.get("wait_ms", 0.0) or 0.0)
        s["wait_ms_total"] = round(s["wait_ms_total"] + w, 3)
        s["wait_ms_max"] = max(s["wait_ms_max"], w)
        s["barrier_ms_max"] = max(
            s["barrier_ms_max"], float(r.get("barrier_ms", 0.0) or 0.0)
        )
    return sites


# ----------------------------------------------------------------------
# Roofline attribution
# ----------------------------------------------------------------------


def _steady_rollups(rep: dict) -> Dict[tuple, dict]:
    """Last-epoch ``spec_rollup`` row per (region, spec) — the steady
    state the roofline verdict should describe (epoch-0 rows carry the
    compile stalls)."""
    out: Dict[tuple, dict] = {}
    for r in rep["mfu"]:
        key = (r.get("region", "?"), r.get("spec", "?"))
        prev = out.get(key)
        if prev is None or r.get("epoch", 0) >= prev.get("epoch", 0):
            out[key] = r
    return out


def build_roofline(rep: dict) -> dict:
    """Per-spec roofline attribution from the stream's OWN emitted
    fields: analytic vs counted flops, bytes, intensity, the ceiling
    ``min(peak_flops, intensity * peak_bw)``, achieved fraction of it,
    and a memory-bound/compute-bound verdict. A spec whose dispatches
    have no executable attribution (capture failed, cost_analysis
    unavailable, ``Telemetry.cost_analysis: false``) gets ``verdict:
    None`` — bound-ness is never fabricated from analytic numbers."""
    header = rep["header"]
    execs_by_key: Dict[tuple, int] = {}
    for r in rep["executables"]:
        key = (r.get("region", "?"), r.get("spec", "?"))
        execs_by_key[key] = execs_by_key.get(key, 0) + 1
    specs: List[dict] = []
    for (region, spec), row in sorted(_steady_rollups(rep).items()):
        peak = row.get("peak_flops") or header.get("peak_flops")
        basis = row.get("peak_basis") or header.get("peak_basis")
        bw = row.get("peak_hbm_bytes_per_sec") or header.get(
            "peak_hbm_bytes_per_sec"
        )
        bw_basis = row.get("peak_hbm_basis") or header.get(
            "peak_hbm_basis"
        )
        wall_s = float(row.get("wall_ms") or 0.0) / 1e3
        e = {
            "region": region,
            "spec": spec,
            "epoch": row.get("epoch"),
            "steps": row.get("steps"),
            "graphs_per_sec": row.get("graphs_per_sec"),
            "model_flops_per_graph": row.get("model_flops_per_graph"),
            "mfu": row.get("mfu"),
            "hw_mfu": row.get("hw_mfu"),
            "hw_flops": row.get("hw_flops"),
            "hw_bytes_accessed": row.get("hw_bytes_accessed"),
            "hw_over_model_flops": row.get("hw_over_model_flops"),
            "intensity": row.get("intensity"),
            "hw_missing_dispatches": row.get("hw_missing_dispatches"),
            "executables": execs_by_key.get((region, spec), 0),
            "peak_flops": peak,
            "peak_basis": basis,
            "peak_hbm_bytes_per_sec": bw,
            "peak_hbm_basis": bw_basis,
            "verdict": None,
        }
        intensity = e["intensity"]
        if intensity and peak and bw:
            ridge = peak / bw  # flops/byte where the roofs intersect
            ceiling = min(peak, intensity * bw)
            e["ridge_intensity"] = ridge
            e["roofline_ceiling_flops_per_sec"] = ceiling
            if e["hw_flops"] and wall_s > 0:
                e["ceiling_frac"] = (e["hw_flops"] / wall_s) / ceiling
            e["verdict"] = (
                "memory-bound" if intensity < ridge else "compute-bound"
            )
        specs.append(e)
    hdr_keys = (
        "log_name",
        "scheme",
        "hostname",
        "jax_version",
        "device_kind",
        "platform",
        "device_count",
        "process_count",
        "peak_flops",
        "peak_basis",
        "peak_hbm_bytes_per_sec",
        "peak_hbm_basis",
    )
    return {
        "path": rep["path"],
        "header": {
            k: header.get(k) for k in hdr_keys if header.get(k) is not None
        },
        "what_if": header.get("peak_basis") == "roofline_anchor",
        "specs": specs,
        "profile": rep["profile"],
    }


def _pct(v) -> str:
    return f"{100.0 * v:.4g}%" if v is not None else "-"


def _eng(v) -> str:
    return f"{v:.3e}" if v is not None else "-"


def render_roofline(rl: dict) -> str:
    out = [f"== graftboard roofline: {rl['path']}"]
    h = rl["header"]
    out.append(
        f"device={h.get('device_kind', '-')}  "
        f"peak_flops={_eng(h.get('peak_flops'))} "
        f"({h.get('peak_basis', '-')})  "
        f"peak_hbm={_eng(h.get('peak_hbm_bytes_per_sec'))} B/s "
        f"({h.get('peak_hbm_basis', '-')})  "
        f"devices={h.get('device_count', '-')}x{h.get('platform', '-')}"
    )
    if rl["what_if"]:
        out.append(
            "NOTE: peak basis is the anchor chip of a CPU run — "
            "utilization/ceiling columns are a WHAT-IF on that chip, "
            "not a measurement of this host."
        )
    rows = []
    for e in rl["specs"]:
        rows.append(
            [
                f"{e['region']}/{e['spec']}",
                _fmt(e.get("steps"), 0),
                _eng(e.get("model_flops_per_graph")),
                _pct(e.get("mfu")),
                _pct(e.get("hw_mfu")),
                _fmt(e.get("hw_over_model_flops"), 3),
                _fmt(e.get("intensity"), 3),
                _eng(e.get("roofline_ceiling_flops_per_sec")),
                _pct(e.get("ceiling_frac")),
                e.get("verdict") or "-",
            ]
        )
    out.append(
        _table(
            [
                "region/spec",
                "steps",
                "model F/graph",
                "mfu",
                "hw_mfu",
                "hw/model",
                "F/byte",
                "ceiling F/s",
                "%ceiling",
                "verdict",
            ],
            rows,
        )
    )
    missing = [
        e for e in rl["specs"] if e["verdict"] is None
    ]
    if missing:
        out.append(
            f"({len(missing)} spec(s) without executable attribution — "
            "no verdict; enable Telemetry.cost_analysis or see "
            "exec_capture_failures in the close row)"
        )
    if rl["profile"]:
        for r in rl["profile"]:
            out.append(
                f"-- profile {r.get('event')}: epoch={r.get('epoch', '-')} "
                f"steps={r.get('steps', '-')} "
                f"trace_dir={r.get('trace_dir', '-')} "
                f"reason={r.get('reason', '-')}"
            )
    return "\n".join(out)


# ----------------------------------------------------------------------
# Rendering
# ----------------------------------------------------------------------


def _fmt(v, nd=3) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.{nd}f}"
    return str(v)


def _table(headers: List[str], rows: List[List[str]]) -> str:
    widths = [len(h) for h in headers]
    for row in rows:
        for i, cell in enumerate(row):
            widths[i] = max(widths[i], len(cell))
    line = "  ".join(h.ljust(widths[i]) for i, h in enumerate(headers))
    out = [line, "  ".join("-" * w for w in widths)]
    for row in rows:
        out.append(
            "  ".join(cell.ljust(widths[i]) for i, cell in enumerate(row))
        )
    return "\n".join(out)


def render_report(rep: dict, csv_path: Optional[str] = None) -> str:
    out: List[str] = []
    hdr = rep["header"]
    out.append(f"== graftboard report: {rep['path']}")
    out.append(
        f"schema v{rep.get('schema')}  log={hdr.get('log_name', '-')}  "
        f"scheme={hdr.get('scheme', '-')}  rows={rep['rows']}  "
        f"skipped_lines={rep['skipped_lines']}"
    )
    if rep["drops"] is not None:
        out.append(
            f"stream accounting: dropped={rep['drops']} "
            f"write_errors={rep['write_errors']}"
        )
    if rep["epochs"]:
        out.append("")
        out.append("-- epochs")
        out.append(
            _table(
                ["epoch", "train", "val", "test", "lr", "seconds"],
                [
                    [
                        str(r.get("epoch")),
                        _fmt(r.get("train_loss"), 6),
                        _fmt(r.get("val_loss"), 6),
                        _fmt(r.get("test_loss"), 6),
                        _fmt(r.get("lr"), 6),
                        _fmt(r.get("seconds"), 2),
                    ]
                    for r in rep["epochs"]
                ],
            )
        )
    if rep["breakdown"]:
        out.append("")
        out.append(
            "-- step-time breakdown (per region|feed|scheme|spec; "
            "device-complete only on sampled fence steps)"
        )
        rows = []
        for key, agg in rep["breakdown"].items():
            wall = agg["wall_ms"] or 1.0
            dev = (
                agg["device_complete_ms"]
                / (agg.get("device_sampled_steps") or agg["device_samples"])
                if agg["device_samples"]
                else None
            )
            rows.append(
                [
                    key,
                    str(agg["steps"]),
                    str(agg["dispatches"]),
                    _fmt(agg["input_wait_ms"], 1),
                    _fmt(100.0 * agg["input_wait_ms"] / wall, 1) + "%",
                    _fmt(agg["dispatch_ms"], 1),
                    _fmt(dev, 2),
                    _fmt(agg["wall_ms"], 1),
                ]
            )
        out.append(
            _table(
                [
                    "region|feed|scheme|spec",
                    "steps",
                    "disp",
                    "wait_ms",
                    "wait%",
                    "dispatch_ms",
                    "dev_ms/step",
                    "wall_ms",
                ],
                rows,
            )
        )
    if rep["mfu"]:
        out.append("")
        out.append("-- live MFU per spec (model FLOPs x graphs/s / peak)")
        rows = []
        for r in rep["mfu"]:
            rows.append(
                [
                    f"{r.get('region')}/{r.get('epoch')}",
                    str(r.get("spec")),
                    str(r.get("steps")),
                    _fmt(r.get("graphs_per_sec"), 1),
                    _fmt(r.get("model_flops_per_graph")),
                    (
                        f"{100.0 * r['mfu']:.4g}%"
                        if r.get("mfu") is not None
                        else "-"
                    ),
                    str(r.get("peak_basis", "-")),
                ]
            )
        out.append(
            _table(
                [
                    "region/epoch",
                    "spec",
                    "steps",
                    "graphs/s",
                    "flops/graph",
                    "mfu",
                    "peak_basis",
                ],
                rows,
            )
        )
    if rep["executables"]:
        out.append("")
        out.append(
            "-- executables (XLA cost/memory accounting at first "
            "dispatch; flops/bytes are per dispatch — k steps)"
        )
        rows = []
        for r in rep["executables"]:
            rows.append(
                [
                    f"{r.get('region')}/{r.get('spec')}",
                    str(r.get("k", 1)),
                    _eng(r.get("flops")),
                    _eng(r.get("bytes_accessed")),
                    _eng(r.get("temp_bytes")),
                    _eng(r.get("argument_bytes")),
                    (
                        "ERR"
                        if r.get("capture_error")
                        else ("n/a" if r.get("cost_unavailable") else "ok")
                    ),
                ]
            )
        out.append(
            _table(
                [
                    "region/spec",
                    "k",
                    "flops",
                    "bytes",
                    "temp_B",
                    "arg_B",
                    "cost",
                ],
                rows,
            )
        )
    if rep["memory"]:
        last = rep["memory"][-1]
        peak_dev = max(
            (r.get("peak_bytes_in_use", 0) for r in rep["memory"]),
            default=0,
        )
        peak_host = max(
            (r.get("host_peak_rss_bytes", 0) for r in rep["memory"]),
            default=0,
        )
        out.append("")
        out.append(
            f"-- memory: rows={len(rep['memory'])} "
            f"peak_device_bytes={peak_dev or '-'} "
            f"peak_host_rss={peak_host or '-'} "
            f"last_tag={last.get('tag')}"
        )
    for r in rep["profile"]:
        out.append(
            f"-- profile {r.get('event')}: epoch={r.get('epoch', '-')} "
            f"steps={r.get('steps', '-')} "
            f"trace_dir={r.get('trace_dir', '-')}"
        )
    cs = rep["compile_summary"] or {}
    out.append("")
    out.append(
        f"-- compiles: total={cs.get('compile_count', len(rep['compiles']))} "
        f"({_fmt(cs.get('compile_ms'), 1)}ms)  "
        f"cache_hits={cs.get('cache_hits', '-')} "
        f"cache_misses={cs.get('cache_misses', '-')}  "
        f"POST-WARMUP={rep['post_warmup_compiles']}"
    )
    if rep["retrace_leaks"]:
        out.append("   RETRACE LEAKS (compilation after epoch 0):")
        for r in rep["retrace_leaks"]:
            out.append(
                f"     #{r.get('seq')} epoch={r.get('epoch')} "
                f"{_fmt(r.get('ms'), 1)}ms"
            )
    if rep["pipeline"]:
        last = rep["pipeline"][-1]
        out.append("")
        out.append(
            f"-- input pipeline: delivered={last.get('delivered_batches')} "
            f"starved_steps={last.get('starved_steps')} "
            f"collate_ms_avg={_fmt(last.get('collate_ms_avg'))} "
            f"h2d_ms_avg={_fmt(last.get('h2d_ms_avg'))} "
            f"queue_depth_avg={_fmt(last.get('queue_depth_avg'))}"
        )
    hs = rep.get("health_summary") or {}
    if hs.get("rows"):
        out.append("")
        out.append(
            "-- health (divergence guard): "
            f"skipped_steps={hs['skipped_total']} "
            f"rollbacks={hs['rollbacks']} halts={hs['halts']} "
            f"rejected_saves={hs['rejected_saves']}"
        )
        if hs["bad_steps"]:
            shown = [f"e{e}:s{s}" for e, s in hs["bad_steps"][:24]]
            more = len(hs["bad_steps"]) - len(shown)
            out.append(
                f"   bad optimizer steps: {shown}"
                + (f" (+{more} more)" if more > 0 else "")
            )
        if hs.get("gnorm_steps"):
            out.append(
                f"   grad-norm: min={_eng(hs['gnorm_min'])} "
                f"mean={_eng(hs['gnorm_mean'])} "
                f"max={_eng(hs['gnorm_max'])} "
                f"over {hs['gnorm_steps']} step(s)"
            )
        if hs["fault_plans"]:
            out.append(
                f"   injected fault plan(s): {hs['fault_plans']}"
            )
    ss = rep.get("serve_summary") or {}
    if ss.get("bins") or ss.get("rollup"):
        ru = ss.get("rollup") or {}
        out.append("")
        out.append(
            "-- serving (deadline-batched inference; docs/SERVING.md): "
            f"requests={ru.get('requests', '-')} "
            f"dispatches={ss.get('bins')} "
            f"shapes={ru.get('shapes', '-')} "
            f"p50={_fmt(ru.get('p50_ms'), 2)}ms "
            f"p99={_fmt(ru.get('p99_ms'), 2)}ms "
            f"graphs/s={_fmt(ru.get('graphs_per_sec'), 1)} "
            f"slot_waste={_pct(ru.get('slot_waste'))} "
            f"queue_depth_max={ss.get('queue_depth_max')}"
        )
        if ss.get("per_spec"):
            rows = []
            for spec, agg in sorted(ss["per_spec"].items()):
                g = agg["graphs"] or 1
                rows.append(
                    [
                        spec,
                        str(agg["dispatches"]),
                        str(agg["graphs"]),
                        _fmt(agg["nodes"] / g, 1),
                        _fmt(agg["edges"] / g, 1),
                        ",".join(
                            f"{k}:{v}"
                            for k, v in sorted(agg["reasons"].items())
                        ),
                    ]
                )
            out.append(
                _table(
                    [
                        "spec",
                        "disp",
                        "graphs",
                        "nodes/graph",
                        "edges/graph",
                        "dispatch reasons",
                    ],
                    rows,
                )
            )
    rls = rep.get("rollout_summary") or {}
    if rls.get("macros"):
        out.append("")
        out.append(
            "-- simulation (MD rollout; docs/SIMULATION.md): "
            f"steps={rls.get('steps')} "
            f"macros={rls.get('macros')} "
            f"k={rls.get('k', '-')} "
            f"dt={_fmt(rls.get('dt'), 6)} "
            f"rebuilds={rls.get('rebuilds')} "
            f"drift_last={_fmt(rls.get('drift_last'), 6)} "
            f"drift_max={_fmt(rls.get('drift_max'), 6)} "
            f"steps/s={_fmt(rls.get('steps_per_sec'), 1)} "
            f"ns/day={_fmt(rls.get('ns_per_day'), 4)}"
        )
        if (
            rls.get("overflow_events")
            or rls.get("nonfinite_events")
            or rls.get("actions")
        ):
            out.append(
                "   containment: "
                f"overflow_macros={rls.get('overflow_events', 0)} "
                f"nonfinite_macros={rls.get('nonfinite_events', 0)} "
                f"actions={rls.get('actions') or {}}"
            )
        if rls.get("per_spec") and len(rls["per_spec"]) > 1:
            # More than one spec means the capacity ladder re-jitted
            # mid-run — worth surfacing per spec.
            out.append(
                "   specs: "
                + ", ".join(
                    f"{k}:{v} macro(s)"
                    for k, v in sorted(rls["per_spec"].items())
                )
            )
    if rep["barrier_summary"]:
        out.append("")
        out.append(
            "-- barriers (coordination waits; wait_ms = whole "
            "crossing, barrier_ms = rendezvous park — see "
            "`fleet` for the cross-process decomposition)"
        )
        rows = [
            [
                site,
                str(s["crossings"]),
                _fmt(s["wait_ms_total"], 1),
                _fmt(s["wait_ms_max"], 1),
                _fmt(s["barrier_ms_max"], 1),
            ]
            for site, s in sorted(rep["barrier_summary"].items())
        ]
        out.append(
            _table(
                ["site", "n", "wait_ms", "max_wait", "max_barrier"],
                rows,
            )
        )
    if rep["heartbeats"]:
        hb = rep["heartbeats"]
        first, last = hb[0], hb[-1]
        out.append(
            f"-- heartbeats: {len(hb)} beat(s) over "
            f"{_fmt(float(last.get('ts', 0)) - float(first.get('ts', 0)), 1)}s"
            f"  last_phase={last.get('phase', '-')}"
            + (
                f"  waiting_on={last['waiting_on']}"
                if last.get("waiting_on")
                else ""
            )
        )
    if rep["checkpoints"]:
        saves = [
            r for r in rep["checkpoints"] if r.get("event") == "save"
        ]
        writes = [
            r for r in rep["checkpoints"] if r.get("event") == "write"
        ]
        snap = sum(float(r.get("snapshot_block_ms", 0)) for r in saves)
        wr = sum(float(r.get("serialize_write_ms", 0)) for r in writes)
        out.append(
            f"-- checkpoints: saves={len(saves)} "
            f"snapshot_block_ms_total={_fmt(snap, 2)} "
            f"serialize_write_ms_total={_fmt(wr, 2)} "
            f"failed_writes={sum(1 for r in writes if r.get('failed'))}"
        )
    if csv_path and os.path.exists(csv_path):
        out.append("")
        out.append(f"-- tracer CSV: {csv_path}")
        with open(csv_path) as f:
            for line in f.read().splitlines()[:40]:
                out.append("   " + line)
    return "\n".join(out)


# ----------------------------------------------------------------------
# Diff
# ----------------------------------------------------------------------


def build_diff(rep_a: dict, rep_b: dict) -> dict:
    def _total(rep, field):
        # TRAIN region only: eval cadence can differ between runs
        # (HYDRAGNN_TPU_VALTEST, different val sizes) — folding eval
        # wall into a "train faster" ratio is exactly the false A/B
        # signal this harness exists to prevent.
        return (
            sum(
                v[field]
                for k, v in rep["breakdown"].items()
                if k.split("|")[0] == "train"
            )
            or None
        )

    def _ratio(a, b):
        if a is None or b is None or b == 0:
            return None
        return a / b

    def _mfu_by_spec(rep):
        out = {}
        for r in rep["mfu"]:
            if r.get("region") != "train" or r.get("mfu") is None:
                continue
            # last epoch wins (steady state)
            out[r["spec"]] = r["mfu"]
        return out

    def _roofline_train(rep):
        return {
            e["spec"]: e
            for e in build_roofline(rep)["specs"]
            if e["region"] == "train"
        }

    roof_a, roof_b = _roofline_train(rep_a), _roofline_train(rep_b)

    def _delta(spec, field):
        a = roof_a.get(spec, {}).get(field)
        b = roof_b.get(spec, {}).get(field)
        return {
            "a": a,
            "b": b,
            "delta": (b - a) if a is not None and b is not None else None,
        }

    mfu_a, mfu_b = _mfu_by_spec(rep_a), _mfu_by_spec(rep_b)
    return {
        "a": rep_a["path"],
        "b": rep_b["path"],
        "train_loss_a": rep_a["train_loss_by_epoch"],
        "train_loss_b": rep_b["train_loss_by_epoch"],
        "loss_identical": (
            rep_a["train_loss_by_epoch"] == rep_b["train_loss_by_epoch"]
        ),
        "wall_ms_ratio_b_over_a": _ratio(
            _total(rep_b, "wall_ms"), _total(rep_a, "wall_ms")
        ),
        "input_wait_ratio_b_over_a": _ratio(
            _total(rep_b, "input_wait_ms"), _total(rep_a, "input_wait_ms")
        ),
        "mfu_delta_by_spec": {
            spec: {
                "a": mfu_a.get(spec),
                "b": mfu_b.get(spec),
                "delta": (
                    mfu_b[spec] - mfu_a[spec]
                    if spec in mfu_a and spec in mfu_b
                    else None
                ),
            }
            for spec in sorted(set(mfu_a) | set(mfu_b))
        },
        # Roofline movement (ISSUE 8): did the optimization change the
        # KIND of work, not just its speed? Rising intensity = fewer
        # bytes per flop (fusion working); rising ceiling fraction =
        # closer to what this intensity allows at the peak basis.
        "roofline_delta_by_spec": {
            spec: {
                "intensity": _delta(spec, "intensity"),
                "ceiling_frac": _delta(spec, "ceiling_frac"),
                "hw_mfu": _delta(spec, "hw_mfu"),
                "verdict_a": roof_a.get(spec, {}).get("verdict"),
                "verdict_b": roof_b.get(spec, {}).get("verdict"),
            }
            for spec in sorted(set(roof_a) | set(roof_b))
        },
        "post_warmup_compiles": {
            "a": rep_a["post_warmup_compiles"],
            "b": rep_b["post_warmup_compiles"],
        },
        # Coordination-wait movement (ISSUE 14): total barrier wait
        # per run — an "optimization" that moved time from steps into
        # barrier parks did not get faster, it got less observable.
        "barrier_wait_ms": {
            "a": round(
                sum(
                    s["wait_ms_total"]
                    for s in rep_a.get("barrier_summary", {}).values()
                ),
                3,
            ),
            "b": round(
                sum(
                    s["wait_ms_total"]
                    for s in rep_b.get("barrier_summary", {}).values()
                ),
                3,
            ),
        },
        "drops": {"a": rep_a["drops"], "b": rep_b["drops"]},
        # Numerical-health comparison (docs/DURABILITY.md "Divergence
        # recovery"): two runs of "the same" config whose guard
        # histories differ did NOT execute the same trajectory — a
        # skipped step, a rollback or a rejected save in exactly one
        # of them is a divergence-signature difference the wall/MFU
        # ratios above would silently absorb.
        "health": _health_diff(rep_a, rep_b),
    }


_HEALTH_DIFF_KEYS = (
    "skipped_total",
    "bad_steps",
    "rollbacks",
    "halts",
    "rejected_saves",
    "fault_plans",
)


def _health_diff(rep_a: dict, rep_b: dict) -> dict:
    a = rep_a.get("health_summary") or {}
    b = rep_b.get("health_summary") or {}
    differs = any(
        a.get(k) != b.get(k) for k in _HEALTH_DIFF_KEYS
    )
    return {
        "differs": differs,
        "a": {k: a.get(k) for k in _HEALTH_DIFF_KEYS},
        "b": {k: b.get(k) for k in _HEALTH_DIFF_KEYS},
    }


def render_diff(d: dict) -> str:
    out = [f"== graftboard diff\n   A: {d['a']}\n   B: {d['b']}"]
    out.append(
        f"loss curves identical: {d['loss_identical']}"
        + (
            ""
            if d["loss_identical"]
            else f"\n   A {d['train_loss_a']}\n   B {d['train_loss_b']}"
        )
    )
    r = d["wall_ms_ratio_b_over_a"]
    out.append(
        f"train wall (B/A): {_fmt(r, 3)}"
        + (f"  ({100 * (1 - r):+.1f}% faster B)" if r else "")
    )
    out.append(
        f"input-wait (B/A): {_fmt(d['input_wait_ratio_b_over_a'], 3)}"
    )
    if d["mfu_delta_by_spec"]:
        rows = [
            [
                spec,
                _fmt(v["a"], 5),
                _fmt(v["b"], 5),
                _fmt(v["delta"], 5),
            ]
            for spec, v in d["mfu_delta_by_spec"].items()
        ]
        out.append(_table(["spec", "mfu A", "mfu B", "delta"], rows))
    roof = {
        spec: v
        for spec, v in d.get("roofline_delta_by_spec", {}).items()
        if v["intensity"]["a"] is not None
        or v["intensity"]["b"] is not None
    }
    if roof:
        rows = [
            [
                spec,
                _fmt(v["intensity"]["a"], 3),
                _fmt(v["intensity"]["b"], 3),
                _fmt(v["intensity"]["delta"], 3),
                _pct(v["ceiling_frac"]["a"]),
                _pct(v["ceiling_frac"]["b"]),
                _fmt(v["ceiling_frac"]["delta"], 5),
                f"{v['verdict_a'] or '-'}→{v['verdict_b'] or '-'}",
            ]
            for spec, v in roof.items()
        ]
        out.append(
            _table(
                [
                    "spec",
                    "F/B A",
                    "F/B B",
                    "ΔF/B",
                    "%ceil A",
                    "%ceil B",
                    "Δceil",
                    "verdict",
                ],
                rows,
            )
        )
    pw = d["post_warmup_compiles"]
    out.append(
        f"post-warmup compiles: A={pw['a']} B={pw['b']}   "
        f"drops: A={d['drops']['a']} B={d['drops']['b']}"
    )
    bw = d.get("barrier_wait_ms") or {}
    if bw.get("a") or bw.get("b"):
        out.append(
            f"barrier wait totals: A={_fmt(bw['a'], 1)}ms "
            f"B={_fmt(bw['b'], 1)}ms"
        )
    h = d.get("health") or {}
    if h.get("differs"):
        out.append(
            "HEALTH DIVERGENCE: the runs' guard histories differ — "
            "they did not execute the same trajectory"
        )
        out.append(f"   A {h['a']}")
        out.append(f"   B {h['b']}")
    elif h:
        out.append(
            f"health: identical (skipped={h['a'].get('skipped_total')} "
            f"rollbacks={h['a'].get('rollbacks')} "
            f"rejected_saves={h['a'].get('rejected_saves')})"
        )
    return "\n".join(out)


# ----------------------------------------------------------------------
# Fleet: merged per-process shards (ISSUE 14)
# ----------------------------------------------------------------------

# Straggler thresholds (documented in docs/OBSERVABILITY.md "Straggler
# verdict"): below these floors skew is measurement noise, not a
# verdict.
_STRAGGLER_MIN_MS = 50.0
_STRAGGLER_BARRIER_FRAC = 0.05  # of the mean per-process epoch wall
_STRAGGLER_WAIT_FRAC = 0.10


def discover_shards(path: str) -> Dict[int, str]:
    """Map ``process_index -> shard path`` for one run: the base
    stream (process 0's legacy path) plus every
    ``<root>.proc<i><ext>`` sibling. Accepts a run directory, the base
    ``telemetry.jsonl`` path, or any single shard path."""
    import re

    if os.path.isdir(path):
        base = os.path.join(path, STREAM_NAME)
    else:
        base = path
    d = os.path.dirname(base) or "."
    root, ext = os.path.splitext(os.path.basename(base))
    m = re.match(r"^(.*)\.proc(\d+)$", root)
    if m:  # caller pointed at a non-0 shard: rebase on its root
        root = m.group(1)
        base = os.path.join(d, root + ext)
    shards: Dict[int, str] = {}
    if os.path.exists(base):
        shards[0] = base
    pat = re.compile(
        re.escape(root) + r"\.proc(\d+)" + re.escape(ext) + r"$"
    )
    if os.path.isdir(d):
        for f in sorted(os.listdir(d)):
            mm = pat.match(f)
            if mm:
                shards[int(mm.group(1))] = os.path.join(d, f)
    if not shards:
        raise FileNotFoundError(
            f"{path}: no telemetry shard found (expected {base} "
            f"and/or {root}.proc<i>{ext} next to it — was the run "
            "started with Training.Telemetry.enabled?)"
        )
    return dict(sorted(shards.items()))


def _zero_epoch_agg() -> dict:
    return {
        "steps": 0,
        "dispatches": 0,
        "input_wait_ms": 0.0,
        "dispatch_ms": 0.0,
        "wall_ms": 0.0,
    }


def _fleet_serving(
    rows_by_proc: Dict[int, List[dict]], heartbeats: dict
) -> Optional[dict]:
    """Merge the serving tier's per-replica shards into the fleet
    serving section (docs/SERVING.md "Fleet tier", OBSERVABILITY.md
    "Serving rows"): per-replica request/latency rollups and p99 skew,
    a queue-depth straggler verdict, shed/reroute/rollover accounting,
    and dead-replica detection cross-referenced against re-route
    coverage. None when the run has no serving rows at all (a training
    fleet renders without a serving section)."""
    per: Dict[str, dict] = {}
    sheds: Dict[str, int] = {}
    sheds_by_class: Dict[str, int] = {}
    reroutes: List[dict] = []
    rollovers = {"done": 0, "refused": 0}
    any_rows = False
    for pidx, rows in rows_by_proc.items():
        for r in rows:
            t = r.get("t")
            if t not in (
                "serve",
                "serve_rollup",
                "shed",
                "reroute",
                "rollover",
            ):
                continue
            any_rows = True
            if t == "shed":
                reason = str(r.get("reason", "?"))
                sheds[reason] = sheds.get(reason, 0) + 1
                c = str(r.get("class", "?"))
                sheds_by_class[c] = sheds_by_class.get(c, 0) + 1
                continue
            if t == "reroute":
                reroutes.append(
                    {
                        "from_replica": r.get("from_replica"),
                        "recovered": r.get("recovered"),
                        "moved": r.get("moved"),
                        "shed_expired": r.get("shed_expired"),
                    }
                )
                continue
            if t == "rollover":
                phase = str(r.get("phase", "?"))
                if phase in rollovers:
                    rollovers[phase] += 1
                continue
            # serve / serve_rollup: replica tag wins, shard index is
            # the fallback (single-stream runs have no tag).
            rep = str(r.get("replica", pidx))
            e = per.setdefault(
                rep,
                {
                    "serve_rows": 0,
                    "requests": 0,
                    "dispatches": 0,
                    "queue_depth_max": 0,
                    "p50_ms": None,
                    "p99_ms": None,
                },
            )
            if t == "serve":
                e["serve_rows"] += 1
                e["queue_depth_max"] = max(
                    e["queue_depth_max"],
                    int(r.get("queue_depth", 0) or 0),
                )
            else:
                # Last rollup wins: it aggregates the whole run.
                e["requests"] = int(r.get("requests", 0) or 0)
                e["dispatches"] = int(r.get("dispatches", 0) or 0)
                e["p50_ms"] = r.get("p50_ms")
                e["p99_ms"] = r.get("p99_ms")
    if not any_rows:
        return None
    p99s = {
        k: v["p99_ms"] for k, v in per.items() if v["p99_ms"]
    }
    p99_skew = (
        round(max(p99s.values()) / max(min(p99s.values()), 1e-9), 3)
        if len(p99s) >= 2
        else None
    )
    # Queue-depth straggler: a replica whose max queue depth is at
    # least double the fleet median is falling behind its peers —
    # routing skew or a slow replica, either way the p99 donor.
    depths = sorted(v["queue_depth_max"] for v in per.values())
    verdict = "balanced"
    if len(depths) >= 2:
        med = depths[len(depths) // 2]
        worst = max(
            per.items(), key=lambda kv: kv[1]["queue_depth_max"]
        )
        if worst[1]["queue_depth_max"] >= max(2 * med, med + 4):
            verdict = (
                f"replica {worst[0]} queue-depth straggler "
                f"(max depth {worst[1]['queue_depth_max']} vs "
                f"median {med})"
            )
    # Dead replicas (no close row + heartbeat gap) vs re-route
    # coverage: a dead replica with no reroute row means its pending
    # requests were LOST — the exact silent drop the tier exists to
    # prevent.
    dead = list(heartbeats.get("dead") or [])
    covered = {
        int(rr["from_replica"])
        for rr in reroutes
        if rr.get("from_replica") is not None
    }
    uncovered = sorted(set(int(d) for d in dead) - covered)
    return {
        "per_replica": per,
        "p99_skew": p99_skew,
        "queue_verdict": verdict,
        "sheds_by_reason": sheds,
        "sheds_by_class": sheds_by_class,
        "shed_total": sum(sheds.values()),
        "reroutes": reroutes,
        "rollovers": rollovers,
        "dead_replicas": dead,
        "dead_without_reroute": uncovered,
    }


def build_fleet(path: str) -> dict:
    """Merge one run's shards into the fleet report dict
    ``render_fleet`` prints (stable keys — ``--json`` is the CI
    surface). Degrades LOUDLY on partial fleets: every anomaly lands
    in ``warnings`` (and the dead-process list), never an exception —
    a killed run's fleet must still render, that is the point."""
    shards = discover_shards(path)
    warnings: List[str] = []
    procs: Dict[str, dict] = {}
    rows_by_proc: Dict[int, List[dict]] = {}
    expected = 0
    for pidx, spath in shards.items():
        rows, skipped = read_stream(spath)
        rep = _report_from_rows(spath, rows, skipped)
        rows_by_proc[pidx] = rows
        hdr = rep["header"]
        hdr_idx = hdr.get("process_index")
        if hdr_idx is not None and int(hdr_idx) != pidx:
            warnings.append(
                f"shard {os.path.basename(spath)} claims "
                f"process_index {hdr_idx} but is named proc{pidx} — "
                "trusting the filename"
            )
        expected = max(expected, int(hdr.get("process_count", 0) or 0))
        if skipped:
            warnings.append(
                f"proc{pidx}: {skipped} unparseable line(s) skipped "
                "(truncated tail — the shard was cut mid-write)"
            )
        clean = rep["close"] is not None
        if not clean:
            warnings.append(
                f"proc{pidx}: shard has no close row — the process "
                "died or was killed mid-run (see the heartbeat section)"
            )
        procs[str(pidx)] = {
            "path": spath,
            "rows": rep["rows"],
            "skipped_lines": skipped,
            "drops": rep["drops"],
            "write_errors": rep["write_errors"],
            "clean_exit": clean,
            "hostname": hdr.get("hostname"),
            "epochs": len(rep["epochs"]),
            "post_warmup_compiles": rep["post_warmup_compiles"],
            "barrier_summary": rep["barrier_summary"],
        }
    present = sorted(rows_by_proc)
    expected = max(expected, len(present), (present[-1] + 1) if present else 0)
    missing = sorted(set(range(expected)) - set(present))
    if missing:
        warnings.append(
            f"missing shard(s) for process(es) {missing} of "
            f"{expected} — merged views cover only the present "
            "shards; skew/straggler numbers are LOWER BOUNDS"
        )

    barrier_events = _merge_barriers(rows_by_proc)
    barrier_sites = _rollup_barrier_sites(barrier_events)
    epoch_align = _align_epochs(rows_by_proc)
    stragglers = _straggler_verdicts(epoch_align, barrier_events)
    heartbeats = _heartbeat_health(rows_by_proc, procs, warnings)
    serving = _fleet_serving(rows_by_proc, heartbeats)
    if serving and serving["dead_without_reroute"]:
        warnings.append(
            "dead serving replica(s) "
            f"{serving['dead_without_reroute']} have NO reroute row — "
            "their pending requests were lost, not recovered"
        )

    return {
        "path": path,
        "shards": {str(i): p for i, p in shards.items()},
        "process_count": expected,
        "present": present,
        "missing": missing,
        "warnings": warnings,
        "processes": procs,
        "barrier_events": barrier_events,
        "barrier_sites": barrier_sites,
        "epoch_align": epoch_align,
        "stragglers": stragglers,
        "heartbeats": heartbeats,
        "serving": serving,
    }


def _merge_barriers(rows_by_proc: Dict[int, List[dict]]) -> List[dict]:
    """Align ``barrier`` rows across shards by (site, seq) — the seq
    is minted identically on every process (utils/checkpoint
    ``_barrier_seq`` / the writer's per-job sequence), so the pair IS
    the event identity. The LAST ARRIVER of an event is the process
    with minimum ``barrier_ms`` (it barely parks — everyone else was
    already waiting): a clock-skew-free signal, unlike comparing
    ``ts`` across hosts. ``peer_wait_ms`` is the longest wait the last
    arriver inflicted on a peer — the number the straggler verdict
    charges to it."""
    events: Dict[Tuple[str, int], dict] = {}
    for pidx, rows in rows_by_proc.items():
        for r in rows:
            if r.get("t") != "barrier":
                continue
            key = (str(r.get("site", "?")), int(r.get("seq", 0)))
            ev = events.setdefault(
                key,
                {
                    "site": key[0],
                    "seq": key[1],
                    "epoch": r.get("epoch"),
                    "broadcast": False,
                    "wait_ms": {},
                    "barrier_ms": {},
                },
            )
            if r.get("epoch") is not None and ev.get("epoch") is None:
                ev["epoch"] = r.get("epoch")
            if r.get("broadcast"):
                ev["broadcast"] = True
            ev["wait_ms"][str(pidx)] = float(r.get("wait_ms", 0.0) or 0.0)
            if "barrier_ms" in r:
                ev["barrier_ms"][str(pidx)] = float(r["barrier_ms"])
    out = []
    for (site, seq), ev in sorted(events.items()):
        waits = ev["wait_ms"]
        ev["max_wait_ms"] = max(waits.values()) if waits else 0.0
        ev["max_wait_proc"] = (
            int(max(waits, key=waits.get)) if waits else None
        )
        # Rendezvous events only: a broadcast (KV set/get) is
        # asymmetric — only processes arriving before the setter
        # park, late arrivers read instantly — so min-barrier_ms
        # "last arriver" would blame an innocent late reader. Its
        # waits are still reported per process, unattributed. And
        # NEVER fall back to min-wait_ms: wait_ms includes the
        # straggler's own pre-barrier stall, so it would invert the
        # attribution — rows without barrier_ms stay unattributed.
        src = None if ev["broadcast"] else (
            ev["barrier_ms"] if len(ev["barrier_ms"]) >= 2 else None
        )
        if src is not None:
            last = min(src, key=src.get)
            ev["last_arriver"] = int(last)
            ev["peer_wait_ms"] = max(
                (v for p, v in src.items() if p != last), default=0.0
            )
        else:
            ev["last_arriver"] = None
            ev["peer_wait_ms"] = 0.0
        out.append(ev)
    return out


def _rollup_barrier_sites(events: List[dict]) -> Dict[str, dict]:
    sites: Dict[str, dict] = {}
    for ev in events:
        s = sites.setdefault(
            ev["site"],
            {
                "events": 0,
                "wait_ms_total_by_proc": {},
                "max_wait_ms": 0.0,
                "peer_wait_ms_total": 0.0,
                "last_arrivals": {},
                "worst": None,
            },
        )
        s["events"] += 1
        for p, v in ev["wait_ms"].items():
            s["wait_ms_total_by_proc"][p] = round(
                s["wait_ms_total_by_proc"].get(p, 0.0) + v, 3
            )
        la = ev["last_arriver"]
        if la is not None:
            s["last_arrivals"][str(la)] = (
                s["last_arrivals"].get(str(la), 0) + 1
            )
            s["peer_wait_ms_total"] = round(
                s["peer_wait_ms_total"] + ev["peer_wait_ms"], 3
            )
        if ev["max_wait_ms"] >= s["max_wait_ms"]:
            s["max_wait_ms"] = ev["max_wait_ms"]
            s["worst"] = {
                "seq": ev["seq"],
                "epoch": ev.get("epoch"),
                "max_wait_ms": ev["max_wait_ms"],
                "max_wait_proc": ev["max_wait_proc"],
                "last_arriver": la,
                "peer_wait_ms": ev["peer_wait_ms"],
            }
    return sites


def _align_epochs(rows_by_proc: Dict[int, List[dict]]) -> List[dict]:
    """Per-(region, epoch) alignment of step rows across processes:
    each process's input-wait / dispatch / wall totals side by side,
    plus the skews (max − min) — the per-host load-imbalance view the
    process-coordinated packing work will be judged with."""
    agg: Dict[Tuple[str, int], Dict[str, dict]] = {}
    for pidx, rows in rows_by_proc.items():
        for r in rows:
            if r.get("t") != "step":
                continue
            key = (str(r.get("region", "?")), int(r.get("epoch", 0)))
            a = agg.setdefault(key, {}).setdefault(
                str(pidx), _zero_epoch_agg()
            )
            a["steps"] += int(r.get("k", 1))
            a["dispatches"] += 1
            a["input_wait_ms"] = round(
                a["input_wait_ms"] + float(r.get("input_wait_ms", 0.0)), 3
            )
            a["dispatch_ms"] = round(
                a["dispatch_ms"] + float(r.get("dispatch_ms", 0.0)), 3
            )
            a["wall_ms"] = round(
                a["wall_ms"] + float(r.get("wall_ms", 0.0)), 3
            )
    out = []
    for (region, epoch), per in sorted(agg.items()):
        walls = {p: v["wall_ms"] for p, v in per.items()}
        inwait = {p: v["input_wait_ms"] for p, v in per.items()}
        entry = {
            "region": region,
            "epoch": epoch,
            "per_process": per,
            "wall_skew_ms": (
                round(max(walls.values()) - min(walls.values()), 3)
                if len(walls) >= 2
                else 0.0
            ),
            "input_wait_skew_ms": (
                round(max(inwait.values()) - min(inwait.values()), 3)
                if len(inwait) >= 2
                else 0.0
            ),
            "slowest": int(max(walls, key=walls.get)) if walls else None,
            "most_input_wait": (
                int(max(inwait, key=inwait.get)) if inwait else None
            ),
        }
        out.append(entry)
    return out


def _straggler_verdicts(
    epoch_align: List[dict], barrier_events: List[dict]
) -> List[dict]:
    """One verdict per TRAIN epoch (docs/OBSERVABILITY.md "Straggler
    verdict"): barrier attribution wins (the peer wait charged to an
    epoch's last arrivers — a stalled process slows the fleet without
    slowing itself, so its own step rows look innocent); otherwise
    input-wait skew (the slow-host case); otherwise ``balanced``.
    Thresholds: ``max(50ms, 5% of mean per-process wall)`` for
    barrier peer wait, ``max(50ms, 10%)`` for input-wait skew."""
    peer_by_epoch: Dict[int, Dict[int, float]] = {}
    site_by_epoch: Dict[Tuple[int, int], Dict[str, float]] = {}
    for ev in barrier_events:
        la, ep = ev["last_arriver"], ev.get("epoch")
        if la is None or ep is None or not ev["peer_wait_ms"]:
            continue
        ep = int(ep)
        peer_by_epoch.setdefault(ep, {})
        peer_by_epoch[ep][la] = (
            peer_by_epoch[ep].get(la, 0.0) + ev["peer_wait_ms"]
        )
        sb = site_by_epoch.setdefault((ep, la), {})
        sb[ev["site"]] = sb.get(ev["site"], 0.0) + ev["peer_wait_ms"]
    verdicts = []
    for entry in epoch_align:
        if entry["region"] != "train":
            continue
        epoch = entry["epoch"]
        per = entry["per_process"]
        walls = [v["wall_ms"] for v in per.values()]
        mean_wall = (sum(walls) / len(walls)) if walls else 0.0
        v = {
            "epoch": epoch,
            "straggler": None,
            "cause": None,
            "peer_wait_ms": 0.0,
            "wall_skew_ms": entry["wall_skew_ms"],
            "input_wait_skew_ms": entry["input_wait_skew_ms"],
        }
        peers = peer_by_epoch.get(epoch) or {}
        if peers:
            worst = max(peers, key=peers.get)
            if peers[worst] >= max(
                _STRAGGLER_MIN_MS, _STRAGGLER_BARRIER_FRAC * mean_wall
            ):
                sb = site_by_epoch.get((epoch, worst)) or {}
                site = max(sb, key=sb.get) if sb else "?"
                v.update(
                    straggler=int(worst),
                    cause=f"barrier:{site}",
                    peer_wait_ms=round(peers[worst], 3),
                )
        if v["straggler"] is None and len(per) >= 2:
            if entry["input_wait_skew_ms"] >= max(
                _STRAGGLER_MIN_MS, _STRAGGLER_WAIT_FRAC * mean_wall
            ):
                v.update(
                    straggler=entry["most_input_wait"],
                    cause="input_wait",
                )
        if v["straggler"] is None:
            v["cause"] = "balanced"
        verdicts.append(v)
    return verdicts


def _heartbeat_health(
    rows_by_proc: Dict[int, List[dict]],
    procs: Dict[str, dict],
    warnings: List[str],
) -> dict:
    """Dead/stalled-process detection from heartbeat gaps: the fleet's
    last beat is the reference clock; a process with no close row
    whose last beat trails it by more than ``max(3 × interval, 1s)``
    was SIGKILLed or wedged — exactly what a ``stall:``-class hang
    looks like from outside. A clean close row downgrades an old last
    beat to "exited" (finished earlier, not dead)."""
    per: Dict[str, dict] = {}
    fleet_last = None
    for pidx, rows in rows_by_proc.items():
        hb = [r for r in rows if r.get("t") == "heartbeat"]
        if not hb:
            continue
        ts = [float(r.get("ts", 0.0) or 0.0) for r in hb]
        gaps = [b - a for a, b in zip(ts, ts[1:])]
        last = hb[-1]
        per[str(pidx)] = {
            "beats": len(hb),
            "first_ts": ts[0],
            "last_ts": ts[-1],
            "interval_s": float(last.get("interval_s", 0.0) or 0.0),
            "max_gap_s": round(max(gaps), 3) if gaps else 0.0,
            "last_phase": last.get("phase"),
            "last_waiting_on": last.get("waiting_on"),
            "last_counters": last.get("counters"),
        }
        fleet_last = (
            ts[-1] if fleet_last is None else max(fleet_last, ts[-1])
        )
    silent = [
        p for p in rows_by_proc if str(p) not in per
    ]
    if per and silent:
        warnings.append(
            f"process(es) {sorted(silent)} emitted no heartbeat rows "
            "while peers did — dead before the first beat, or "
            "heartbeats disabled on that process"
        )
    dead = []
    for p, e in sorted(per.items()):
        gap = round((fleet_last or 0.0) - e["last_ts"], 3)
        e["gap_s"] = gap
        thresh = max(3.0 * (e["interval_s"] or 0.0), 1.0)
        clean = procs.get(p, {}).get("clean_exit", False)
        e["exited"] = bool(clean)
        e["dead"] = bool(not clean and gap > thresh)
        if e["dead"]:
            dead.append(int(p))
            warnings.append(
                f"proc{p}: DEAD/STALLED — last heartbeat {gap:.1f}s "
                f"behind the fleet (threshold {thresh:.1f}s), no close "
                f"row; last phase={e['last_phase']!r}"
                + (
                    f", waiting_on={e['last_waiting_on']!r}"
                    if e["last_waiting_on"]
                    else ""
                )
            )
    return {
        "per_process": per,
        "fleet_last_ts": fleet_last,
        "dead": dead,
    }


def render_fleet(fl: dict) -> str:
    out = [f"== graftboard fleet: {fl['path']}"]
    out.append(
        f"processes: {fl['process_count']} expected, "
        f"{len(fl['present'])} shard(s) present "
        f"{fl['present']}"
        + (f", MISSING {fl['missing']}" if fl["missing"] else "")
    )
    for w in fl["warnings"]:
        out.append(f"WARNING: {w}")
    if fl["processes"]:
        rows = []
        for p, e in sorted(fl["processes"].items(), key=lambda kv: int(kv[0])):
            rows.append(
                [
                    f"proc{p}",
                    str(e["rows"]),
                    str(e["epochs"]),
                    _fmt(e["drops"], 0),
                    str(e["skipped_lines"]),
                    "yes" if e["clean_exit"] else "NO",
                    str(e["post_warmup_compiles"]),
                ]
            )
        out.append("")
        out.append(
            _table(
                ["proc", "rows", "epochs", "drops", "skipped",
                 "clean_exit", "retraces"],
                rows,
            )
        )
    if fl["epoch_align"]:
        out.append("")
        out.append(
            "-- per-epoch step-time skew (per process: "
            "input_wait/wall ms)"
        )
        rows = []
        for e in fl["epoch_align"]:
            per = ", ".join(
                f"p{p}:{_fmt(v['input_wait_ms'], 0)}/{_fmt(v['wall_ms'], 0)}"
                for p, v in sorted(
                    e["per_process"].items(), key=lambda kv: int(kv[0])
                )
            )
            rows.append(
                [
                    f"{e['region']}/{e['epoch']}",
                    per,
                    _fmt(e["input_wait_skew_ms"], 1),
                    _fmt(e["wall_skew_ms"], 1),
                    (
                        f"p{e['slowest']}"
                        if e["slowest"] is not None
                        else "-"
                    ),
                ]
            )
        out.append(
            _table(
                ["region/epoch", "per-proc wait/wall", "wait_skew",
                 "wall_skew", "slowest"],
                rows,
            )
        )
    if fl["barrier_sites"]:
        out.append("")
        out.append(
            "-- barrier decomposition (last arriver = min barrier_ms "
            "— the process its peers waited on)"
        )
        rows = []
        for site, s in sorted(fl["barrier_sites"].items()):
            worst = s["worst"] or {}
            arrivals = ",".join(
                f"p{p}:{n}"
                for p, n in sorted(s["last_arrivals"].items())
            )
            rows.append(
                [
                    site,
                    str(s["events"]),
                    _fmt(s["max_wait_ms"], 1),
                    _fmt(s["peer_wait_ms_total"], 1),
                    arrivals or "-",
                    (
                        f"seq{worst.get('seq')}→p"
                        f"{worst.get('last_arriver')}"
                        if worst.get("last_arriver") is not None
                        else "-"
                    ),
                ]
            )
        out.append(
            _table(
                ["site", "n", "max_wait_ms", "peer_wait_ms",
                 "last_arrivals", "worst"],
                rows,
            )
        )
    if fl["stragglers"]:
        out.append("")
        out.append("-- straggler verdict per epoch")
        for v in fl["stragglers"]:
            if v["straggler"] is None:
                out.append(f"   epoch {v['epoch']}: balanced")
            else:
                out.append(
                    f"   epoch {v['epoch']}: STRAGGLER proc"
                    f"{v['straggler']} ({v['cause']}"
                    + (
                        f", peers waited {_fmt(v['peer_wait_ms'], 0)}ms"
                        if v["peer_wait_ms"]
                        else ""
                    )
                    + ")"
                )
    hb = fl["heartbeats"]
    if hb["per_process"]:
        out.append("")
        out.append("-- heartbeats (liveness)")
        rows = []
        for p, e in sorted(
            hb["per_process"].items(), key=lambda kv: int(kv[0])
        ):
            status = (
                "DEAD"
                if e["dead"]
                else ("exited" if e["exited"] else "alive-at-end")
            )
            rows.append(
                [
                    f"proc{p}",
                    str(e["beats"]),
                    _fmt(e["gap_s"], 1),
                    _fmt(e["max_gap_s"], 1),
                    str(e["last_phase"] or "-"),
                    str(e["last_waiting_on"] or "-"),
                    status,
                ]
            )
        out.append(
            _table(
                ["proc", "beats", "tail_gap_s", "max_gap_s",
                 "last_phase", "waiting_on", "status"],
                rows,
            )
        )
        if hb["dead"]:
            out.append(
                f"   DEAD PROCESS(ES): {hb['dead']} — heartbeat gap "
                "with no close row (SIGKILL or hard stall)"
            )
    sv = fl.get("serving")
    if sv:
        out.append("")
        out.append("-- serving tier (per-replica)")
        rows = []
        for rep, e in sorted(
            sv["per_replica"].items(), key=lambda kv: str(kv[0])
        ):
            rows.append(
                [
                    f"r{rep}",
                    str(e["requests"]),
                    str(e["dispatches"]),
                    _fmt(e["p50_ms"], 2),
                    _fmt(e["p99_ms"], 2),
                    str(e["queue_depth_max"]),
                ]
            )
        out.append(
            _table(
                ["replica", "requests", "dispatches", "p50_ms",
                 "p99_ms", "queue_max"],
                rows,
            )
        )
        if sv["p99_skew"] is not None:
            out.append(
                f"   p99 skew (max/min across replicas): "
                f"{sv['p99_skew']}x"
            )
        out.append(f"   queue verdict: {sv['queue_verdict']}")
        if sv["shed_total"]:
            out.append(
                f"   sheds: {sv['shed_total']} "
                f"(by reason {sv['sheds_by_reason']}, "
                f"by class {sv['sheds_by_class']})"
            )
        else:
            out.append("   sheds: 0")
        for rr in sv["reroutes"]:
            out.append(
                f"   reroute from replica {rr['from_replica']}: "
                f"{rr['recovered']} recovered, {rr['moved']} moved, "
                f"{rr['shed_expired']} shed expired"
            )
        ro = sv["rollovers"]
        if ro["done"] or ro["refused"]:
            out.append(
                f"   rollovers: {ro['done']} completed, "
                f"{ro['refused']} refused at admission"
            )
        if sv["dead_replicas"]:
            cov = (
                "re-route covered"
                if not sv["dead_without_reroute"]
                else "REQUESTS LOST: no reroute row for "
                f"{sv['dead_without_reroute']}"
            )
            out.append(
                f"   dead replica(s) {sv['dead_replicas']} — {cov}"
            )
    return "\n".join(out)


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(prog="graftboard", description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)
    pr = sub.add_parser("report", help="render one run's report")
    pr.add_argument("run", help="telemetry.jsonl or run directory")
    pr.add_argument("--json", action="store_true", dest="as_json")
    pr.add_argument("--csv", default=None, help="tracer timing CSV to append")
    pf = sub.add_parser(
        "roofline", help="per-spec cost/memory roofline attribution"
    )
    pf.add_argument("run", help="telemetry.jsonl or run directory")
    pf.add_argument("--json", action="store_true", dest="as_json")
    pd = sub.add_parser("diff", help="A/B two runs")
    pd.add_argument("run_a")
    pd.add_argument("run_b")
    pd.add_argument("--json", action="store_true", dest="as_json")
    pfl = sub.add_parser(
        "fleet",
        help="merge one run's per-process shards: skew, barrier "
        "attribution, stragglers, dead processes",
    )
    pfl.add_argument("run", help="run directory or any shard path")
    pfl.add_argument("--json", action="store_true", dest="as_json")
    args = p.parse_args(argv)

    try:
        if args.cmd == "report":
            rep = build_report(args.run)
            if args.as_json:
                print(json.dumps(rep))
            else:
                print(render_report(rep, csv_path=args.csv))
        elif args.cmd == "roofline":
            rl = build_roofline(build_report(args.run))
            if args.as_json:
                print(json.dumps(rl))
            else:
                print(render_roofline(rl))
        elif args.cmd == "fleet":
            fl = build_fleet(args.run)
            if args.as_json:
                print(json.dumps(fl))
            else:
                print(render_fleet(fl))
        else:
            d = build_diff(
                build_report(args.run_a), build_report(args.run_b)
            )
            if args.as_json:
                print(json.dumps(d))
            else:
                print(render_diff(d))
    except FileNotFoundError as e:
        print(f"graftboard: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
