"""Run telemetry: structured JSONL step streams, the step clock, live
MFU accounting, and the compile/retrace observer (docs/OBSERVABILITY.md).

The framework's training claims — "the loop never blocks on a per-batch
sync", "one compiled shape per budget", "8.35% MFU" — were only ever
checkable offline (a benchmark's report, end-of-run tracer CSVs). This module
makes them continuously observable DURING a run, under one discipline
inherited from the checkpoint writer (utils/checkpoint.CheckpointWriter,
docs/DURABILITY.md): telemetry must never block or perturb a training
step.

- ``TelemetryStream`` — a bounded, non-blocking background JSONL
  writer: callers enqueue plain dicts (``put_nowait``), a daemon worker
  serializes and appends them; a full queue DROPS the row and counts it
  (``dropped``) instead of stalling the caller, and I/O failures are
  absorbed onto ``write_errors``/``last_error`` — the stream can die,
  training cannot. Rows are whole lines, so a kill mid-write leaves at
  most one truncated tail line (tools/graftboard.py skips it on read).

- ``StepClock`` — the per-epoch step clock ``train/loop._run_epoch``
  drives: wall time decomposes into input-wait (the ``next(it)`` fetch),
  host-dispatch (the step call returning, async), and device-complete —
  the last measured only by SAMPLED sync fences (every
  ``sync_interval_steps`` steps, config-gated; the default interval 0
  adds ZERO host syncs, so the loop's one-fetch-per-epoch contract and
  graftlint's host-sync rule stay intact). Superstep macros attribute K
  steps to one dispatch; dp feeds attribute D device lanes per step.
  Per-step losses and real-graph counts are DEFERRED device refs,
  resolved in one batched fetch at epoch end — after the loop's own
  single metrics fetch, never between steps. Real delivered sizes come
  from the loader's plan arithmetic (``epoch_size_rows`` — host
  metadata, no device work).

- Live MFU: per-spec achieved FLOP/s from the analytic model-flop
  inventories of utils/flops.py, over the plan-domain real sizes,
  divided by ``flops.resolve_peak_flops`` (the running chip, or on a
  CPU run the anchor chip ``flops.ANCHOR_DEVICE_KIND`` — flagged by
  ``peak_basis``).

- ``CompileObserver`` — registers ``jax.monitoring`` listeners to count
  XLA compilations + compile milliseconds, surface persistent-cache
  hits/misses, and flag any compilation at epoch >= 1 as a RETRACE
  LEAK (the runtime complement to graftlint's static ``retrace`` rule).
  The jax listeners are module-level dispatchers registered once per
  process and never torn down (jax.monitoring has no public
  unregister); ``install``/``close`` swap the active observer behind
  them, so registration is idempotent and a closed observer receives
  nothing — no cross-test leakage.

- Roofline attribution (docs/OBSERVABILITY.md "Roofline"): at the
  FIRST dispatch of each compiled train/eval executable the clock
  captures XLA's own accounting — ``compiled.cost_analysis()``
  (counted hardware flops, HBM bytes accessed) and
  ``compiled.memory_analysis()`` (argument/output/temp footprint) —
  via an AOT ``fn.lower(args).compile()`` of the SAME jitted step,
  keyed by (region, spec, k, lanes) and emitted as ``executable``
  rows. One capture per executable, at warmup, off by
  ``Telemetry.cost_analysis: false``; steady-state steps pay one dict
  lookup. ``spec_rollup`` rows then carry hw-MFU next to the analytic
  MFU (their quotient is the padding/recompute waste number) and the
  arithmetic intensity the roofline verdict needs — all derived from
  the rows' own emitted fields, and OMITTED (plus counted) whenever
  ``cost_analysis`` is unavailable: never a fabricated estimate.

- ``memory`` rows: live allocator telemetry (``Device.memory_stats``
  via the hardened ``utils/runtime.memory_stats``) + host RSS, at
  epoch boundaries and after each XLA compile — a graceful partial
  row on backends without allocator stats (CPU keeps host RSS).

- Fleet shards (ISSUE 14, docs/OBSERVABILITY.md "Fleet
  observability"): in a multi-process run EVERY process streams —
  process 0 keeps the legacy path, process ``i`` opens
  ``<stream>.proc<i>.jsonl`` (``shard_path``). Rows are tagged with
  ``process_index`` on the WORKER thread (the step path never pays the
  copy), headers carry the process identity, and the
  never-block/drop-with-counter discipline is unchanged.
  ``emit_barrier`` records coordination waits (the checkpoint
  barriers, the validate-finite agreement, the walltime broadcast) as
  versioned ``barrier`` rows; a ``heartbeat`` thread per stream emits
  a periodic liveness row carrying the run ``phase`` (``note_phase``),
  the current blocking wait site (``waiting_on``) and the feed
  counters (``bump``) — ``graftboard fleet`` merges the shards,
  decomposes per-site barrier wait, names last arrivers/stragglers
  and detects dead processes from heartbeat gaps.

Config: ``Training.Telemetry {enabled, stream_path,
sync_interval_steps, rollup, queue_depth, cost_analysis,
heartbeat_interval_s}`` with ``HYDRAGNN_TPU_TELEMETRY`` /
``HYDRAGNN_TPU_TELEMETRY_STREAM`` / ``HYDRAGNN_TPU_TELEMETRY_SYNC``
env overrides.
"""

from __future__ import annotations

import contextlib
import json
import os
import queue
import sys
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from hydragnn_tpu.utils import faults
from hydragnn_tpu.utils import tracer as tr

SCHEMA_VERSION = 1

__all__ = [
    "SCHEMA_VERSION",
    "TelemetrySettings",
    "telemetry_settings",
    "TelemetryStream",
    "StepClock",
    "CompileObserver",
    "configure",
    "install",
    "get",
    "active",
    "emit",
    "memory_row",
    "emit_memory",
    "set_context",
    "get_context",
    "process_identity",
    "shard_path",
    "note_phase",
    "get_phase",
    "waiting_on",
    "bump",
    "counters",
    "heartbeat_row",
    "emit_barrier",
    "suppress_compile_events",
    "note_epoch",
    "end_of_training",
    "epoch_clock",
    "install_observer",
    "observer",
    "close_run",
    "setup_row",
    "setup_phase",
    "SetupClock",
]


# ----------------------------------------------------------------------
# Config
# ----------------------------------------------------------------------


@dataclass(frozen=True)
class TelemetrySettings:
    enabled: bool = False
    stream_path: Optional[str] = None  # default logs/<log_name>/telemetry.jsonl
    sync_interval_steps: int = 0  # 0 = never fence (zero added syncs)
    rollup: bool = True  # per-epoch rollup + mfu rows
    queue_depth: int = 16384
    cost_analysis: bool = True  # first-dispatch executable rows
    heartbeat_interval_s: float = 10.0  # 0 = no heartbeat thread


def telemetry_settings(training: dict) -> TelemetrySettings:
    """Resolve the ``Training.Telemetry`` block (+ env overrides) into
    settings. ``Telemetry: true`` is shorthand for ``{"enabled": true}``;
    unknown keys are rejected eagerly by config.update_config (a
    misspelled ``sync_interval_steps`` silently measuring nothing is
    exactly the failure class this subsystem exists to end)."""
    raw = training.get("Telemetry") or {}
    if isinstance(raw, bool):
        raw = {"enabled": raw}
    elif not isinstance(raw, dict):
        raise ValueError(
            "Training.Telemetry must be a bool or an object "
            '{"enabled", "stream_path", "sync_interval_steps", '
            '"rollup", "queue_depth", "cost_analysis", '
            '"heartbeat_interval_s"}'
        )
    enabled = bool(raw.get("enabled", False))
    env = os.environ.get("HYDRAGNN_TPU_TELEMETRY")
    if env is not None:
        enabled = env.strip().lower() not in ("", "0", "false", "no")
    path = os.environ.get("HYDRAGNN_TPU_TELEMETRY_STREAM") or raw.get(
        "stream_path"
    )
    sync_env = os.environ.get("HYDRAGNN_TPU_TELEMETRY_SYNC", "").strip()
    sync = (
        int(sync_env)
        if sync_env
        else int(raw.get("sync_interval_steps", 0))
    )
    return TelemetrySettings(
        enabled=enabled,
        stream_path=path,
        sync_interval_steps=max(0, sync),
        rollup=bool(raw.get("rollup", True)),
        queue_depth=max(64, int(raw.get("queue_depth", 16384))),
        cost_analysis=bool(raw.get("cost_analysis", True)),
        heartbeat_interval_s=max(
            0.0, float(raw.get("heartbeat_interval_s", 10.0))
        ),
    )


def process_identity() -> Tuple[int, int]:
    """``(process_index, process_count)`` for shard naming and row
    tagging. The launcher env (``HYDRAGNN_TPU_PROCESS_ID`` /
    ``HYDRAGNN_TPU_NUM_PROCESSES``) wins — it is readable before any
    jax import, and it is what the multi-process drills arm their
    children with; otherwise an ALREADY-initialized jax backend
    answers (constructing a stream must never initialize one);
    otherwise ``(0, 1)``."""
    idx = cnt = None
    e_idx = os.environ.get("HYDRAGNN_TPU_PROCESS_ID", "").strip()
    e_cnt = os.environ.get("HYDRAGNN_TPU_NUM_PROCESSES", "").strip()
    if e_idx.isdigit():
        idx = int(e_idx)
    if e_cnt.isdigit():
        cnt = int(e_cnt)
    if (idx is None or cnt is None) and _jax_backend_initialized():
        try:
            import jax

            if idx is None:
                idx = int(jax.process_index())
            if cnt is None:
                cnt = int(jax.process_count())
        except Exception:
            pass
    return (idx or 0, cnt or 1)


def shard_path(base: str, process_index: int) -> str:
    """The per-process shard for ``base``: process 0 keeps the legacy
    path (single-process streams and every existing reader are
    untouched), process ``i`` gets ``<root>.proc<i><ext>`` —
    ``telemetry.jsonl`` → ``telemetry.proc1.jsonl`` — next to it, so
    one run directory holds one run's whole fleet."""
    if process_index <= 0:
        return base
    root, ext = os.path.splitext(base)
    return f"{root}.proc{int(process_index)}{ext}"


# ----------------------------------------------------------------------
# The stream writer
# ----------------------------------------------------------------------


def _jax_backend_initialized() -> bool:
    """True only when a jax backend is ALREADY live. ``"jax" in
    sys.modules`` is not enough — jax is imported transitively by the
    package, and ``jax.devices()`` on a merely-imported jax would
    INITIALIZE the default backend as a side effect of constructing a
    stream, racing a launcher's platform probe or a pending
    ``jax.distributed.initialize``. Unknowable (internals moved) reads
    as False: a header without device fields beats a hijacked
    backend."""
    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import xla_bridge

        return bool(xla_bridge._backends)
    except Exception:
        return False


def _self_description() -> dict:
    """Host/device/peak facts for the versioned ``header`` row —
    ``graftboard roofline``/``diff`` resolve their peak basis from
    these instead of guessing (a CPU-captured stream renders as a
    what-if on the ROOFLINE anchor, and says so). Device fields appear
    only when a jax backend is ALREADY initialized
    (``_jax_backend_initialized``) — constructing a stream must never
    initialize one. Host and device facts are best-effort — a partial
    header beats no stream."""
    out: Dict[str, Any] = {}
    try:
        import socket

        out["hostname"] = socket.gethostname()
    except Exception:
        pass
    device_kind = None
    if _jax_backend_initialized():
        try:
            import jax

            out["jax_version"] = jax.__version__
            devs = jax.devices()
            device_kind = devs[0].device_kind
            out["device_kind"] = device_kind
            out["platform"] = devs[0].platform
            out["device_count"] = len(devs)
            out["local_device_count"] = jax.local_device_count()
            out["process_count"] = jax.process_count()
        except Exception:
            pass
    from hydragnn_tpu.utils.flops import (
        resolve_peak_bandwidth,
        resolve_peak_flops,
    )

    # not best-effort: a chip the peak tables do not know raises here,
    # before any row could be read against another chip's peaks
    peak, basis = resolve_peak_flops(device_kind)
    if peak:
        out["peak_flops"] = peak
        out["peak_basis"] = basis
    bw, bw_basis = resolve_peak_bandwidth(device_kind)
    if bw:
        out["peak_hbm_bytes_per_sec"] = bw
        out["peak_hbm_basis"] = bw_basis
    return out


def _json_default(x):
    """Serialize numpy scalars/arrays without importing numpy eagerly
    (rows are built from host values; anything exotic degrades to str
    rather than killing the worker)."""
    item = getattr(x, "item", None)
    if callable(item):
        try:
            return item()
        except Exception:
            pass
    tolist = getattr(x, "tolist", None)
    if callable(tolist):
        try:
            return tolist()
        except Exception:
            pass
    return str(x)


class TelemetryStream:
    """Bounded non-blocking JSONL writer (one JSON object per line).

    Same never-block-the-step discipline as the async checkpoint
    writer: ``emit`` is a ``put_nowait`` — when the queue is full the
    row is dropped and counted (``dropped``), never awaited. The worker
    batches queued rows into one write+flush; write failures are
    absorbed (``write_errors``/``last_error`` surface them, the batch's
    rows count as ``lost_rows``) and the path re-opens on the next
    batch. ``utils.faults.on_write`` is volunteered before every batch
    write so the fault harness can prove the posture
    (tests/test_telemetry.py).
    """

    def __init__(
        self,
        path: str,
        *,
        queue_depth: int = 16384,
        sync_interval_steps: int = 0,
        rollup: bool = True,
        cost_analysis: bool = True,
        heartbeat_interval_s: float = 0.0,
        process_index: Optional[int] = None,
        meta: Optional[dict] = None,
    ) -> None:
        self.path = path
        self.sync_interval_steps = max(0, int(sync_interval_steps))
        self.rollup = bool(rollup)
        self.cost_analysis = bool(cost_analysis)
        self.heartbeat_interval_s = max(0.0, float(heartbeat_interval_s))
        ident = process_identity()
        self.process_index = int(
            ident[0] if process_index is None else process_index
        )
        self.process_count = int(ident[1])
        self.heartbeats = 0
        self.dropped = 0
        self.emitted = 0
        self.written = 0
        self.lost_rows = 0
        self.write_errors = 0
        self.last_error: Optional[BaseException] = None
        # Per-executable cost/memory registry: (region, spec, k, lanes)
        # -> {"flops", "bytes"} once captured, None when the capture
        # was attempted and failed (so it is never retried per step).
        self.exec_stats: Dict[Tuple, Optional[dict]] = {}
        self.exec_captured = 0
        self.exec_capture_failures = 0
        self._q: "queue.Queue" = queue.Queue(maxsize=max(64, queue_depth))
        self._stop = threading.Event()
        self._hb_stop = threading.Event()
        self._closed = False
        self._fh = None
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        header = {
            "t": "header",
            "schema": SCHEMA_VERSION,
            "pid": os.getpid(),
            "sync_interval_steps": self.sync_interval_steps,
        }
        header.update(_self_description())
        # Per-host identity for shard merging (graftboard fleet):
        # process_index pairs shards back into one run, process_count
        # tells the merger how many to expect (a missing shard is then
        # a LOUD degrade, not silence). Written AFTER the
        # self-description: the identity that NAMED this shard (the
        # launcher env, readable pre-jax) must win over a backend
        # answering for a different topology.
        header["process_index"] = self.process_index
        header["process_count"] = self.process_count
        if meta:
            header.update(meta)
        self._q.put_nowait(header)
        self.emitted += 1
        self._worker = threading.Thread(
            target=self._worker_main,
            name="telemetry-stream",
            daemon=True,
        )
        self._worker.start()
        self._hb_thread = None
        if self.heartbeat_interval_s > 0:
            self._hb_thread = threading.Thread(
                target=self._heartbeat_main,
                name="telemetry-heartbeat",
                daemon=True,
            )
            self._hb_thread.start()

    # -- caller side ---------------------------------------------------

    def emit(self, row: Dict[str, Any]) -> bool:
        """Enqueue one row; False (+ ``dropped``) on overflow or after
        close. NEVER blocks and never raises — the step hot path calls
        this."""
        if self._closed:
            return False
        try:
            self._q.put_nowait(row)
        except queue.Full:
            self.dropped += 1
            return False
        self.emitted += 1
        return True

    def flush(self, timeout: float = 30.0) -> bool:
        """Wait (bounded) until every enqueued row has been handed to
        the filesystem — for tests and end-of-run reports, never the
        step path."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._q.empty() and self.written + self.lost_rows >= self.emitted:
                return True
            time.sleep(0.005)
        return False

    def close(self, timeout: float = 30.0) -> None:
        """Emit a final accounting row, drain, and stop the worker.
        Never raises on I/O failure (it surfaces on ``last_error``)."""
        if self._closed:
            return
        # Heartbeat stops FIRST so the close row stays the stream's
        # last word (its own stop event — the worker's must not be set
        # before the close row is enqueued, or a racing Empty poll
        # could drop it).
        self._hb_stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=timeout)
        self.emit(
            {
                "t": "close",
                "emitted": self.emitted + 1,
                "dropped": self.dropped,
                "write_errors": self.write_errors,
                "lost_rows": self.lost_rows,
                "executables": self.exec_captured,
                "exec_capture_failures": self.exec_capture_failures,
            }
        )
        self._closed = True
        self.flush(timeout)
        self._stop.set()
        self._worker.join(timeout=timeout)

    def abandon(self, timeout: float = 5.0) -> None:
        """Stop the stream WITHOUT a close row — the SIGKILL analog for
        in-process fleet drills (serve/fleet.py's replica kill). The
        shard ends mid-stream exactly the way a killed process leaves
        it: heartbeats stop, no ``close`` accounting row, so
        graftboard's dead-replica detection (no clean exit + heartbeat
        gap) fires on it. Already-queued rows still drain — a real
        kill loses at most the in-queue tail, and keeping it makes the
        drill's pre-kill accounting deterministic."""
        if self._closed:
            return
        self._hb_stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=timeout)
        self._closed = True
        self.flush(timeout)
        self._stop.set()
        self._worker.join(timeout=timeout)

    # -- worker side ---------------------------------------------------

    def _worker_main(self) -> None:
        while True:
            rows: List[dict] = []
            try:
                rows.append(self._q.get(timeout=0.05))
            except queue.Empty:
                if self._stop.is_set():
                    break
                continue
            # Batch whatever else is queued into one write+flush.
            while len(rows) < 1024:
                try:
                    rows.append(self._q.get_nowait())
                except queue.Empty:
                    break
            lines: List[str] = []
            try:
                for row in rows:
                    try:
                        # Fleet tagging happens HERE, on the worker:
                        # every row carries process_index so a shard's
                        # rows stay attributable after any merge, and
                        # the step path never pays the dict copy.
                        if "process_index" not in row:
                            row = dict(row, process_index=self.process_index)
                        lines.append(
                            json.dumps(
                                row,
                                default=_json_default,
                                separators=(",", ":"),
                            )
                        )
                    except Exception as e:  # one bad row never kills a batch
                        self.write_errors += 1
                        self.last_error = e
                        self.lost_rows += 1
                if lines:
                    # Fault-injection point (write_fail / slow_write —
                    # the slow-write delay lands HERE, on the worker,
                    # never on the step).
                    faults.on_write(self.path)
                    if self._fh is None:
                        self._fh = open(self.path, "a")
                    self._fh.write("\n".join(lines) + "\n")
                    self._fh.flush()
                    self.written += len(lines)
            except Exception as e:
                # Absorb EVERYTHING: a dead filesystem degrades the
                # stream, never the run. The handle re-opens next
                # batch. Only the SERIALIZED lines are lost here —
                # rows that already failed json.dumps were counted
                # above (written + lost_rows must never exceed
                # emitted, or flush()'s drained test lies).
                self.write_errors += 1
                self.last_error = e
                self.lost_rows += len(lines)
                try:
                    if self._fh is not None:
                        self._fh.close()
                except Exception:
                    pass
                self._fh = None
        try:
            if self._fh is not None:
                self._fh.close()
        except Exception:
            pass
        self._fh = None

    def _heartbeat_main(self) -> None:
        """Per-process liveness beacon (docs/OBSERVABILITY.md "Fleet
        observability"): one ``heartbeat`` row immediately (every
        shard has at least one), then one per interval, carrying the
        run phase, the current blocking wait site and the feed
        counters — a SIGKILLed or wedged process becomes a heartbeat
        GAP in its shard, which ``graftboard fleet`` turns into a
        dead/stalled verdict. Its own thread: a stalled step loop or a
        parked barrier never silences the beacon."""
        while not self._hb_stop.is_set() and not self._closed:
            self.heartbeats += 1
            self.emit(
                heartbeat_row(self.heartbeats, self.heartbeat_interval_s)
            )
            if self._hb_stop.wait(self.heartbeat_interval_s):
                break


# ----------------------------------------------------------------------
# Module-level active stream + run context
# ----------------------------------------------------------------------

_ACTIVE: Optional[TelemetryStream] = None
_CONTEXT: Dict[str, Any] = {}


def install(stream: Optional[TelemetryStream]) -> None:
    """Install ``stream`` as the process's active stream. Installing a
    NEW stream starts a new run's ledger: the liveness counters and
    run phase reset, so a second in-process run (HPO trials, bench
    reps) never inherits the previous run's totals — a counter the
    new run genuinely never bumps must read absent, not frozen at the
    old value (the frozen-counter signature means a wedged feed).
    ``install(None)`` only detaches — teardown paths may still read
    state."""
    global _ACTIVE
    if stream is not None:
        _COUNTERS.clear()
        note_phase("startup")
    _ACTIVE = stream


def get() -> Optional[TelemetryStream]:
    return _ACTIVE


def active() -> bool:
    return _ACTIVE is not None


def emit(row: Dict[str, Any]) -> bool:
    """Emit onto the active stream; a cheap no-op (one global read)
    when telemetry is off — safe to call from any hot path."""
    s = _ACTIVE
    if s is None:
        return False
    return s.emit(row)


def memory_row(tag: str, epoch: Optional[int] = None) -> Dict[str, Any]:
    """Build one live ``memory`` row: per-device allocator telemetry
    (bytes_in_use / peak_bytes_in_use, summed and max'd over local
    devices via the hardened ``utils/runtime.memory_stats``) plus host
    RSS. Backends without allocator stats (CPU, older libtpu) degrade
    to the host fields only — a partial row, never a fabricated
    number and never an exception (this runs at epoch boundaries and
    after compiles, inside the run)."""
    row: Dict[str, Any] = {"t": "memory", "tag": tag}
    if epoch is not None:
        row["epoch"] = int(epoch)
    try:
        from hydragnn_tpu.utils.runtime import host_memory, memory_stats

        dev = memory_stats()
        if dev:
            row["devices"] = len(dev)
            in_use = [
                v["bytes_in_use"]
                for v in dev.values()
                if v.get("bytes_in_use") is not None
            ]
            peak = [
                v["peak_bytes_in_use"]
                for v in dev.values()
                if v.get("peak_bytes_in_use") is not None
            ]
            limit = [
                v["bytes_limit"]
                for v in dev.values()
                if v.get("bytes_limit")
            ]
            if in_use:
                row["bytes_in_use"] = int(sum(in_use))
                row["max_bytes_in_use"] = int(max(in_use))
            if peak:
                row["peak_bytes_in_use"] = int(sum(peak))
                row["max_peak_bytes_in_use"] = int(max(peak))
            if limit:
                row["bytes_limit"] = int(sum(limit))
        row.update(host_memory())
    except Exception:
        pass  # a memory sample must never be able to hurt the run
    return row


def emit_memory(tag: str, epoch: Optional[int] = None) -> bool:
    """Sample + emit a ``memory`` row onto the active stream (no-op
    off-path: the sample itself is skipped, not just the emit)."""
    s = _ACTIVE
    if s is None:
        return False
    return s.emit(memory_row(tag, epoch))


def set_context(**kw) -> None:
    """Run context the step clock folds into its rows: ``model_cfg``
    (models/spec.ModelConfig — enables the MFU rows), ``scheme``,
    ``lr``, ``epoch``. Callers own the lifecycle (the runner sets it;
    tests may too); unknown keys are stored as-is."""
    _CONTEXT.update(kw)


def get_context() -> Dict[str, Any]:
    return dict(_CONTEXT)


# ----------------------------------------------------------------------
# Fleet liveness: run phase, blocking-wait site, feed counters,
# barrier rows (docs/OBSERVABILITY.md "Fleet observability")
# ----------------------------------------------------------------------

_PHASE = "startup"
_PHASE_TS = time.time()
# Active blocking waits, PER THREAD (keyed by thread id): the
# checkpoint worker and the caller thread wait concurrently (worker
# parked at a publish barrier while the loop broadcasts walltime) —
# a single slot would let the first exit erase or resurrect the
# other's site and heartbeats would name a phantom wait.
_WAIT_SITES: Dict[int, Tuple[str, float]] = {}
_COUNTERS: Dict[str, int] = {}


def note_phase(name: str) -> None:
    """Advance the coarse run phase the heartbeat rows carry
    (``train`` / ``eval`` / ``post_training`` / ...). Called at epoch
    granularity — two module stores, nothing per step."""
    global _PHASE, _PHASE_TS
    _PHASE = str(name)
    _PHASE_TS = time.time()


def get_phase() -> str:
    return _PHASE


@contextlib.contextmanager
def waiting_on(site: str):
    """Mark a BLOCKING coordination wait (a cross-process barrier, a
    KV broadcast) for the duration of the enclosed call: heartbeats
    emitted meanwhile carry ``waiting_on``/``wait_age_s``, so a
    process parked on a rendezvous its peer never reaches is
    attributable from its own shard's tail. Kept separate from the
    loop phase — barrier waits run on the checkpoint worker thread
    while the step loop keeps its own phase — and registered PER
    THREAD so concurrent waits never clobber each other (nested waits
    on one thread restore the outer site on exit)."""
    key = threading.get_ident()
    prev = _WAIT_SITES.get(key)
    _WAIT_SITES[key] = (str(site), time.time())
    try:
        yield
    finally:
        if prev is None:
            _WAIT_SITES.pop(key, None)
        else:
            _WAIT_SITES[key] = prev


def bump(name: str, n: int = 1) -> None:
    """Count feed/dispatch liveness (monotonic, per process) for the
    heartbeat rows — a wedged feed shows as a frozen counter across
    beats. One global read + one dict store; a cheap no-op with the
    stream off. Pure host work: safe on every hot path."""
    if _ACTIVE is None:
        return
    _COUNTERS[name] = _COUNTERS.get(name, 0) + n


def counters() -> Dict[str, int]:
    return dict(_COUNTERS)


def heartbeat_row(seq: int, interval_s: float) -> Dict[str, Any]:
    """One liveness row: wall clock, run phase (+ age), the current
    blocking wait site when one is marked, and the counter snapshot.
    Pure host reads — built on the heartbeat thread."""
    now = time.time()
    row: Dict[str, Any] = {
        "t": "heartbeat",
        "seq": int(seq),
        "ts": round(now, 3),
        "interval_s": interval_s,
        "phase": _PHASE,
        "phase_age_s": round(now - _PHASE_TS, 3),
    }
    try:
        # The OLDEST active wait across threads — the one a wedged
        # fleet is actually stuck on. Snapshots of dicts other
        # threads mutate can rarely raise mid-resize; a beat without
        # the optional fields beats a dead beacon.
        sites = list(_WAIT_SITES.values())
        if sites:
            site, ts0 = min(sites, key=lambda sv: sv[1])
            row["waiting_on"] = site
            row["wait_age_s"] = round(now - ts0, 3)
        if _COUNTERS:
            row["counters"] = dict(_COUNTERS)
    except Exception:
        pass
    return row


def emit_barrier(
    site: str,
    seq: int,
    total_s: float,
    barrier_s: Optional[float] = None,
    timed_out: bool = False,
    broadcast: bool = False,
) -> bool:
    """Emit one versioned ``barrier`` row for a coordination wait:
    ``wait_ms`` is the whole crossing (fault ticks included — an
    injected stall is visible here), ``barrier_ms`` only the time
    parked at the shared rendezvous. The asymmetry is the attribution
    signal ``graftboard fleet`` keys on: the LAST arriver barely waits
    at the barrier itself (min ``barrier_ms``), its peers absorb the
    delay — clock-skew-free, unlike comparing ``ts`` across hosts.
    ``timed_out`` marks a crossing whose wait RAISED (dead peer,
    coordination timeout) — the most diagnostic wait of all must
    still reach the shard. ``broadcast`` marks an ASYMMETRIC wait (a
    KV set/get broadcast: only processes arriving before the setter
    park; late arrivers read instantly) — graftboard reports its
    waits per process but must NOT apply rendezvous last-arriver
    attribution, whose premise doesn't hold there. Never blocks
    (plain ``emit``); a no-op with the stream off."""
    s = _ACTIVE
    if s is None:
        return False
    row: Dict[str, Any] = {
        "t": "barrier",
        "site": str(site),
        "seq": int(seq),
        "ts": round(time.time(), 3),
        "wait_ms": round(1e3 * float(total_s), 3),
    }
    if barrier_s is not None:
        row["barrier_ms"] = round(1e3 * float(barrier_s), 3)
    if timed_out:
        row["timed_out"] = True
    if broadcast:
        row["broadcast"] = True
    ep = _CONTEXT.get("epoch")
    if ep is not None:
        row["epoch"] = int(ep)
    bump("barriers")
    return s.emit(row)


def note_epoch(epoch: int, lr: Optional[float] = None) -> None:
    """Advance the run context (and the compile observer's phase) to
    ``epoch`` — called by the epoch loop so post-warmup compiles are
    attributable to the epoch that triggered them."""
    _CONTEXT["epoch"] = int(epoch)
    if lr is not None:
        _CONTEXT["lr"] = float(lr)
    obs = _OBSERVER
    if obs is not None:
        obs.set_phase(int(epoch))


def end_of_training() -> None:
    """Mark the post-training phase: compiles from here on (BN
    recalibration forwards, run_test's collect-outputs eval, export)
    are NEW executables by design, not retrace leaks."""
    note_phase("post_training")
    obs = _OBSERVER
    if obs is not None:
        obs.set_phase(-1)


def configure(
    training: dict,
    log_name: Optional[str] = None,
    meta: Optional[dict] = None,
) -> Optional[TelemetryStream]:
    """Build + install the stream (and the compile observer) from the
    ``Training.Telemetry`` block; None when disabled. The runner owns
    this; tests may call it with a synthetic block. EVERY process of a
    multi-process run configures its own shard (``shard_path``):
    process 0 keeps the configured/legacy path, process ``i`` writes
    ``<stream>.proc<i>.jsonl`` next to it — ``graftboard fleet``
    merges them back into one run."""
    st = telemetry_settings(training)
    if not st.enabled:
        return None
    base = st.stream_path or os.path.join(
        "logs", log_name or "run", "telemetry.jsonl"
    )
    # Reset the run ledger BEFORE the stream exists: its heartbeat
    # thread emits beat #1 immediately on construction, and that beat
    # must not carry a previous in-process run's counters/phase
    # (install() also resets, but it runs after construction).
    _COUNTERS.clear()
    note_phase("startup")
    pidx, _ = process_identity()
    stream = TelemetryStream(
        shard_path(base, pidx),
        queue_depth=st.queue_depth,
        sync_interval_steps=st.sync_interval_steps,
        rollup=st.rollup,
        cost_analysis=st.cost_analysis,
        heartbeat_interval_s=st.heartbeat_interval_s,
        meta=meta,
    )
    install(stream)
    install_observer(stream)
    return stream


# ----------------------------------------------------------------------
# Set-up phases
# ----------------------------------------------------------------------

# ``setup`` rows whose phase ended before the stream was configured
# (run_training builds loaders and model first); None = not holding.
_SETUP_HELD: Optional[List[dict]] = None


def setup_row(phase: str, ms: float, **kw) -> None:
    """One ``{"t": "setup", "phase", "ms"}`` row: where the time before
    the first steady epoch went. Held in memory while ``SetupClock`` is
    waiting for the stream, emitted otherwise (a no-op with no stream)."""
    row = {"t": "setup", "phase": phase, "ms": round(float(ms), 3), **kw}
    if _SETUP_HELD is not None:
        _SETUP_HELD.append(row)
    else:
        emit(row)


@contextlib.contextmanager
def setup_phase(name: str):
    """A named phase of the run's set-up: a ``tr.region`` (so it shows in
    the RegionTimer's CSV as ``setup/<name>`` and on a live profiler
    capture) and a ``setup`` row with its wall time."""
    t0 = time.perf_counter()
    try:
        with tr.region(f"setup/{name}"):
            yield
    finally:
        setup_row(name, 1e3 * (time.perf_counter() - t0))


class SetupClock:
    """``run_training``'s set-up cut into phases at its own boundaries:
    ``phase(name)`` ends the open phase and begins the next. Rows are
    held until ``stream_ready()`` says the telemetry stream is configured
    (or is not going to be)."""

    def __init__(self) -> None:
        global _SETUP_HELD
        _SETUP_HELD = []
        self._open = contextlib.ExitStack()  # the open phase, if any

    def phase(self, name: str) -> None:
        self._open.close()
        self._open.enter_context(setup_phase(name))

    def end_phase(self) -> None:
        self._open.close()

    def stream_ready(self) -> None:
        """The stream is open, or stays off: write what was held."""
        global _SETUP_HELD
        held, _SETUP_HELD = _SETUP_HELD or [], None
        for row in held:
            emit(row)

    def close(self) -> None:
        """End of ``run_training``, sound or not: nothing stays open, and
        rows no stream ever came for are dropped."""
        global _SETUP_HELD
        self.end_phase()
        _SETUP_HELD = None


def close_run(stream: Optional[TelemetryStream]) -> None:
    """Tear down what ``configure`` built — closes the observer (its
    summary row lands in the stream first), then the stream. Only
    touches the module state the given stream owns, so an externally
    installed stream (tests) survives a runner invocation."""
    if stream is None:
        return
    obs = _OBSERVER
    if obs is not None and obs.stream is stream:
        obs.close()
    stream.close()
    global _ACTIVE
    if _ACTIVE is stream:
        _ACTIVE = None


# ----------------------------------------------------------------------
# The step clock
# ----------------------------------------------------------------------


def _feed_labels(loader) -> tuple:
    """(feed, scheme_hint, d, base_loader) derived from the wrapper
    chain — the same ``.loader`` walk every find-in-chain helper uses
    (data/loader.iter_loader_chain)."""
    from hydragnn_tpu.data.loader import iter_loader_chain

    labels = []
    d = 1
    base = None
    scheme = None
    for ld in iter_loader_chain(loader):
        name = type(ld).__name__
        if name == "ParallelPipelineLoader":
            labels.append("pipeline")
        elif name == "PrefetchLoader":
            labels.append("prefetch")
        elif name == "SuperstepLoader":
            labels.append("superstep")
        elif name == "DPLoader":
            labels.append("dp")
            scheme = "dp"
            d = int(getattr(ld, "n_global", 1))
            if int(getattr(ld, "superstep_k", 1)) > 1:
                labels.append("superstep")
        elif name == "MultiBranchLoader":
            labels.append("multibranch")
            scheme = "multibranch"
        if hasattr(ld, "epoch_size_rows"):
            base = ld
    return ("+".join(labels) or "serial", scheme, d, base)


def _spec_of(batch) -> tuple:
    """(spec_id, nodes_pad, edges_pad, graphs_pad) from the padded
    shapes' LAST axes — static metadata, no device access. Leading
    axes ([K, ...] macros, [D, ...] dp stacks) are reported separately
    as k / lanes."""
    from hydragnn_tpu.data.graph import MacroBatch

    b = batch.batch if isinstance(batch, MacroBatch) else batch
    n = int(b.node_mask.shape[-1])
    e = int(b.edge_mask.shape[-1])
    g = int(b.graph_mask.shape[-1])
    return (f"n{n}_e{e}_g{g}", n, e, g)


def _triplets_pad(batch) -> Optional[int]:
    """Padded triplet slots of one step (the last axis of ``t_kj``), or
    None for a batch without triplets."""
    from hydragnn_tpu.data.graph import MacroBatch

    b = batch.batch if isinstance(batch, MacroBatch) else batch
    t_kj = getattr(b, "t_kj", None)
    return None if t_kj is None else int(t_kj.shape[-1])


class StepClock:
    """Per-epoch step clock — built by ``epoch_clock`` and driven by
    ``train/loop._run_epoch``. Collects one row per DISPATCH (a
    superstep macro is one dispatch covering ``k`` optimizer steps; a
    dp batch carries ``lanes`` device lanes), with deferred device refs
    for loss/graph counts, and resolves + emits everything in
    ``finish`` — zero host syncs on the default path."""

    def __init__(
        self,
        stream: TelemetryStream,
        *,
        region: str,
        epoch: int = 0,
        feed: str = "serial",
        scheme: str = "single",
        d: int = 1,
        step0: int = 0,
        size_rows=None,
        triplet_rows=None,
        model_cfg=None,
        lr: Optional[float] = None,
    ) -> None:
        self.stream = stream
        self.region = region
        self.epoch = int(epoch)
        self.feed = feed
        self.scheme = scheme
        self.d = max(1, int(d))
        self.lr = lr
        self.model_cfg = model_cfg
        self.sync_interval = stream.sync_interval_steps
        self._rows: List[dict] = []
        self._refs: List[Any] = []
        self._size_rows = size_rows  # [n_plan_steps, 3] or None
        self._triplet_rows = triplet_rows  # [n_plan_steps] or None
        self._size_cursor = int(step0) * self.d
        self._prev_end: Optional[float] = None
        self._t_first: Optional[float] = None
        self._n_records = 0

    def record(
        self,
        *,
        step: int,
        k: int,
        batch,
        is_macro: bool,
        t_fetch_start: float,
        t_fetch_end: float,
        t_dispatch_start: float,
        t_dispatch_end: float,
        loss_ref=None,
        ng_ref=None,
        capture_fn=None,
        capture_args=None,
    ) -> None:
        """One dispatch: ``step`` is the cumulative optimizer-step
        count AFTER it, ``k`` the steps it covered. ``loss_ref`` /
        ``ng_ref`` are lazy device scalars held (not fetched) until
        ``finish`` — holding a ref adds no arithmetic and no sync.

        ``capture_fn``/``capture_args``: the jitted step and the
        post-dispatch arguments whose avals reproduce this dispatch's
        executable — on the FIRST sighting of (region, spec, k,
        lanes) the clock AOT-lowers and compiles them to read XLA's
        cost/memory accounting (``_maybe_capture``); every later
        dispatch of the key pays one dict lookup. Post-dispatch args
        are deliberate: the returned state/acc carry the same avals
        as the donated inputs, and lowering never touches buffer
        contents, so the capture adds no sync and no donation hazard.

        Macro (superstep) dispatches DONATE the metric accumulator to
        the next dispatch, which host-side marks the held buffer
        deleted — so the macro's cumulative ``loss_sum`` is snapshot
        through ``x + 0.0`` (bitwise x, the same identity the
        zero-init accumulator relies on) into a fresh, never-donated
        scalar; one tiny enqueued op per K-step macro."""
        import jax

        if is_macro and loss_ref is not None:
            loss_ref = loss_ref + 0.0
        spec, n_pad, e_pad, g_pad = _spec_of(batch)
        if (
            capture_fn is not None
            and self.stream.cost_analysis
            and (self.region, spec, int(k), self.d)
            not in self.stream.exec_stats
        ):
            self._maybe_capture(capture_fn, capture_args, spec, int(k))
        wall_start = (
            self._prev_end if self._prev_end is not None else t_fetch_start
        )
        if self._t_first is None:
            self._t_first = t_fetch_start
        self._prev_end = t_dispatch_end
        row = {
            "t": "step",
            "region": self.region,
            "epoch": self.epoch,
            "step": int(step),
            "k": int(k),
            "lanes": self.d,
            "feed": self.feed,
            "scheme": self.scheme,
            "spec": spec,
            "nodes_pad": n_pad,
            "edges_pad": e_pad,
            "graphs_pad": g_pad,
            "input_wait_ms": round(1e3 * (t_fetch_end - t_fetch_start), 4),
            "dispatch_ms": round(
                1e3 * (t_dispatch_end - t_dispatch_start), 4
            ),
            "wall_ms": round(1e3 * (t_dispatch_end - wall_start), 4),
        }
        if self.lr is not None:
            row["lr"] = float(self.lr)
        # Plan-domain real sizes: k optimizer steps x d lanes consume
        # k*d plan entries — pure host metadata from epoch_size_rows
        # (rows are (nodes+1 pad slot, edges, graphs+1 pad slot)).
        rows = self._size_rows
        take = int(k) * self.d
        if rows is not None and self._size_cursor + take <= len(rows):
            sl = rows[self._size_cursor : self._size_cursor + take]
            row["nodes"] = int(sl[:, 0].sum()) - take
            row["edges"] = int(sl[:, 1].sum())
            row["graphs_plan"] = int(sl[:, 2].sum()) - take
        trips = self._triplet_rows
        t_pad = _triplets_pad(batch) if trips is not None else None
        if t_pad is not None and self._size_cursor + take <= len(trips):
            row["triplets"] = int(
                trips[self._size_cursor : self._size_cursor + take].sum()
            )
            row["triplets_pad"] = t_pad
        self._size_cursor += take
        self._n_records += 1
        # Liveness counters for the heartbeat rows: a process whose
        # dispatch counter freezes across beats is wedged, not slow.
        bump("dispatches")
        bump("opt_steps", int(k))
        if (
            self.sync_interval > 0
            and loss_ref is not None
            and self._n_records % self.sync_interval == 0
        ):
            # The SAMPLED device fence — the one opt-in host sync in
            # the telemetry path: it drains the dispatch queue so
            # wall decomposition gains a device-complete reading, at
            # the documented cost of the async overlap on this step.
            # graftlint: disable-next-line=host-sync -- config-gated sampled fence (Telemetry.sync_interval_steps > 0); the default interval 0 never reaches this line
            jax.block_until_ready(loss_ref)
            row["device_complete_ms"] = round(
                1e3 * (time.perf_counter() - t_dispatch_start), 4
            )
        # Defer device scalars to the ONE epoch-end fetch.
        if loss_ref is not None:
            row["_loss_ref"] = len(self._refs)
            row["_loss_field"] = "loss_sum" if is_macro else "loss"
            self._refs.append(loss_ref)
        if ng_ref is not None:
            row["_ng_ref"] = len(self._refs)
            self._refs.append(ng_ref)
        self._rows.append(row)

    def _maybe_capture(self, fn, args, spec: str, k: int) -> None:
        """First-dispatch executable capture: AOT ``fn.lower(*args)
        .compile()`` of the SAME jitted step this dispatch ran, parsed
        by the helpers of utils/flops.py and
        emitted as one versioned ``executable`` row. Runs ONCE per
        (region, spec, k, lanes) key — at warmup for the stable specs,
        at the leak's first dispatch for a post-warmup retrace (the
        compile observer flags the leak; the row records what it
        cost). The extra XLA compile lands next to the jit compile it
        mirrors (and hits the persistent compilation cache when one is
        enabled); a failed capture is counted and NEVER retried per
        step, and cost fields XLA doesn't report are OMITTED, not
        zero-filled. No host syncs: lowering/compiling reads avals,
        never buffer contents (graftlint HOT_SEEDS covers this)."""
        key = (self.region, spec, int(k), self.d)
        stream = self.stream
        stream.exec_stats[key] = None  # claim: attempted, not retried
        row = {
            "t": "executable",
            "region": self.region,
            "epoch": self.epoch,
            "feed": self.feed,
            "scheme": self.scheme,
            "spec": spec,
            "k": int(k),
            "lanes": self.d,
        }
        t0 = time.perf_counter()
        try:
            with suppress_compile_events():
                compiled = fn.lower(*args).compile()
        except Exception as e:
            stream.exec_capture_failures += 1
            row["capture_error"] = repr(e)[:200]
            stream.emit(row)
            return
        from hydragnn_tpu.utils.flops import (
            compiled_cost_stats,
            compiled_memory_stats,
        )

        cost = compiled_cost_stats(compiled)
        mem = compiled_memory_stats(compiled)
        row["capture_ms"] = round(1e3 * (time.perf_counter() - t0), 3)
        if cost:
            row.update(cost)
        else:
            row["cost_unavailable"] = True
        if mem:
            row.update(mem)
        obs = _OBSERVER
        if obs is not None and 0 <= obs.warmup_phase <= self.epoch:
            # A steady-state epoch should never meet a NEW executable:
            # mark the row so graftboard can pair it with the
            # observer's retrace-leak compile events.
            row["post_warmup"] = True
        stream.exec_captured += 1
        stream.emit(row)
        if cost.get("flops"):
            stream.exec_stats[key] = {
                "flops": cost["flops"],
                "bytes": cost.get("bytes_accessed", 0.0),
            }

    def finish(self) -> None:
        """Resolve the deferred refs in ONE batched fetch and emit the
        epoch's step rows, the per-spec aggregates, and — when the run
        context carries a model config — the live MFU rows. Runs at
        epoch end, AFTER the loop's own single metrics fetch."""
        import jax
        import numpy as np

        vals: List[Any] = []
        if self._refs:
            # graftlint: disable-next-line=host-sync -- ONE batched epoch-end fetch of already-computed scalars (the loop's own metrics fetch has already drained the queue)
            vals = list(jax.device_get(self._refs))
        specs: Dict[str, dict] = {}
        for row in self._rows:
            li = row.pop("_loss_ref", None)
            lf = row.pop("_loss_field", "loss")
            if li is not None:
                row[lf] = float(np.asarray(vals[li]).reshape(())[()])
            gi = row.pop("_ng_ref", None)
            if gi is not None:
                row["graphs"] = float(np.asarray(vals[gi]).reshape(())[()])
            agg = specs.setdefault(
                row["spec"],
                {
                    "dispatches": 0,
                    "steps": 0,
                    "input_wait_ms": 0.0,
                    "dispatch_ms": 0.0,
                    "wall_ms": 0.0,
                    "device_complete_ms": 0.0,
                    "device_samples": 0,
                    "nodes": 0,
                    "edges": 0,
                    "graphs": 0.0,
                    "have_sizes": True,
                    "_hw_flops": 0.0,
                    "_hw_bytes": 0.0,
                    "_hw_dispatches": 0,
                    "_hw_missing": 0,
                },
            )
            agg["dispatches"] += 1
            agg["steps"] += row["k"]
            # Counted-hardware attribution: the executable registry
            # keyed at first dispatch (same k-remainder singles of a
            # spec resolve to their OWN executable's numbers).
            hw = self.stream.exec_stats.get(
                (self.region, row["spec"], row["k"], self.d)
            )
            if hw:
                agg["_hw_flops"] += hw["flops"]
                agg["_hw_bytes"] += hw["bytes"] or 0.0
                agg["_hw_dispatches"] += 1
            else:
                agg["_hw_missing"] += 1
            agg["input_wait_ms"] += row["input_wait_ms"]
            agg["dispatch_ms"] += row["dispatch_ms"]
            agg["wall_ms"] += row["wall_ms"]
            if "device_complete_ms" in row:
                agg["device_complete_ms"] += row["device_complete_ms"]
                agg["device_samples"] += 1
            if "nodes" in row:
                agg["nodes"] += row["nodes"]
                agg["edges"] += row["edges"]
            else:
                agg["have_sizes"] = False
            if "graphs" in row:
                agg["graphs"] += row["graphs"]
            elif "graphs_plan" in row:
                agg["graphs"] += row["graphs_plan"]
            self.stream.emit(row)
        if not self.stream.rollup or not specs:
            self._rows, self._refs = [], []
            return
        from hydragnn_tpu.utils.flops import (
            model_flops_per_graph,
            resolve_peak_bandwidth,
            resolve_peak_flops,
        )

        kind = None
        try:
            kind = jax.devices()[0].device_kind
        except Exception:
            pass
        peak, basis = resolve_peak_flops(kind)
        peak_bw, bw_basis = resolve_peak_bandwidth(kind)
        for spec, agg in specs.items():
            have_sizes = agg.pop("have_sizes")
            hw_flops = agg.pop("_hw_flops")
            hw_bytes = agg.pop("_hw_bytes")
            hw_dispatches = agg.pop("_hw_dispatches")
            hw_missing = agg.pop("_hw_missing")
            out = {
                "t": "spec_rollup",
                "region": self.region,
                "epoch": self.epoch,
                "feed": self.feed,
                "scheme": self.scheme,
                "lanes": self.d,
                "spec": spec,
                **{
                    kk: (round(vv, 4) if isinstance(vv, float) else vv)
                    for kk, vv in agg.items()
                },
            }
            # MFU is derived from the EMITTED fields (not pre-rounding
            # intermediates), so a reader recomputing
            # ``flops(cfg, mean_nodes, mean_edges) * graphs / wall /
            # peak`` from the row reproduces ``mfu`` exactly — the
            # 1e-9-relative consistency contract with utils/flops.py's
            # arithmetic (tests/test_telemetry.py pins it).
            graphs = out["graphs"]
            wall_s = out["wall_ms"] / 1e3
            if graphs > 0 and wall_s > 0:
                out["graphs_per_sec"] = round(graphs / wall_s, 3)
            if (
                self.model_cfg is not None
                and have_sizes
                and graphs > 0
                and wall_s > 0
            ):
                out["mean_nodes"] = agg["nodes"] / graphs
                out["mean_edges"] = agg["edges"] / graphs
                mf = model_flops_per_graph(
                    self.model_cfg, out["mean_nodes"], out["mean_edges"]
                )
                if mf:
                    achieved = mf * graphs / wall_s
                    out["model_flops_per_graph"] = mf
                    out["achieved_flops_per_sec"] = achieved
                    if peak:
                        out["peak_flops"] = peak
                        out["peak_basis"] = basis
                        out["mfu"] = achieved / peak
            # Counted-hardware side (roofline attribution): totals are
            # the sum of each dispatch's executable cost_analysis;
            # hw-MFU / intensity are derived from the EMITTED fields
            # (same reader-reproducibility contract as ``mfu``) and
            # only at FULL coverage — a partially attributed epoch
            # reports its sums and the miss count, never a diluted
            # utilization (no fabricated estimates).
            if hw_dispatches:
                out["hw_dispatches"] = hw_dispatches
                if hw_missing:
                    out["hw_missing_dispatches"] = hw_missing
                out["hw_flops"] = round(hw_flops, 4)
                if hw_bytes > 0:
                    out["hw_bytes_accessed"] = round(hw_bytes, 4)
                if hw_missing == 0 and wall_s > 0:
                    hw_rate = out["hw_flops"] / wall_s
                    out["hw_flops_per_sec"] = hw_rate
                    if peak:
                        out.setdefault("peak_flops", peak)
                        out.setdefault("peak_basis", basis)
                        out["hw_mfu"] = hw_rate / out["peak_flops"]
                    if hw_bytes > 0:
                        out["intensity"] = (
                            out["hw_flops"] / out["hw_bytes_accessed"]
                        )
                    if peak_bw:
                        out["peak_hbm_bytes_per_sec"] = peak_bw
                        out["peak_hbm_basis"] = bw_basis
                    if (
                        "model_flops_per_graph" in out
                        and graphs > 0
                    ):
                        # executed/analytic — the padding + lowering
                        # + recompute waste factor (>= 1 for plain
                        # fwd+bwd; MLIP's 9x is an upper bound, so
                        # the quotient can read < 1 there).
                        out["hw_over_model_flops"] = out["hw_flops"] / (
                            out["model_flops_per_graph"] * graphs
                        )
            elif hw_missing and self.stream.cost_analysis:
                out["hw_missing_dispatches"] = hw_missing
            self.stream.emit(out)
        self._rows, self._refs = [], []


def epoch_clock(loader, region: str, step0: int = 0) -> Optional[StepClock]:
    """Build the epoch's StepClock off the active stream (None when
    telemetry is off — the loop then pays a single ``is None`` test per
    epoch). Feed/scheme labels and the plan-domain size rows are
    derived from the loader chain; model config and lr ride the run
    context (``set_context``)."""
    stream = _ACTIVE
    if stream is None:
        return None
    feed, scheme_hint, d, base = _feed_labels(loader)
    # The PLAN epoch is the base loader's cursor (eval loaders stay at
    # 0 — their plan is epoch-invariant); the LABEL epoch prefers the
    # run context so an epoch-5 eval pass is attributed to epoch 5.
    plan_epoch = int(getattr(base, "_epoch", 0) or 0)
    ctx = _CONTEXT
    epoch = int(ctx.get("epoch", plan_epoch)) if "epoch" in ctx else plan_epoch
    size_rows = triplet_rows = None
    if base is not None:
        try:
            size_rows = base.epoch_size_rows(plan_epoch)
        except Exception:
            size_rows = None  # lazy containers without size metadata
        if size_rows is not None and getattr(base, "with_triplets", False):
            triplet_rows = base.epoch_triplet_rows(plan_epoch)
    return StepClock(
        stream,
        region=region,
        epoch=epoch,
        feed=feed,
        scheme=scheme_hint or ctx.get("scheme") or "single",
        d=d,
        step0=step0,
        size_rows=size_rows,
        triplet_rows=triplet_rows,
        model_cfg=ctx.get("model_cfg"),
        lr=ctx.get("lr"),
    )


# ----------------------------------------------------------------------
# Compile / retrace observer
# ----------------------------------------------------------------------

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

_OBSERVER: Optional["CompileObserver"] = None
_MONITOR_REGISTERED = False
# True while a DELIBERATE AOT lower+compile runs — StepClock's
# first-dispatch capture and the serving engine's startup warm-up
# (serve/engine.py): their backend_compile events (the jit cache and
# the AOT path don't share, so these genuinely recompile) must not
# reach the observer — the capture would double-count every compile
# and report one real post-warmup retrace leak as TWO, and a serving
# warm-up would read as a leak storm at startup. Main-thread-only
# (both run synchronously between dispatches), so a plain flag is
# race-free. Enter through ``suppress_compile_events()``.
_SUPPRESS_COMPILE_EVENTS = False


@contextlib.contextmanager
def suppress_compile_events():
    """Context manager hiding the enclosed DELIBERATE compiles from the
    retrace-leak observer (see ``_SUPPRESS_COMPILE_EVENTS``) — the one
    sanctioned way in: ``StepClock._maybe_capture`` wraps its AOT
    cost capture in it, the serving engine wraps its startup
    executable warm-up (tests/test_serving.py pins the observer counts
    through a warm-up). Steady-state work must NEVER run inside it —
    that would blind the leak detector to real retraces."""
    global _SUPPRESS_COMPILE_EVENTS
    prev = _SUPPRESS_COMPILE_EVENTS
    _SUPPRESS_COMPILE_EVENTS = True
    try:
        yield
    finally:
        _SUPPRESS_COMPILE_EVENTS = prev


def _dispatch_event(name: str, **kw) -> None:
    if _SUPPRESS_COMPILE_EVENTS:
        return
    obs = _OBSERVER
    if obs is not None:
        obs._on_event(name)


def _dispatch_duration(name: str, duration: float, **kw) -> None:
    if _SUPPRESS_COMPILE_EVENTS:
        return
    obs = _OBSERVER
    if obs is not None:
        obs._on_duration(name, duration)


def _ensure_monitor_listeners() -> None:
    """Register the module dispatchers with jax.monitoring ONCE per
    process. jax.monitoring has no public unregister, so the
    dispatchers stay registered forever and route to whatever observer
    is active (or nothing) — install/close of observers is therefore
    idempotent and leak-free."""
    global _MONITOR_REGISTERED
    if _MONITOR_REGISTERED:
        return
    import jax.monitoring

    jax.monitoring.register_event_listener(_dispatch_event)
    jax.monitoring.register_event_duration_secs_listener(
        _dispatch_duration
    )
    _MONITOR_REGISTERED = True


class CompileObserver:
    """Counts XLA compilations (``backend_compile`` duration events)
    and persistent-compilation-cache hits/misses; any compilation at
    phase >= ``warmup_phase`` (phases are epochs; warmup default 1 =
    "after epoch 0") is flagged as a RETRACE LEAK — the runtime
    complement to graftlint's static ``retrace`` rule. Rows go to the
    attached stream when one is set; counters always accumulate for
    direct inspection (``summary()``)."""

    def __init__(
        self,
        stream: Optional[TelemetryStream] = None,
        warmup_phase: int = 1,
    ) -> None:
        self.stream = stream
        self.warmup_phase = int(warmup_phase)
        self.phase = 0
        self.compile_count = 0
        self.compile_ms = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.events: List[dict] = []
        self.post_warmup: List[dict] = []
        self._setup_emitted = False

    # -- lifecycle -----------------------------------------------------

    def install(self) -> "CompileObserver":
        """Make this the active observer (idempotent — installing an
        already-active observer is a no-op; installing a new one
        replaces the old, which then receives nothing)."""
        global _OBSERVER
        _ensure_monitor_listeners()
        _OBSERVER = self
        return self

    def close(self) -> None:
        """Detach (a closed observer receives no further events — the
        no-cross-test-leakage contract) and emit the summary row."""
        global _OBSERVER
        self._emit_setup_row()
        if self.stream is not None:
            self.stream.emit({"t": "compile_summary", **self.summary()})
        if _OBSERVER is self:
            _OBSERVER = None

    def set_phase(self, phase: int) -> None:
        self.phase = int(phase)
        if phase < 0 or phase >= self.warmup_phase:
            self._emit_setup_row()

    def _emit_setup_row(self) -> None:
        """The warm-up's compilations as one ``setup`` row, written once,
        when the warm-up ends: ``ms`` is XLA compilation plus retrieval
        from the persistent cache (jax's ``backend_compile`` event spans
        both), beside the count and the cache's hits and misses."""
        if self._setup_emitted or self.stream is None:
            return
        self._setup_emitted = True
        self.stream.emit(
            {
                "t": "setup",
                "phase": "compile",
                "ms": round(self.compile_ms, 3),
                "compile_count": self.compile_count,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
            }
        )

    # -- event sinks (called from the module dispatchers) --------------

    def _on_event(self, name: str) -> None:
        if name == _CACHE_HIT:
            self.cache_hits += 1
        elif name == _CACHE_MISS:
            self.cache_misses += 1

    def _on_duration(self, name: str, duration: float) -> None:
        if name != _BACKEND_COMPILE:
            return
        ms = 1e3 * float(duration)
        self.compile_count += 1
        self.compile_ms += ms
        leak = 0 <= self.warmup_phase <= self.phase
        ev = {
            "seq": self.compile_count,
            "epoch": self.phase,
            "ms": round(ms, 3),
            "retrace_leak": leak,
        }
        self.events.append(ev)
        if leak:
            self.post_warmup.append(ev)
            print(
                f"[telemetry] RETRACE LEAK: XLA compilation #"
                f"{self.compile_count} ({ms:.1f}ms) during epoch "
                f"{self.phase} — steady-state epochs should replay "
                "cached executables (see graftlint's retrace rule for "
                "the static hazards; a new shape reaching jit is the "
                "usual cause)",
                flush=True,
            )
        if self.stream is not None:
            self.stream.emit({"t": "compile", **ev})
            # A fresh executable is exactly when the allocator
            # footprint moves: sample memory right after each compile
            # (one cheap host call per compile event, never per step).
            self.stream.emit(memory_row("compile", epoch=self.phase))

    def summary(self) -> dict:
        return {
            "compile_count": self.compile_count,
            "compile_ms": round(self.compile_ms, 3),
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "post_warmup_compiles": len(self.post_warmup),
        }


def install_observer(
    stream: Optional[TelemetryStream] = None, warmup_phase: int = 1
) -> CompileObserver:
    return CompileObserver(stream, warmup_phase).install()


def observer() -> Optional[CompileObserver]:
    return _OBSERVER
