"""Region tracing + profiling: one vocabulary of scopes and spans.

TPU-native equivalent of the reference's tracer multiplexer
(hydragnn/utils/profiling_and_tracing/tracer.py:361-483: registry of
optional tracers, ``tr.start/stop`` with optional device sync,
``@tr.profile`` decorator, CSV dumps) and of the epoch-gated
torch.profiler wrapper (profiling_and_tracing/profile.py:9-70).

Three helpers name the work where it happens (docs/OBSERVABILITY.md
"Profiler alignment" lists every name):

- ``scope(name)`` -- a ``jax.named_scope`` from the fixed ``SCOPES``
  vocabulary around DEVICE work. Compile-time metadata only: the name
  lands in every enclosed op's HLO ``op_name`` (the trace's ``tf_op``),
  so a trace is read by layer and phase whatever implements the op.
  Forward and backward need no scope: JAX writes ``jvp(`` /
  ``transpose(`` into the same path.
- ``span(name)`` -- a ``jax.profiler.TraceAnnotation`` around HOST work
  on any thread, while a capture started here is live; the shared no-op
  context otherwise.
- ``region(name)`` -- a ``span`` that also drives the installed tracers
  (``RegionTimer`` under ``HYDRAGNN_TPU_TRACE_LEVEL``, dumped to
  ``logs/<run>/timing.p<rank>.csv``). Loop thread only: the timer nests
  regions on one stack.

Device activity comes from the profiler's trace (``Profiler``), device
memory from ``memory_stats()``; no sideband poller lives here.

Device sync: JAX dispatch is async; ``sync=True`` inserts a
``block_until_ready`` barrier so region times measure device completion
(the analog of the reference's cudasync, tracer.py:384-414).
"""

from __future__ import annotations

import contextlib
import csv
import functools
import os
import time
from typing import Any, Callable, Dict, List, Optional

__all__ = [
    "initialize",
    "start",
    "stop",
    "sample",
    "profile",
    "enable",
    "disable",
    "reset",
    "save",
    "has",
    "Profiler",
    "jax_trace_active",
    "set_trace_step_budget",
    "note_trace_step",
    "step_annotation",
    "SCOPES",
    "scope",
    "scoped",
    "span",
    "region",
]

_TRACERS: Dict[str, Any] = {}


class RegionTimer:
    """Nested wall-clock regions: total / count / min / max per name."""

    def __init__(self) -> None:
        self._open: Dict[str, float] = {}
        self._stack: List[str] = []
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self.mins: Dict[str, float] = {}
        self.maxs: Dict[str, float] = {}
        self.enabled = True

    def start(self, name: str) -> None:
        if not self.enabled:
            return
        self._stack.append(name)
        self._open[self._key()] = time.perf_counter()

    def stop(self, name: str) -> None:
        if not self.enabled:
            return
        key = self._key()
        t0 = self._open.pop(key, None)
        if self._stack and self._stack[-1] == name:
            self._stack.pop()
        if t0 is None:
            return
        dt = time.perf_counter() - t0
        self.totals[key] = self.totals.get(key, 0.0) + dt
        self.counts[key] = self.counts.get(key, 0) + 1
        self.mins[key] = min(self.mins.get(key, dt), dt)
        self.maxs[key] = max(self.maxs.get(key, dt), dt)

    def _key(self) -> str:
        return "/".join(self._stack)

    def add_sample(self, name: str, value: float) -> None:
        """Record an externally-measured value as one observation of
        region ``name`` (total/count/min/max semantics identical to a
        start/stop pair). The input pipeline uses this to surface
        collate/H2D latency and starvation counters measured off the
        tracer's thread — values land as ordinary CSV rows."""
        if not self.enabled:
            return
        self.totals[name] = self.totals.get(name, 0.0) + value
        self.counts[name] = self.counts.get(name, 0) + 1
        self.mins[name] = min(self.mins.get(name, value), value)
        self.maxs[name] = max(self.maxs.get(name, value), value)

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        # Clear the measurements, NOT the switch: re-running __init__
        # wholesale silently re-enabled a tracer the caller had
        # explicitly disabled (reset-between-phases is the normal
        # workflow; re-enabling is an explicit enable()).
        enabled = self.enabled
        self.__init__()
        self.enabled = enabled

    def save_csv(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(
                ["region", "count", "total_s", "min_s", "max_s", "avg_s"]
            )
            for k in sorted(self.totals):
                c = self.counts[k]
                w.writerow(
                    [
                        k,
                        c,
                        f"{self.totals[k]:.6f}",
                        f"{self.mins[k]:.6f}",
                        f"{self.maxs[k]:.6f}",
                        f"{self.totals[k] / max(c, 1):.6f}",
                    ]
                )


_JAX_TRACE_ACTIVE = False  # one jax.profiler trace at a time
_TRACE_STEP_BUDGET: Optional[int] = None  # dispatches left in window
_NULL_CTX = contextlib.nullcontext()  # the shared reusable no-op context


def _start_jax_trace(trace_dir: str, python_tracer: bool = False) -> bool:
    """THE one place the package starts a jax.profiler capture. The
    Python tracer is off unless asked for (``Training.Profiling.
    python_tracer``): jax's default, on, traces every Python call and
    stretched a 16.7 s epoch of this host-heavy loop to 27.5 s on the
    v5e (PERF.md, PR 26). The host tracer stays at 2, which keeps the
    TraceMe spans ``span``/``region``/``step_annotation`` write."""
    global _JAX_TRACE_ACTIVE
    if _JAX_TRACE_ACTIVE:
        return False
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 1 if python_tracer else 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    _JAX_TRACE_ACTIVE = True
    return True


def _stop_jax_trace() -> None:
    global _JAX_TRACE_ACTIVE, _TRACE_STEP_BUDGET
    if _JAX_TRACE_ACTIVE:
        import jax

        jax.profiler.stop_trace()
        _JAX_TRACE_ACTIVE = False
    _TRACE_STEP_BUDGET = None


def jax_trace_active() -> bool:
    """True while a jax.profiler capture started HERE (Profiler) is
    live — the epoch loop's cheap per-step gate for StepTraceAnnotation
    metadata: profiling off costs one module-global read per dispatch,
    nothing else."""
    return _JAX_TRACE_ACTIVE


def set_trace_step_budget(steps: Optional[int]) -> None:
    """Bound the live capture window to ``steps`` dispatches (None =
    epoch-gated only). ``note_trace_step`` decrements and stops the
    trace when the budget is spent — ``Training.Profiling.steps``."""
    global _TRACE_STEP_BUDGET
    _TRACE_STEP_BUDGET = int(steps) if steps else None


def note_trace_step() -> None:
    """Advance the capture window by one dispatch; stops the trace
    (and logs the window's close into the telemetry stream) when the
    step budget runs out. No-op when no trace or no budget is live."""
    global _TRACE_STEP_BUDGET
    if not _JAX_TRACE_ACTIVE or _TRACE_STEP_BUDGET is None:
        return
    _TRACE_STEP_BUDGET -= 1
    if _TRACE_STEP_BUDGET <= 0:
        _stop_jax_trace()
        _emit_profile_row("stop", reason="step_budget")


def step_annotation(region: str, step: int, **meta):
    """``jax.profiler.StepTraceAnnotation`` carrying step/spec/k
    metadata while a capture is live, else a shared reusable no-op
    context — so per-dispatch trace annotation costs nothing when
    profiling is off, and the captured timeline aligns device ops to
    the loop's own step numbering when it is on."""
    if not _JAX_TRACE_ACTIVE:
        return _NULL_CTX
    import jax

    return jax.profiler.StepTraceAnnotation(
        region, step_num=int(step), **meta
    )


# The vocabulary of device scopes (docs/OBSERVABILITY.md "Profiler
# alignment"; read by benchmarks/scopes.py). ``segment`` carries the
# primitive after a slash: ``segment/sum``, ``segment/softmax``, ...
SCOPES = (
    "edge_geometry",
    "edge_aggregate",
    "segment",
    "pool",
    "loss",
    "forces",
    "optimizer",
    "guard",
    "triplet",
    "triplet_basis",
    "vector_message",
    "vector_update",
)


def scope(name: str):
    """``jax.named_scope(name)`` for a name of ``SCOPES``: the ops
    traced inside carry ``.../<name>/...`` in their HLO ``op_name``.
    Metadata only — nothing runs on the device or per step for it. A
    model that opens ``edge_aggregate`` around a helper that opens it
    too reads ``edge_aggregate/edge_aggregate``; readers fold the
    repeat (benchmarks/scopes.py)."""
    if name.split("/", 1)[0] not in SCOPES:
        raise ValueError(f"scope {name!r} is not in tracer.SCOPES")
    import jax

    return jax.named_scope(name)


def scoped(name: str) -> Callable:
    """Decorator: trace the function's body under ``scope(name)``."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*a, **kw):
            with scope(name):
                return fn(*a, **kw)

        return wrapped

    return deco


def span(name: str):
    """A host span on the profiler's clock: ``TraceAnnotation(name)``
    while a capture started here is live, else the shared no-op
    context (one module-global read). Safe on any thread. No span's
    name may end in ``_step``: that suffix marks the loop's thread
    (``step_annotation``) for the trace's readers."""
    if not _JAX_TRACE_ACTIVE:
        return _NULL_CTX
    import jax

    return jax.profiler.TraceAnnotation(name)


class _Region:
    """An entered ``region``: tracers outside, the profiler span inside."""

    __slots__ = ("name", "ann")

    def __init__(self, name: str, ann) -> None:
        self.name = name
        self.ann = ann

    def __enter__(self) -> None:
        start(self.name)
        self.ann.__enter__()

    def __exit__(self, *exc) -> bool:
        self.ann.__exit__(*exc)
        stop(self.name)
        return False


def region(name: str, annotate: bool = True):
    """One context manager per named site of host work: drives the
    installed tracers as a ``start``/``stop`` pair does and, with
    ``annotate``, is a ``span`` as well. Off — no tracer installed, no
    live capture — it is the shared no-op context, with no allocation.
    The loop's thread only (``RegionTimer`` keeps one stack); other
    threads take ``span``. ``annotate=False`` is for the dispatch
    site, which ``step_annotation`` already puts on the trace."""
    ann = span(name) if annotate else _NULL_CTX
    if not _TRACERS:
        return ann
    return _Region(name, ann)


def _emit_profile_row(event: str, **kw) -> None:
    """Log the capture window into the telemetry stream (when one is
    active) so run reports can point at the trace dir and say which
    steps it covers. Lazy import: tracer must stay importable without
    the telemetry subsystem in play."""
    try:
        from hydragnn_tpu.utils import telemetry

        telemetry.emit({"t": "profile", "event": event, **kw})
    except Exception:
        pass


def initialize(
    trlist: Optional[List[str]] = None, verbose: bool = False, **kwargs
) -> None:
    """Install tracers (reference tracer.py:368-381). Keyword args are
    forwarded only to the tracers whose constructors accept them."""
    import inspect

    classes = {"RegionTimer": RegionTimer}
    for name in trlist or ["RegionTimer"]:
        cls = classes[name]
        accepted = set(inspect.signature(cls.__init__).parameters)
        kw = {k: v for k, v in kwargs.items() if k in accepted}
        try:
            _TRACERS[name] = cls(**kw)
        except Exception as e:  # pragma: no cover
            if verbose:
                print("tracer loading error:", name, e)


def has(name: str) -> bool:
    return name in _TRACERS


def _device_sync() -> None:
    import jax

    # graftlint: disable-next-line=host-sync -- this IS the sync barrier: opt-in (sync=True) fence so region timers measure device completion
    (jax.device_put(0.0) + 0).block_until_ready()


def start(name: str, sync: bool = False) -> None:
    if sync:
        _device_sync()
    for tr in _TRACERS.values():
        tr.start(name)


def stop(name: str, sync: bool = False) -> None:
    if sync:
        _device_sync()
    for tr in _TRACERS.values():
        tr.stop(name)


def sample(name: str, value: float) -> None:
    """Record one observation of ``name`` on every tracer that supports
    value samples (RegionTimer) — the entry point for asynchronous
    producers (the input pipeline) whose measurements can't bracket a
    start/stop pair on this thread."""
    for tr in _TRACERS.values():
        add = getattr(tr, "add_sample", None)
        if add is not None:
            add(name, value)


def profile(name: str, sync: bool = False) -> Callable:
    """Decorator timing every call (reference @tr.profile)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*a, **kw):
            start(name, sync=sync)
            try:
                return fn(*a, **kw)
            finally:
                stop(name, sync=sync)

        return wrapped

    return deco


def enable() -> None:
    for tr in _TRACERS.values():
        tr.enable()


def disable() -> None:
    for tr in _TRACERS.values():
        tr.disable()


def reset() -> None:
    for tr in _TRACERS.values():
        tr.reset()


def save(log_name: str) -> None:
    """Per-process CSV dump (reference tracer.py:432-458)."""
    import jax

    rank = jax.process_index() if jax.process_count() > 1 else 0
    if has("RegionTimer"):
        _TRACERS["RegionTimer"].save_csv(
            os.path.join("logs", log_name, f"timing.p{rank}.csv")
        )


class Profiler:
    """Epoch-gated jax.profiler trace (reference Profile wrapper,
    profiling_and_tracing/profile.py:9-70: config section ``Profile``
    with enable + target epoch; traces land in a TensorBoard dir).

    Preferred config is the ``Training.Profiling {enabled, epoch,
    steps, trace_dir, python_tracer}`` block (docs/OBSERVABILITY.md
    "Profiler alignment"): capture epoch ``epoch``, optionally bounded
    to the first ``steps`` dispatches (a steady-state window small
    enough to open in TensorBoard; 0 = whole epoch), with the Python
    tracer off unless ``python_tracer`` is true. While the capture is
    live the epoch loop wraps every dispatch in a
    ``StepTraceAnnotation`` carrying step/spec/k metadata
    (``step_annotation``) and its host work in ``region`` spans, and
    the window's start/stop land in the telemetry stream as ``profile``
    rows so graftboard reports can point at the trace. The legacy
    top-level ``Profile {enable, target_epoch, trace_dir}`` section
    keeps working unchanged."""

    def __init__(self, config: Optional[dict] = None) -> None:
        config = config or {}
        pcfg = (
            config.get("NeuralNetwork", {})
            .get("Training", {})
            .get("Profiling")
        ) or {}
        if pcfg:
            self.enabled = bool(pcfg.get("enabled", True))
            self.target_epoch = int(pcfg.get("epoch", 0))
            self.steps = max(0, int(pcfg.get("steps", 0)))
            self.trace_dir = pcfg.get("trace_dir", "logs/jax_trace")
            self.python_tracer = bool(pcfg.get("python_tracer", False))
        else:
            cfg = config.get("Profile", {})
            self.enabled = bool(cfg.get("enable", 0))
            self.target_epoch = int(cfg.get("target_epoch", 0))
            self.steps = 0
            self.trace_dir = cfg.get("trace_dir", "logs/jax_trace")
            self.python_tracer = False
        self._active = False

    def on_epoch_start(self, epoch: int) -> None:
        if self.enabled and epoch == self.target_epoch:
            self._active = _start_jax_trace(
                self.trace_dir, self.python_tracer
            )
            if self._active:
                set_trace_step_budget(self.steps or None)
                _emit_profile_row(
                    "start",
                    epoch=epoch,
                    trace_dir=self.trace_dir,
                    steps=self.steps or None,
                )

    def on_epoch_end(self, epoch: int) -> None:
        if self._active:
            # The step budget may have closed the window mid-epoch
            # (note_trace_step logged the stop); only a still-live
            # trace stops — and logs — here.
            if _JAX_TRACE_ACTIVE:
                _stop_jax_trace()
                _emit_profile_row("stop", epoch=epoch, reason="epoch_end")
            self._active = False
