"""Runtime helpers: walltime-aware early stop, device memory stats.

Counterparts of the reference's SLURM walltime probe
(hydragnn/utils/distributed/distributed.py:614-639 check_remaining:
rank-0 squeue query + broadcast stop decision, hooked at
train_validate_test.py:430-437) and print_peak_memory (:566-581).
"""

from __future__ import annotations

import os
import subprocess
import time
from typing import Optional


_COMPILE_CACHE_PATH: list = []
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".xla_cache")


def maybe_enable_compilation_cache() -> Optional[str]:
    """Persistent XLA compilation cache: jitted executables are
    serialized to disk and reloaded by later processes, so repeat runs
    of the same configs (bench invocations, HPO trials, resumed jobs)
    skip the TPU compiles. Returns the cache dir when one is live.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax itself reads it and
    that directory is the cache: nothing here touches the setting. Where
    it is not set and the platform is a TPU, the cache is the fixed
    ``<checkout>/.xla_cache`` (the path is part of the cache key, so a
    directory that moves never hits). On CPU it stays off: XLA:CPU
    entries are machine-feature-fingerprinted and reloading them on
    another host warns of a possible SIGILL. Idempotent.
    """
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if placed:
        return placed
    if jax.default_backend() != "tpu":
        return None
    path = _DEFAULT_CACHE_DIR
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # jax initializes its persistent-cache module AT MOST ONCE, on the
    # first compile — a process that already jitted anything before this
    # call has latched the cache as "initialized, disabled", and the
    # config update above alone would be silently ignored. Reset the
    # latch so the next compile re-initializes against the new dir
    # (skipped when this path is already live — a reset would only
    # discard the open cache handle).
    if path not in _COMPILE_CACHE_PATH:
        reset_compilation_cache()
        _COMPILE_CACHE_PATH.append(path)
    # Cache even fast compiles: HPO sweeps re-enter many small jits.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # ... but bound the disk footprint (LRU eviction) — an unpruned
    # repo-local cache would otherwise grow without limit across runs.
    jax.config.update("jax_compilation_cache_max_size", 4 * 1024**3)
    return path


def reset_compilation_cache() -> None:
    """Drop jax's latched persistent-cache state (and this module's
    record of the enabled dir) so the next compile re-initializes from
    the current config. The ONE copy of the reset grammar — used by
    ``maybe_enable_compilation_cache`` and by tests restoring pristine
    state."""
    from jax.experimental.compilation_cache import compilation_cache

    _COMPILE_CACHE_PATH.clear()
    compilation_cache.reset_cache()


def job_end_time() -> Optional[float]:
    """Epoch seconds when the job ends, from the environment.

    Sources, in order: HYDRAGNN_WALLCLOCK_DEADLINE (epoch seconds —
    works on any scheduler), SLURM_JOB_END_TIME (set by recent SLURM),
    else an squeue probe like the reference (only if SLURM_JOB_ID is
    set and squeue exists).
    """
    v = os.environ.get("HYDRAGNN_WALLCLOCK_DEADLINE")
    if v:
        return float(v)
    v = os.environ.get("SLURM_JOB_END_TIME")
    if v:
        return float(v)
    return _job_end_time_squeue()


_SQUEUE_CACHE: list = []


def _job_end_time_squeue() -> Optional[float]:
    """squeue probe, done ONCE per process (subprocess per epoch would
    be wasteful and, worse, nondeterministic across processes)."""
    if _SQUEUE_CACHE:
        return _SQUEUE_CACHE[0]
    _SQUEUE_CACHE.append(None)
    job = os.environ.get("SLURM_JOB_ID")
    if job:
        try:
            out = subprocess.run(
                ["squeue", "-h", "-j", job, "-O", "TimeLeft"],
                capture_output=True,
                text=True,
                timeout=10,
            ).stdout.strip()
            if out:
                parts = out.split("-")
                days = int(parts[0]) if len(parts) == 2 else 0
                hms = parts[-1].split(":")
                hms = [0] * (3 - len(hms)) + [int(x) for x in hms]
                left = days * 86400 + hms[0] * 3600 + hms[1] * 60 + hms[2]
                _SQUEUE_CACHE[0] = time.time() + left
        except Exception:
            pass
    return _SQUEUE_CACHE[0]


def check_remaining(min_seconds_left: float = 300.0) -> bool:
    """True when training may continue; False when the job is within
    ``min_seconds_left`` of its walltime (stop + checkpoint now).

    The env-var paths are deterministic across processes; the cached
    squeue path is not, so in multi-host jobs process 0's decision is
    broadcast (the reference's rank-0 squeue + MPI bcast,
    distributed.py:614-639) — every host then breaks out of the epoch
    loop together instead of deadlocking in the next collective. The
    broadcast rides the COORDINATION SERVICE's KV store, not an XLA
    collective: a once-per-epoch scalar must not queue device work
    behind the step stream (and some backends cannot run multi-process
    XLA computations at all).
    """
    import jax

    end = job_end_time()
    ok = end is None or (end - time.time()) > min_seconds_left
    if jax.process_count() > 1:
        from hydragnn_tpu.utils import telemetry
        from hydragnn_tpu.utils.checkpoint import _barrier_seq, _dist_client

        client = _dist_client()
        # graftlint: disable-next-line=barrier-discipline -- the walltime broadcast runs in lockstep once per epoch from the epoch loop (every process reaches it the same number of times); a failure mid-broadcast aborts the run, never desyncs a later one
        seq = _barrier_seq("walltime")
        key = f"hgtpu_walltime/{seq}"
        # The once-per-epoch KV broadcast is a coordination wait like
        # any barrier: attribute it (a process stuck here is waiting
        # on process 0's decision — docs/OBSERVABILITY.md "Fleet
        # observability").
        with telemetry.waiting_on("walltime"):
            t0 = time.perf_counter()
            try:
                if jax.process_index() == 0:
                    client.key_value_set(key, "1" if ok else "0")
                ok = client.blocking_key_value_get(key, 600_000) == "1"
            except BaseException:
                # A broadcast that raised (process 0 died) must still
                # reach the shard — same contract as _process_barrier.
                telemetry.emit_barrier(
                    "walltime",
                    seq,
                    time.perf_counter() - t0,
                    timed_out=True,
                    broadcast=True,
                )
                raise
            dt = time.perf_counter() - t0
        # broadcast=True: a KV set/get is ASYMMETRIC (only processes
        # arriving before process 0's set wait; late arrivers read
        # instantly), so rendezvous last-arriver attribution would
        # blame an innocent late reader — graftboard reports the
        # waits but skips attribution for this site.
        telemetry.emit_barrier("walltime", seq, dt, broadcast=True)
    return ok


def memory_stats() -> dict:
    """Per-device memory stats (bytes) when the backend reports them
    (TPU runtime does; CPU returns {}). Reference print_peak_memory.

    Hardened for telemetry use (docs/OBSERVABILITY.md ``memory``
    rows): a backend whose ``memory_stats()`` RAISES (a device
    mid-teardown, non-addressable devices in multi-host
    meshes) or reports only a subset of the allocator keys degrades to
    a partial/empty dict — live memory telemetry must never be able
    to kill a run. Only keys the allocator actually reported appear
    (absent != 0)."""
    try:
        import jax

        devices = jax.devices()
    except Exception:
        return {}
    out = {}
    for d in devices:
        try:
            stats = getattr(d, "memory_stats", None)
            s = stats() if callable(stats) else None
        except Exception:
            continue  # a device mid-teardown raises instead of returning None
        if not s:
            continue
        entry = {}
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            try:
                v = s.get(key)
            except Exception:
                break  # non-mapping stats object: nothing trustworthy
            if v is not None:
                entry[key] = v
        if entry:
            out[str(d)] = entry
    return out


def host_memory() -> dict:
    """Host-process memory (bytes): ``host_rss_bytes`` (current, from
    /proc/self/statm) and ``host_peak_rss_bytes`` (ru_maxrss). Partial
    on platforms without either source — same degrade-don't-raise
    posture as ``memory_stats`` (the telemetry ``memory`` rows fold
    this in next to the device allocator numbers so a host-side leak
    — loader caches, checkpoint snapshots — is visible in the same
    stream)."""
    out = {}
    try:
        import resource

        peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        out["host_peak_rss_bytes"] = int(peak_kb) * 1024  # linux: KiB
    except Exception:
        pass
    try:
        with open("/proc/self/statm") as f:
            rss_pages = int(f.read().split()[1])
        out["host_rss_bytes"] = rss_pages * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        pass
    return out


def print_peak_memory(verbosity_fn=print) -> None:
    for dev, s in memory_stats().items():
        peak = s.get("peak_bytes_in_use")
        lim = s.get("bytes_limit")
        if peak is not None:
            msg = f"{dev}: peak memory {peak / 2**30:.2f} GiB"
            if lim:
                msg += f" / {lim / 2**30:.2f} GiB"
            verbosity_fn(msg)
