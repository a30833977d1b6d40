"""One run of one cell, after the device has been checked: drive the cell's
driver, read the device, let the driver's checker compare the timed path
with the plain reference, hold each number to the cell's limit, evaluate the
metrics BENCHMARK.json lists for the cell, build the result. Nothing here
knows what kind of cell it runs.
"""

from __future__ import annotations

import json
import os
import sys
import time

from benchmarks import check, spec, trace as trace_mod

WORK = os.path.join(spec.REPO, ".bench_work")


MARKS = []  # (name, perf_counter): where the set-up's time went


def log(msg: str) -> None:
    print(msg, flush=True)


def mark(name: str) -> None:
    MARKS.append((name, time.perf_counter()))


class RunInfo:
    """What a metric reader may read. Readers return None where they find
    nothing to read; the harness then leaves the metric out."""

    def __init__(self, cell, driver, facts, device, setup_s):
        self.cell = cell
        self.driver = driver  # its helpers read the driver's own artefacts
        self.facts = facts
        self.device = device
        self.setup_s = setup_s
        self._trace = False

    @property
    def peaks(self):
        """The chip's peaks; None off the TPU (a rehearsal), so that no CPU
        number is ever set against a chip's peak."""
        if self.device["platform"] != "tpu":
            return None
        return spec.peaks(self.device["kind"])

    def trace(self):
        """The reduced device trace, or None (no trace, no device plane)."""
        if self._trace is False:
            self._trace = None
            tdir = self.facts.get("trace_dir")
            path = trace_mod.find_xplane(tdir) if tdir else None
            if path:
                t0 = time.perf_counter()
                self._trace = trace_mod.reduce(
                    trace_mod.planes_of(trace_mod.load(path))
                )
                log(f"trace {path} ({os.path.getsize(path)} bytes) reduced "
                    f"in {time.perf_counter() - t0:.1f}s")
        return self._trace


def memory_peak_bytes() -> int:
    """The fullest device's peak. The TPU runtime keeps two books: buffers
    (arguments, outputs, batches in flight) are "in use"; a running
    program's temporaries are "reserved" (8.13 GB reserved against 2.74 GB
    in use in schnet_qm9.train, the former equal to what memory_analysis()
    gives for the compiled step; my chip run 2, PR 26). Both are held while
    a program runs, so the peak is their sum."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    log(f"device memory: {stats}")
    return int(max(
        s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
        for s in stats
    ))


def execute(
    cell: dict,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    device: dict,
    t_start: float,
    **driver_kw,
) -> dict:
    """Run the cell once and return the result line as a dict.

    The cell's driver, ``drivers/<driver>.py``, runs the window:
    ``run(cell, seed, seconds, trace, work, log, mark) -> facts`` with
    ``t_open`` (perf_counter at the window's start), ``window_s``,
    ``attempted``, ``failed``, ``notes`` (lines for the log) and, where it
    has them, ``marks`` and ``trace_dir``; whatever else it puts there is
    for its checker and its metric readers. The driver has freed the
    program's state when it returns. ``checks/<driver>.py`` then gives the
    numbers to compare: ``compared(cell, facts) -> (numbers, detail)``."""
    kind = cell["traffic"]["driver"]
    driver = spec.load_module("drivers", kind)
    work = os.path.join(WORK, cell["name"])
    facts = driver.run(
        cell, seed=seed, seconds=seconds, trace=trace, work=work, log=log,
        mark=mark, **driver_kw,
    )
    setup_s = facts["t_open"] - t_start
    stamps = [("start", t_start)] + MARKS + list(facts.get("marks", [])) + [
        ("window_open", facts["t_open"])
    ]
    log("set-up phases (s): " + ", ".join(
        f"{name} +{b - a:.1f}"
        for (_, a), (name, b) in zip(stamps[:-1], stamps[1:])
    ))
    log(f"set-up {setup_s:.2f}s")
    for line in facts.get("notes", []):
        log(line)
    device = dict(device, memory_peak_bytes=memory_peak_bytes())

    t_ref = time.perf_counter()
    numbers, detail = spec.load_module("checks", kind).compared(cell, facts)
    correct, rows = check.verdict(numbers, cell["extras"]["limits"])
    log(f"reference followed the timed path in "
        f"{time.perf_counter() - t_ref:.1f}s: {json.dumps(detail)}")

    run = RunInfo(cell, driver, facts, device, setup_s)
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_for(cell["bench"], cell["name"], section):
        value = spec.load_module("metrics", m["name"]).compute(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {
        "correct": bool(correct),
        "attempted": int(facts["attempted"]),
        "failed": int(facts["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        reduced = run.trace()
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
    result["compared"] = {name: {"value": v, "limit": lim} for name, v, lim in rows}
    for name, value, limit in rows:
        print(f"compared {name}: {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    return result
