#!/usr/bin/env python3
"""Record the small device trace that benchmarks/selfcheck.py reduces.

Run once on the chip (it needs a TPU): a few annotated steps of a small
jitted function under ``jax.profiler``. Writes ``small_trace.xplane.pb``
and, beside it, ``small_trace.expected.json``: the busy union, the window
and the op totals worked out here by a second, slower method (a sweep over
every interval edge), which selfcheck holds benchmarks/trace.py to.
``--expected`` works the numbers out again for the trace that is there
(after a change to how ops are named); that needs no TPU.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def sweep_busy(intervals) -> float:
    """Length of the union of intervals, by counting open intervals at
    every edge (not the merge benchmarks/trace.py uses)."""
    edges = sorted([(a, 1) for a, _ in intervals] + [(b, -1) for _, b in intervals])
    busy, depth, last = 0.0, 0, None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def write_expected(dst: str) -> dict:
    """The numbers of the recorded trace by the second method, beside it."""
    from benchmarks import trace

    planes = trace.planes_of(trace.load(dst))
    for row in trace.describe(planes):
        print(row)
    ops = [
        ev for name, lines in planes.items()
        if name.startswith(trace.DEVICE_PREFIX)
        for ev in lines.get(trace.OPS_LINE, [])
    ]
    totals = {}
    for name, a, b in ops:  # no op of this trace encloses another
        name = trace.short_name(name)
        totals[name] = totals.get(name, 0.0) + (b - a) / 1e9
    host = [
        ev for name, lines in planes.items()
        if name.startswith(trace.HOST_PREFIX)
        for evs in lines.values() for ev in evs
        if ev[0].endswith(trace.STEP_SUFFIX)
    ]
    edges = [t for _, a, b in ops + host for t in (a, b)]
    expected = {
        "busy_s": sweep_busy([(a, b) for _, a, b in ops]) / 1e9,
        "window_s": (max(edges) - min(edges)) / 1e9,
        "n_op_events": len(ops),
        "op_totals_s": totals,
    }
    with open(dst.replace(".xplane.pb", ".expected.json"), "w") as fh:
        json.dump(expected, fh, indent=1)
    print(json.dumps(expected))
    return expected


def main() -> int:
    from benchmarks import trace

    here = os.path.dirname(os.path.abspath(__file__))
    if "--expected" in sys.argv:  # no TPU needed: the trace is there
        write_expected(os.path.join(here, "small_trace.xplane.pb"))
        return 0
    import jax
    import jax.numpy as jnp

    out_dir = sys.argv[1] if len(sys.argv) > 1 else here
    os.makedirs(out_dir, exist_ok=True)
    if jax.devices()[0].platform != "tpu":
        print("record_small_trace.py: not a TPU", file=sys.stderr)
        return 4

    @jax.jit
    def step(x, w):
        return jnp.tanh(x @ w) * 0.5 + x

    x = jnp.ones((256, 256), jnp.float32)
    w = jnp.ones((256, 256), jnp.float32) * 0.01
    step(x, w).block_until_ready()
    tmp = tempfile.mkdtemp(dir=out_dir)
    jax.profiler.start_trace(tmp)
    for i in range(3):
        with jax.profiler.StepTraceAnnotation("train_step", step_num=i):
            x = step(x, w)
        x.block_until_ready()
    jax.profiler.stop_trace()
    dst = os.path.join(out_dir, "small_trace.xplane.pb")
    shutil.copy(trace.find_xplane(tmp), dst)
    shutil.rmtree(tmp)
    write_expected(dst)
    print("size", os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
