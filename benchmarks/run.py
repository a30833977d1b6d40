#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time. It fails (non-zero, no result line) when jax finds
no TPU, a device kind that benchmarks/peaks.json does not hold, or another
number of devices than the cell's ``chips``. The last line of standard
output is the result, one JSON object.

``--rehearse`` walks the same code on the CPU at the cell's own tiny
``rehearsal`` size: it prints no result line and exits 3. A rehearsal is
not a chip run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(REPO, "hydragnn_tpu")):
        print("benchmarks/run.py: no hydragnn_tpu package beside "
              "benchmarks/: nothing to measure", file=sys.stderr)
        return 4

    from benchmarks import harness, spec

    cell = spec.cell(args.workload, rehearse=args.rehearse)

    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    harness.mark("backend")
    harness.log(f"device: {json.dumps(device)}; jax {jax.__version__}")
    if not args.rehearse:
        if device["platform"] != "tpu":
            print(f"benchmarks/run.py: platform is {device['platform']!r}, "
                  "not a TPU", file=sys.stderr)
            return 4
        spec.peaks(device["kind"])  # raises for a kind without peaks
    if device["count"] != cell["chips"]:
        print(f"benchmarks/run.py: the cell needs {cell['chips']} device(s), "
              f"jax sees {device['count']}", file=sys.stderr)
        return 4

    from hydragnn_tpu.utils.runtime import maybe_enable_compilation_cache

    harness.log(f"compile cache: {maybe_enable_compilation_cache() or 'off'}")
    # every program into the cache, also the ~100 that compile in under a
    # second: where JAX_COMPILATION_CACHE_DIR is placed the program leaves
    # jax's one-second threshold alone, and a warm run then compiles them
    # all again (13 s of set-up, my chip run 1, PR 26)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = harness.execute(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        device=device, t_start=T_START,
    )
    if args.rehearse:
        harness.log(f"rehearsal result (not printed as a result line): "
                    f"{json.dumps(result)[:3000]}")
        harness.log("rehearsal walked the run; not a chip run, no result")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # no interpreter shutdown: after a traced run it hung for 17 minutes in
    # Py_FinalizeEx (my chip run 1, PR 26). The run starts no process, and
    # everything it wrote is flushed and closed by now.
    os._exit(rc)
