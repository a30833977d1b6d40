#!/usr/bin/env python3
"""Readings for the limits of ``correct``: many seeds in one process.

    python3 benchmarks/readings.py --workload <cell> --seeds 1,2,3
        [--faults 3] [--looks 3] [--control-seeds 7,8,9] [--rehearse] [--leaves]

For each seed: generate the data, start the cell's ``run_training`` call,
take the dispatches of epoch 0 that ``correct`` compares (the run is stopped
there: these readings need no measured window), follow them with the plain
reference and print the numbers compared, each run's verdict under the
cell's own limits beside them. Three kinds of row:

* ``sound``: the program as the configuration states it (the lower
  reading is the largest over these);
* ``fault:<name>``, on the first ``--faults`` seeds: the reference with the
  fault planted (checks/<driver>.py ``FAULTS``: half of every batch left
  out, the mean taken over the rest), put in the program's place;
* ``look:<name>@<precision>``, on the first ``--looks`` seeds: the
  reference against itself on the same graphs in another order (``LOOKS``),
  at the stated matmul precision and at ``highest``: how far round-off
  alone carries over the dispatch's steps, no program involved;
* ``control``, on ``--control-seeds``: the program's own lower-precision
  path switched on (checks/<driver>.py ``CONTROL``). Control and faults
  have to come out as not correct.

Prints one JSON line per row and a summary. Not part of a benchmark run; on
the chip it needs the TPU like run.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--faults", type=int, default=0,
                    help="plant each fault on the first N of --seeds")
    ap.add_argument("--looks", type=int, default=0,
                    help="make each look on the first N of --seeds")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--highest", action="store_true",
                    help="also compare with the reference at 'highest' "
                         "matmul precision (numbers named hi.*): how far "
                         "the stated arithmetic itself lies from float32")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--leaves", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks import check, harness, spec

    cell = spec.cell(args.workload, rehearse=args.rehearse)
    import jax

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    if not args.rehearse and dev.platform != "tpu":
        print("readings.py: not a TPU", file=sys.stderr)
        return 4
    from hydragnn_tpu.utils.runtime import maybe_enable_compilation_cache

    maybe_enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    kind = cell["traffic"]["driver"]
    driver = spec.load_module("drivers", kind)
    checker = spec.load_module("checks", kind)
    limits = cell["extras"]["limits"]
    seen = {}

    def show(row_kind, seed, numbers, detail=None):
        lists = {k: v for k, v in numbers.items() if isinstance(v, list)}
        numbers = {k: v for k, v in numbers.items() if k not in lists}
        detail = dict(detail or {}, **lists)
        ok, _ = check.verdict(numbers, limits)
        print(json.dumps({"kind": row_kind, "seed": seed, "correct": ok,
                          **numbers, "detail": detail or {}}), flush=True)
        seen.setdefault(row_kind, []).append(dict(numbers, correct=ok))

    def capture(seed, **driver_kw):
        return driver.run(
            cell, seed=seed, seconds=0, trace=False,
            work=os.path.join(harness.WORK, cell["name"] + ".readings"),
            log=lambda m: None, stop_after_capture=True, **driver_kw,
        )

    for i, seed in enumerate(_seeds(args.seeds)):
        facts = capture(seed)
        numbers, detail = checker.compared(cell, facts, leaves=args.leaves)
        if args.highest:
            hi, _ = checker.compared(cell, facts, precision="highest")
            numbers.update({f"hi.{k}": v for k, v in hi.items()})
        show("sound", seed, numbers, detail)
        if i < args.faults:
            for fault in checker.FAULTS:
                show(f"fault:{fault}", seed,
                     checker.fault_numbers(cell, facts, fault))
        if i < args.looks:
            stated = cell["config"].get("matmul_precision", "highest")
            for look in checker.LOOKS:
                for precision in dict.fromkeys((stated, "highest")):
                    show(f"look:{look}@{precision}", seed,
                         checker.fault_numbers(
                             cell, facts, look, precision=precision))
    for seed in _seeds(args.control_seeds):
        facts = capture(seed, **checker.CONTROL)
        show("control", seed, *checker.compared(cell, facts))

    for row_kind, rows in seen.items():
        print(f"{row_kind}: correct on {sum(r['correct'] for r in rows)} of "
              f"{len(rows)} seeds under the cell's limits")
        for name in rows[0]:
            if name == "correct":
                continue
            values = sorted(r[name] for r in rows)
            print(f"  {name}: min {values[0]:.3e} median "
                  f"{values[len(values) // 2]:.3e} max {values[-1]:.3e} "
                  f"limit {limits.get(name)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
