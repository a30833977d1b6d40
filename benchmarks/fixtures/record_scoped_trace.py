#!/usr/bin/env python3
"""Record the small scoped trace that benchmarks/tests/test_scopes.py reads.

Run once on the chip (it needs a TPU): a tiny message-passing step with two
named scopes of the program's vocabulary (``edge_aggregate``, ``loss``) and
a third around its update (``optimizer``), differentiated with
``value_and_grad`` so that real ``transpose(`` paths exist, jitted once as
``train_step`` and once as ``train_superstep`` (a ``lax.scan`` of K=2, so
that a real ``while`` encloses its body's ops), three annotated dispatches.
Writes ``scoped_trace.xplane.pb`` and, beside it,
``scoped_trace.expected.json``: the table of benchmarks/scopes.py worked out
by a second, slower method (every op's self time by subtracting, pair by
pair, the events it encloses; busy time by a sweep over interval edges).
``--expected`` works the numbers out again for the trace that is there;
that needs no TPU.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

N, E, F, K = 512, 8192, 128, 2


def write_expected(dst: str) -> dict:
    from benchmarks import scopes, trace
    from benchmarks.fixtures.record_small_trace import sweep_busy

    planes = scopes.decode(dst)
    for row in trace.describe(
        {p: {l: [e[:3] for e in evs] for l, evs in lines.items()}
         for p, lines in planes.items()}
    ):
        print(row)
    (lines,) = [
        lines for name, lines in planes.items()
        if name.startswith(trace.DEVICE_PREFIX) and lines.get(trace.OPS_LINE)
    ]
    ops = lines[trace.OPS_LINE]
    modules = lines[scopes.MODULES_LINE]
    table, with_tf_op = {}, 0.0
    for i, (_, a, b, tf_op) in enumerate(ops):
        # what it encloses, each counted once: children that are not
        # themselves inside another of its children
        inside = [
            (c, d) for j, (_, c, d, _) in enumerate(ops)
            if j != i and a <= c and d <= b and (c, d) != (a, b)
        ]
        covered = sweep_busy(inside)
        seconds = (b - a - covered) / 1e9
        (program,) = [
            scopes.program_of(name) for name, c, d, _ in modules if c <= a < d
        ] or ["no program"]
        scope, backward = scopes.scope_of(tf_op)
        row = table.setdefault(program, {}).setdefault(
            scope, {"fwd": 0.0, "bwd": 0.0}
        )
        row["bwd" if backward else "fwd"] += seconds
        with_tf_op += seconds if tf_op else 0.0
    expected = {
        "busy_s": sweep_busy([(a, b) for _, a, b, _ in ops]) / 1e9,
        "n_op_events": len(ops),
        "n_while_events": sum(1 for e in ops if " while(" in e[0]),
        "with_tf_op_s": with_tf_op,
        "modules": [scopes.program_of(m[0]) for m in sorted(modules, key=lambda m: m[1])],
        "programs": table,
        "tf_ops": sorted({e[3] for e in ops if e[3]}),
    }
    with open(dst.replace(".xplane.pb", ".expected.json"), "w") as fh:
        json.dump(expected, fh, indent=1)
    print(json.dumps({k: v for k, v in expected.items() if k != "tf_ops"}))
    return expected


def main() -> int:
    from benchmarks import trace

    here = os.path.dirname(os.path.abspath(__file__))
    if "--expected" in sys.argv:  # no TPU needed: the trace is there
        write_expected(os.path.join(here, "scoped_trace.xplane.pb"))
        return 0
    import jax
    import jax.numpy as jnp
    import numpy as np

    out_dir = sys.argv[1] if len(sys.argv) > 1 else here
    os.makedirs(out_dir, exist_ok=True)
    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace.py: not a TPU", file=sys.stderr)
        return 4

    rng = np.random.default_rng(0)
    senders = jnp.asarray(rng.integers(0, N, E), jnp.int32)
    receivers = jnp.asarray(np.sort(rng.integers(0, N, E)), jnp.int32)

    def loss_fn(params):
        w, h, filt = params
        with jax.named_scope("edge_aggregate"):
            msg = h[senders] * filt
            agg = jax.ops.segment_sum(msg, receivers, num_segments=N)
            out = agg @ w
        with jax.named_scope("loss"):
            return jnp.mean(out * out)

    def one_step(params, _):
        # every operand takes a gradient, so the gather's transpose (a
        # scatter) and the scatter's (a gather) are both in the trace
        loss, grads = jax.value_and_grad(loss_fn)(params)
        with jax.named_scope("optimizer"):
            params = jax.tree_util.tree_map(
                lambda p, g: p - 0.01 * g, params, grads
            )
        return params, loss

    @jax.jit
    def train_step(params):
        return one_step(params, None)

    @jax.jit
    def train_superstep(params):
        return jax.lax.scan(one_step, params, None, length=K)

    params = (
        jnp.eye(F, dtype=jnp.float32),
        jnp.asarray(rng.normal(size=(N, F)), jnp.float32),
        jnp.asarray(rng.normal(size=(E, F)), jnp.float32),
    )
    jax.block_until_ready(train_step(params))
    jax.block_until_ready(train_superstep(params))
    tmp = tempfile.mkdtemp(dir=out_dir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=options)
    for i in range(3):
        with jax.profiler.StepTraceAnnotation("train_step", step_num=i):
            step = train_step if i < 2 else train_superstep
            params, loss = step(params)
        with jax.profiler.TraceAnnotation("train/epoch_fetch"):
            jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    dst = os.path.join(out_dir, "scoped_trace.xplane.pb")
    shutil.copy(trace.find_xplane(tmp), dst)
    shutil.rmtree(tmp)
    write_expected(dst)
    print("size", os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
