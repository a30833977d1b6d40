"""host-sync: device-to-host synchronization inside the training hot
path.

Every ``.item()``, ``float()``-on-device-value, ``np.asarray``,
``jax.device_get`` or ``.block_until_ready()`` between steps drains the
device dispatch queue: the accelerator idles until the host catches up,
which shows up as an unexplained throughput cliff on long runs (the
reference implementation pays a per-batch ``.item()`` —
train_validate_test.py:749 — that this codebase's epoch loop explicitly
amortizes to ONE fetch per epoch).

Scope = the union of
- every jit-compiled function (where ``np.asarray``/``jax.device_get``
  is additionally a trace-time error), and
- everything statically reachable from ``train/loop.py``'s
  ``_run_epoch`` — the per-batch step path (dynamic ``step_fn``
  dispatch is covered by the jitted seed set), and
- ``train/loop.py``'s ``make_superstep_fn`` INCLUDING its nested defs:
  the ``lax.scan`` body is handed to scan as a value (no static call
  edge exists), yet it runs K times per dispatch inside the hottest
  jitted region of all — a stray ``.item()`` there would fence every
  superstep. Hot seeds therefore pull in every function NESTED under
  them (callbacks passed to scan/jit are exactly where hot-path code
  hides from the name-based callgraph).

Flagged in that scope: ``x.item()``, ``jax.device_get(...)``,
``jax.block_until_ready(...)``, ``x.block_until_ready()``, and — in
TRACED context only (jitted bodies plus helpers reachable from them,
which jit inlines into the trace), where it is a hard trace error
rather than a judgment call — ``np.asarray(...)`` / ``np.array(...)``.

Intentional syncs — the once-per-epoch metric fetch, trace-mode
barriers — carry ``# graftlint: disable=host-sync -- why`` comments;
that is the designed workflow, not an exception to it.
"""

from __future__ import annotations

import ast
from typing import Iterable, Set

from hydragnn_tpu.analysis.callgraph import (
    module_env,
    own_statements,
    seed_scope,
)
from hydragnn_tpu.analysis.engine import Finding, LintContext, Rule

HOT_SEEDS = (
    ("train/loop.py", "_run_epoch"),
    # The single-step builders (ISSUE 12, found by the hot-coverage
    # ratchet): their jitted closures dispatch once per batch on the
    # non-superstep path — the original hot path of all, covered since
    # PR 2 only via _run_epoch's dynamic step_fn (which the name-based
    # callgraph cannot follow). Seeding the builders makes the nested
    # jitted steps hot directly.
    ("train/loop.py", "make_train_step"),
    ("train/loop.py", "make_eval_step"),
    ("parallel/dp.py", "make_dp_train_step"),
    ("parallel/dp.py", "make_dp_eval_step"),
    ("parallel/multibranch.py", "make_multibranch_train_step"),
    # The superstep executors: their scan bodies/closures are nested
    # defs passed BY VALUE to lax.scan / jax.jit, invisible to the
    # name-based call edges — the nested-def expansion below makes
    # them hot. The dp variant scans the pjit'ed data-parallel step
    # (K*D batches per dispatch: the hottest region of all).
    ("train/loop.py", "make_superstep_fn"),
    ("parallel/dp.py", "make_dp_superstep_fn"),
    # The dp epoch drivers: DPLoader's grouped/plain iterators run
    # between every step dispatch (host-side stacking + sharded
    # device_put) — a stray sync there stalls the whole data axis.
    ("parallel/dp.py", "DPLoader.__iter__"),
    ("parallel/dp.py", "DPLoader._iter_superstep"),
    # The multibranch epoch driver + its plan-domain resume cursor
    # (ISSUE 13): the stacked-batch iterator runs between every step
    # dispatch, and skip_to's per-slot epoch_plan replay runs inside a
    # resumed epoch's first fetch — spec arithmetic only, nothing may
    # touch the device.
    ("parallel/multibranch.py", "MultiBranchLoader.__iter__"),
    ("parallel/multibranch.py", "MultiBranchLoader.skip_to"),
    # The async checkpoint path (docs/DURABILITY.md): save() runs on
    # the CALLER thread between optimizer steps — its only permitted
    # sync is the designed snapshot barrier (suppressed in place); the
    # background worker must only ever touch host-materialized trees —
    # a device access there re-serializes against the training stream
    # the whole writer exists to stay off of.
    ("utils/checkpoint.py", "CheckpointWriter.save"),
    ("utils/checkpoint.py", "CheckpointWriter._worker_main"),
    # The mid-epoch resume fast-forward: skip_to + the plan-domain
    # group cutters run once per resume inside the epoch's first fetch
    # — spec arithmetic only, nothing may touch the device.
    ("data/loader.py", "GraphLoader.skip_to"),
    ("data/loader.py", "drop_consumed_groups"),
    ("data/loader.py", "skip_delivered_items"),
    ("data/pipeline.py", "ParallelPipelineLoader.skip_to"),
    # The run-telemetry emit paths (docs/OBSERVABILITY.md): emit() and
    # record() run between every step dispatch and must stay pure host
    # work — the ONLY permitted syncs are the config-gated sampled
    # fence in StepClock.record and the one batched epoch-end fetch in
    # StepClock.finish, both suppressed in place. The stream's worker
    # thread may never touch the device at all (it serializes rows the
    # clock already resolved).
    ("utils/telemetry.py", "TelemetryStream.emit"),
    ("utils/telemetry.py", "emit"),
    ("utils/telemetry.py", "StepClock.record"),
    ("utils/telemetry.py", "StepClock.finish"),
    ("utils/telemetry.py", "TelemetryStream._worker_main"),
    # Roofline attribution (ISSUE 8): the first-dispatch executable
    # capture runs BETWEEN steps (once per spec, but on the step
    # thread) — it may lower/compile, never sync; the memory sampler
    # runs at epoch boundaries and after compiles and must stay pure
    # host reads; the trace-annotation helpers run per dispatch while
    # a profiler capture is live.
    ("utils/telemetry.py", "StepClock._maybe_capture"),
    ("utils/telemetry.py", "memory_row"),
    ("utils/tracer.py", "note_trace_step"),
    ("utils/tracer.py", "step_annotation"),
    # The host spans of ISSUE 27: region/span open and close around
    # every site of the loop's host work (feed wait, clock record, step
    # hook, epoch fetch) and on the feed's threads, per dispatch; scope
    # runs at trace time inside every jitted step. None may sync.
    ("utils/tracer.py", "region"),
    ("utils/tracer.py", "span"),
    ("utils/tracer.py", "scope"),
    ("utils/tracer.py", "_Region.__enter__"),
    ("utils/tracer.py", "_Region.__exit__"),
    # Fleet observability (ISSUE 14, docs/OBSERVABILITY.md "Fleet
    # observability"): the liveness counters/phase marks run on the
    # feed hot paths (DPLoader/MultiBranchLoader iterators, once per
    # delivery) and per epoch; the heartbeat builder runs on its own
    # thread but must stay pure host reads (a device touch there
    # would serialize against the step stream from a background
    # thread); emit_barrier runs on the checkpoint worker AND the
    # caller thread (the end-of-run barrier) — all must never sync.
    ("utils/telemetry.py", "bump"),
    ("utils/telemetry.py", "note_phase"),
    ("utils/telemetry.py", "heartbeat_row"),
    ("utils/telemetry.py", "emit_barrier"),
    ("utils/telemetry.py", "TelemetryStream._heartbeat_main"),
    # The instrumented coordination waits themselves: barrier timing
    # must ride the coordination client only — a jax device sync in
    # _process_barrier would fence the training stream from the
    # writer thread (the exact hazard the coordination-service design
    # exists to avoid; docs/DURABILITY.md "Async collective
    # checkpointing").
    ("utils/checkpoint.py", "_process_barrier"),
    ("utils/checkpoint.py", "_processes_agree_finite"),
    # The divergence guard (ISSUE 10, docs/DURABILITY.md "Divergence
    # recovery"): guarded_commit + the poison helpers are traced into
    # every guarded step (and the superstep scan body — by-value, so
    # the nested-def expansion matters); GuardMonitor.observe runs
    # between every dispatch and must stay list appends, and the
    # monitor's ONLY legal sync is the designed resolution fetch in
    # check() (epoch-end / opt-in sampled cadence), suppressed in
    # place. A stray `.item()` anywhere here fences every dispatch.
    ("train/guard.py", "guarded_commit"),
    ("train/guard.py", "poison_scalar"),
    ("train/guard.py", "poison_tree"),
    ("train/guard.py", "poison_batch"),
    ("train/guard.py", "GuardMonitor.observe"),
    ("train/guard.py", "GuardMonitor.check"),
    # The online-serving hot paths (ISSUE 11, docs/SERVING.md): the
    # batcher's submit/placement/next_bin run between every request
    # and every dispatch, and the engine's dispatch loop is the
    # serving twin of _run_epoch — its ONLY permitted sync is the
    # designed response fetch in _resolve (suppressed in place; paid
    # AFTER the next bin was dispatched, preserving the double-buffer
    # overlap). A stray ``.item()`` in any of these fences every
    # request on the service.
    ("serve/batcher.py", "DynamicBatcher.submit"),
    ("serve/batcher.py", "DynamicBatcher._place"),
    ("serve/batcher.py", "DynamicBatcher.next_bin"),
    ("serve/engine.py", "ServingEngine.process"),
    ("serve/engine.py", "ServingEngine._dispatch"),
    ("serve/engine.py", "ServingEngine._resolve"),
    ("serve/engine.py", "ServingEngine._collate_bin"),
    # The fleet routing front (ISSUE 16, docs/SERVING.md "Fleet
    # tier"): submit runs on every frontend thread between requests —
    # policy arithmetic over host-side queue gauges only; a device
    # touch here would fence every request through the router. The
    # swap is the rollover's atomic section: anything slow inside it
    # widens the window every concurrent submit serializes behind.
    ("serve/router.py", "Router.submit"),
    ("serve/router.py", "Router._route"),
    ("serve/router.py", "Router._shed"),
    ("serve/fleet.py", "ServingTier.submit"),
    ("serve/fleet.py", "ReplicaHandle.submit_inner"),
    ("serve/fleet.py", "ReplicaHandle.swap"),
    # The replica worker mains (ISSUE 17): the pump IS the per-replica
    # dispatch loop (every request on the replica flows through it;
    # its only legal sync is inside engine.process's designed resolve
    # fetch), and the beat main must stay a clock read + flag write —
    # a device touch there turns the liveness signal into a liveness
    # HAZARD (a wedged device stops the beats and the monitor declares
    # a healthy replica dead). The kill path is flag-flips only for
    # the same reason (the SIGKILL analog cannot wait on a device).
    ("serve/fleet.py", "ReplicaHandle._pump_main"),
    ("serve/fleet.py", "ReplicaHandle._beat_main"),
    ("serve/fleet.py", "ReplicaHandle.kill"),
    ("serve/fleet.py", "ServingTier.kill_replica"),
    # The fused edge-pipeline Pallas entry points (ISSUE 9): the
    # kernel body and the index_map lambdas inside the pallas_call
    # builder are passed BY VALUE to pallas_call — invisible to
    # name-based call edges, so the nested-def expansion must cover
    # them. These run inside every planned-path train step; any host
    # touch here (np.asarray of a traced plan array, a stray
    # device_get) stalls the hottest dispatch in the repo.
    ("ops/pallas_segment.py", "edge_pipeline_planned"),
    ("ops/pallas_segment.py", "_edge_pipeline_kernel"),
    ("ops/pallas_segment.py", "_pallas_edge_pipeline"),
    # The symmetric backward kernel (ISSUE 18): the vjp dispatch and
    # the pullback pallas_call builder run once per TRAINING step on
    # the planned path — the backward half of the same hot dispatch.
    # Seeded for the same reason as the forward trio: the kernel body
    # and index_map lambdas are passed by value and only the
    # nested-def expansion sees them.
    ("ops/pallas_segment.py", "_edge_pipeline_bwd"),
    ("ops/pallas_segment.py", "_edge_pipeline_bwd_kernel"),
    ("ops/pallas_segment.py", "_pallas_edge_pipeline_bwd"),
    ("ops/pallas_segment.py", "edge_pipeline_bwd_planned"),
    # The MD rollout engine (ISSUE 15, docs/SIMULATION.md): the macro
    # builder's nested scan body is the hottest region of the
    # subsystem — it runs MILLIONS of times per simulation and is
    # passed by value to lax.scan (nested-def expansion covers it and
    # the integrator/neighbor/force helpers it calls, including
    # simulate/integrators.py through the call edges). run() is the
    # dispatch loop between macros; its ONLY permitted sync is the
    # designed per-macro policy fetch, suppressed in place. A stray
    # ``.item()`` in the integrator would fence every physics step.
    ("simulate/engine.py", "RolloutEngine._build_macro"),
    ("simulate/engine.py", "RolloutEngine._neighbor_impl"),
    ("simulate/engine.py", "RolloutEngine._init_forces_impl"),
    ("simulate/engine.py", "RolloutEngine._energy_forces"),
    ("simulate/engine.py", "RolloutEngine.run"),
)

_JAX_SYNC_FNS = {"device_get", "block_until_ready"}


class HostSyncRule(Rule):
    name = "host-sync"
    description = "host-device sync points in the step hot path"
    seeds = HOT_SEEDS

    def run(self, ctx: LintContext) -> Iterable[Finding]:
        graph = ctx.callgraph
        jit_keys = {f.key for f in graph.jitted()}
        # jit_reach = traced context: helpers called from jitted code
        # are inlined into the trace, so np.asarray there is the same
        # hard error as in the jitted body itself. seed_scope pulls a
        # hot function's NESTED defs in too: scan bodies / jit
        # closures are passed as values, so no call edge reaches them
        # — qualname nesting is the ground truth.
        jit_reach = graph.reachable(jit_keys)
        hot_reach = seed_scope(graph, HOT_SEEDS)
        envs = {}
        for key in sorted(jit_reach | hot_reach):
            info = graph.funcs[key]
            sf = info.module
            env = envs.setdefault(sf.relpath, module_env(sf))
            traced = key in jit_reach  # traced context (incl. helpers)
            where = (
                f"jit-compiled `{key[1]}`"
                if info.jitted
                else f"`{key[1]}` (reachable from jit-compiled code)"
                if key in jit_reach
                else f"`{key[1]}` (reachable from the train step path)"
            )
            for node in own_statements(info.node):
                if not isinstance(node, ast.Call):
                    continue
                fn = node.func
                if isinstance(fn, ast.Attribute):
                    # x.item() / x.block_until_ready()
                    if fn.attr == "item" and not node.args:
                        yield Finding(
                            self.name, sf.relpath, node.lineno,
                            f"`.item()` in {where} — per-call device "
                            "sync; accumulate on device and fetch once",
                        )
                        continue
                    if fn.attr == "block_until_ready" and not node.args:
                        yield Finding(
                            self.name, sf.relpath, node.lineno,
                            f"`.block_until_ready()` in {where} — "
                            "drains the dispatch queue",
                        )
                        continue
                    base = fn.value
                    if isinstance(base, ast.Name):
                        root = env.mod_aliases.get(base.id)
                        if root == "jax" and fn.attr in _JAX_SYNC_FNS:
                            yield Finding(
                                self.name, sf.relpath, node.lineno,
                                f"`jax.{fn.attr}(...)` in {where} — "
                                "host-device sync in the hot path",
                            )
                            continue
                        if (
                            traced
                            and root == "numpy"
                            and fn.attr in ("asarray", "array")
                        ):
                            yield Finding(
                                self.name, sf.relpath, node.lineno,
                                f"`np.{fn.attr}(...)` inside {where} — "
                                "concretizes traced values at trace "
                                "time (use jnp)",
                            )
