"""Data-parallel (and FSDP-style) training over a device mesh.

DDP equivalence (reference distributed.py:396-481): the per-device batch
axis is sharded over the mesh's ``data`` axis, parameters are replicated
(or sharded over ``fsdp``), and the gradient mean over devices is an XLA
all-reduce inserted by GSPMD — the compiler-native form of DDP's NCCL
bucket all-reduce.

FSDP/ZeRO equivalence: passing an ``fsdp`` axis shards every parameter
(and its optimizer state, which follows the param sharding through
``tx.init``) on its largest divisible dimension — GSPMD then inserts the
all-gather / reduce-scatter pairs that FSDP does by hand.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hydragnn_tpu.data.graph import GraphBatch
from hydragnn_tpu.data.loader import GraphLoader
from hydragnn_tpu.models.base import MultiHeadGraphModel
from hydragnn_tpu.models.spec import ModelConfig
from hydragnn_tpu.parallel.mesh import stack_batches, shard_stacked_batch
from hydragnn_tpu.train.losses import multihead_loss
from hydragnn_tpu.train.state import TrainState, cast_batch


def param_sharding_spec(params, mesh: Mesh, axis: str = "fsdp"):
    """Shard each parameter's largest dim divisible by the axis size
    (GSPMD FSDP); everything else replicated."""
    size = mesh.shape[axis]

    def _spec(x):
        if x.ndim == 0:
            return NamedSharding(mesh, P())
        dims = sorted(
            range(x.ndim), key=lambda d: x.shape[d], reverse=True
        )
        for d in dims:
            if x.shape[d] % size == 0 and x.shape[d] >= size:
                spec = [None] * x.ndim
                spec[d] = axis
                return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(_spec, params)


def replicate_state(
    state: TrainState, mesh: Mesh, *, fsdp: bool = False, axis: str = "fsdp"
):
    """Place TrainState on the mesh: replicated, or param-sharded (FSDP).

    ``axis="data"`` shards parameters over the data-parallel axis itself
    — the ZeRO-3 / torch-FSDP FULL_SHARD layout (one axis carries both
    the batch and the param shards; GSPMD inserts the all-gather before
    use and the reduce-scatter after the gradient)."""
    rep = NamedSharding(mesh, P())
    if not fsdp or axis not in mesh.shape:
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, rep), state
        )
    pspec = param_sharding_spec(state.params, mesh, axis)
    params = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), state.params, pspec
    )
    # Optimizer-state moment tensors mirror param shapes; shard them the
    # same way, replicate scalars/counters.
    opt_state = _shard_opt_state(state.opt_state, state.params, pspec, rep)
    return state.replace(
        params=params,
        opt_state=opt_state,
        batch_stats=jax.tree_util.tree_map(
            lambda x: jax.device_put(x, rep), state.batch_stats
        ),
        step=jax.device_put(state.step, rep),
    )


def _shard_opt_state(opt_state, params, pspec, rep):
    """Shard optimizer-state leaves that mirror a param's shape."""
    flat_params, _ = jax.tree_util.tree_flatten(params)
    flat_specs, _ = jax.tree_util.tree_flatten(pspec)
    shape_to_spec = {}
    for p, s in zip(flat_params, flat_specs):
        shape_to_spec.setdefault(p.shape, s)

    def _put(x):
        if hasattr(x, "shape") and x.shape in shape_to_spec and x.ndim > 0:
            return jax.device_put(x, shape_to_spec[x.shape])
        return jax.device_put(x, rep)

    return jax.tree_util.tree_map(_put, opt_state)


def _device_weighted_mean(tots, tasks, graph_mask):
    """Graph-weighted mean of per-device ``(tot, tasks)`` rows over the
    stacked device axis — THE shared reduction arithmetic of the dp
    train/eval steps (including the collect_outputs eval branch), so a
    change to the weighting lands everywhere at once."""
    ng = jnp.sum(graph_mask, axis=1).astype(jnp.float32)  # [D]
    denom = jnp.maximum(jnp.sum(ng), 1.0)
    w = ng / denom
    tot = jnp.sum(tots * w)
    task = jnp.sum(tasks * w[:, None], axis=0)
    return tot, task


def _weighted_loss_over_devices(device_loss_fn):
    """Lift a per-device loss into a graph-weighted mean over the stacked
    device axis.

    Each device's loss is already the mean over its real (unpadded)
    graphs; weighting by per-device real-graph counts makes the stacked
    loss the exact mean over every real graph in the global batch — the
    value DDP's equal-rank mean approximates (reference distributed
    loss averaging, train_validate_test.py:560-626)."""

    def loss_over_devices(params, batch_stats, stacked: GraphBatch):
        tots, (tasks, new_bn) = jax.vmap(
            lambda b: device_loss_fn(params, batch_stats, b)
        )(stacked)
        # Cross-device batch-stat sync: average the per-device updates
        # (SyncBatchNorm semantics; reference distributed.py:416).
        new_bn = jax.tree_util.tree_map(
            lambda x: jnp.mean(x, axis=0), new_bn
        )
        tot, tasks = _device_weighted_mean(
            tots, tasks, stacked.graph_mask
        )
        return tot, (tasks, new_bn)

    return loss_over_devices


def _weighted_eval_over_devices(device_loss_fn):
    """Eval-side sibling of ``_weighted_loss_over_devices``: lift a
    per-device eval loss into the graph-weighted mean over the stacked
    device axis. THE single definition of the dp eval reduction — the
    standalone eval step and the superstep scan body both call it, so
    their op sequences (and the K-scan-vs-sequential bitwise contract)
    agree by construction."""

    def eval_over_devices(params, batch_stats, stacked: GraphBatch):
        tots, tasks = jax.vmap(
            lambda b: device_loss_fn(params, batch_stats, b)
        )(stacked)
        return _device_weighted_mean(tots, tasks, stacked.graph_mask)

    return eval_over_devices


def make_dp_train_step(
    model: MultiHeadGraphModel,
    tx,
    cfg: ModelConfig,
    mesh: Mesh,
    compute_dtype=jnp.float32,
    compute_grad_energy: bool = False,
    guard: bool = False,
) -> Callable:
    """Jitted data-parallel train step over stacked batches [D, ...].

    The step vmaps the per-device loss over the leading axis; with the
    leading axis sharded over ``data``, GSPMD partitions the vmapped
    compute per device and turns the gradient mean into an all-reduce
    over ICI. The train state is donated (buffers reused in place).

    ``guard`` builds the divergence-guarded variant — the exact
    mechanics of ``make_train_step(guard=True)`` (train/guard.py,
    docs/DURABILITY.md "Divergence recovery") applied after the dp
    reduction: the predicate ``isfinite(loss) & isfinite(global grad
    norm)`` reads the post-all-reduce loss and gradients, which GSPMD
    leaves REPLICATED across every device and process — so every
    process computes the identical verdict from values it already
    holds, and the guard adds ZERO collectives of its own. The
    tree-level select then commits or skips the (replicated or
    fsdp-sharded) state leaf-for-leaf; loss/tasks/graph-weight are
    zero-masked so a poisoned batch contributes nothing to the epoch
    accumulator. Armed ``nan:<site>@<step>`` fault rules are traced
    into BOTH variants at build time (the unguarded control run must
    diverge visibly in the drill).
    """
    from hydragnn_tpu.train import guard as guard_mod
    from hydragnn_tpu.train.loop import make_loss_fn

    device_loss = make_loss_fn(model, cfg, compute_grad_energy)
    loss_over_devices = _weighted_loss_over_devices(device_loss)
    rules = guard_mod.nan_injections()

    # program names as in train/loop.py: one vocabulary on the trace
    @partial(jax.jit, donate_argnums=0)
    def train_step(state: TrainState, stacked: GraphBatch):
        stacked = guard_mod.poison_batch(rules, state.step, stacked)
        if guard:
            ng = jnp.sum(stacked.graph_mask).astype(jnp.float32)
        stacked = cast_batch(stacked, compute_dtype)
        (tot, (tasks, new_bn)), grads = jax.value_and_grad(
            loss_over_devices, has_aux=True
        )(state.params, state.batch_stats, stacked)
        tot = guard_mod.poison_scalar(rules, "loss", state.step, tot)
        grads = guard_mod.poison_tree(rules, "grad", state.step, grads)
        new_state = state.apply_gradients(grads, tx)
        new_state = new_state.replace(batch_stats=new_bn)
        if guard:
            state, tot, tasks, ok, gnorm = guard_mod.guarded_commit(
                state, new_state, tot, tasks, grads
            )
            ng = jnp.where(ok, ng, jnp.zeros_like(ng))
            return state, tot, tasks, ng, ok, gnorm
        return new_state, tot, tasks

    return train_step


def make_dp_eval_step(
    model: MultiHeadGraphModel,
    cfg: ModelConfig,
    mesh: Mesh,
    compute_dtype=jnp.float32,
    compute_grad_energy: bool = False,
    collect_outputs: bool = False,
) -> Callable:
    """Jitted data-parallel eval step over stacked batches [D, ...].

    With ``collect_outputs`` also returns the per-device head outputs
    ([D, B, dim] / [D, N, dim]) for per-sample collection (loop.test
    flattens the device axis; reference test loop
    train_validate_test.py:986-1080)."""
    from hydragnn_tpu.train.loop import make_eval_loss_fn

    device_loss = make_eval_loss_fn(
        model, cfg, compute_grad_energy, collect_outputs
    )
    eval_over_devices = (
        None if collect_outputs else _weighted_eval_over_devices(device_loss)
    )

    @jax.jit
    def eval_step(state: TrainState, stacked: GraphBatch):
        stacked = cast_batch(stacked, compute_dtype)
        if collect_outputs:
            tots, tasks, outputs = jax.vmap(
                lambda b: device_loss(state.params, state.batch_stats, b)
            )(stacked)
            tot, task = _device_weighted_mean(
                tots, tasks, stacked.graph_mask
            )
            return tot, task, outputs
        tot, task = eval_over_devices(
            state.params, state.batch_stats, stacked
        )
        return tot, task

    return eval_step


def make_dp_superstep_fn(
    model: MultiHeadGraphModel,
    tx,
    cfg: ModelConfig,
    mesh: Mesh,
    *,
    train: bool = True,
    compute_dtype=jnp.float32,
    compute_grad_energy: bool = False,
    donate: bool = True,
    guard: bool = False,
) -> Callable:
    """Jitted dp superstep: K data-parallel train (or eval) steps per
    Python dispatch, via ``lax.scan`` over a ``[K, D, ...]``-stacked
    GraphBatch (a MacroBatch's payload whose device axis is sharded
    over ``data`` by ``mesh.shard_superstacked_batch``) — the dp form
    of ``train/loop.make_superstep_fn`` with the identical contract:

    - train ``(state, acc, batches) -> (state, acc)``, eval
      ``(state, acc, batches) -> acc`` with ``acc = (loss_sum,
      tasks_sum, n_graphs)``, the weighted partial sums ``_run_epoch``
      threads through the carry;
    - the scan body is EXACTLY the per-step op sequence of
      ``make_dp_train_step`` / ``make_dp_eval_step``, emitting the
      per-step ``(tot, tasks, g)`` rows that ``fold_step_metrics``
      folds with the epoch loop's exact weighted-accumulation
      arithmetic — so one K-group dispatch is bitwise identical to K
      sequential dp step dispatches feeding the same running sums
      (tests/test_dp_fastpath.py pins this on the fake 8-device CPU
      mesh);
    - state and accumulator are donated through the carry (train);
      eval donates only the accumulator.

    Composes with fsdp/ZeRO param sharding unchanged: the state rides
    the scan carry with whatever sharding ``replicate_state`` gave it,
    and GSPMD inserts the same all-gather/reduce-scatter pairs inside
    the scan body it inserts around the standalone step.

    ``guard`` (train variant only): the scan body runs the divergence
    guard's predicate + containment PER INNER STEP — a poisoned batch
    inside a ``[K, D, ...]`` macro that commits K dp steps atomically
    becomes a no-op for exactly that step — and the train signature
    grows the per-step predicate rows: ``(state, acc, batches) ->
    (state, acc, oks, gnorms)``. The predicate reads the
    post-all-reduce (replicated) loss and grad norm, so every process
    decides identically with zero extra collectives; the masked
    ``(tot, tasks, g)`` rows keep ``fold_step_metrics``'s multiply-free
    accumulation chain bitwise equal to a run without the poisoned
    step (the select feeds the scan's ys, never the accumulation
    body).
    """
    from hydragnn_tpu.train import guard as guard_mod
    from hydragnn_tpu.train.loop import (
        fold_step_metrics,
        make_eval_loss_fn,
        make_loss_fn,
    )

    if train:
        device_loss = make_loss_fn(model, cfg, compute_grad_energy)
        loss_over_devices = _weighted_loss_over_devices(device_loss)
        rules = guard_mod.nan_injections()

        def train_superstep(state, acc, batches):
            def body(st, stacked):
                stacked = guard_mod.poison_batch(rules, st.step, stacked)
                stacked = cast_batch(stacked, compute_dtype)
                g = jnp.sum(stacked.graph_mask).astype(jnp.float32)
                (tot, (tasks, new_bn)), grads = jax.value_and_grad(
                    loss_over_devices, has_aux=True
                )(st.params, st.batch_stats, stacked)
                tot = guard_mod.poison_scalar(
                    rules, "loss", st.step, tot
                )
                grads = guard_mod.poison_tree(
                    rules, "grad", st.step, grads
                )
                new_st = st.apply_gradients(grads, tx)
                new_st = new_st.replace(batch_stats=new_bn)
                if guard:
                    st, tot, tasks, ok, gnorm = guard_mod.guarded_commit(
                        st, new_st, tot, tasks, grads
                    )
                    g = jnp.where(ok, g, jnp.zeros_like(g))
                    return st, (tot, tasks, g, ok, gnorm)
                return new_st, (tot, tasks, g)

            if guard:
                state, (tots, tasks, gs, oks, gnorms) = jax.lax.scan(
                    body, state, batches
                )
                acc = fold_step_metrics(acc, tots, tasks, gs)
                return state, acc, oks, gnorms
            state, (tots, tasks, gs) = jax.lax.scan(body, state, batches)
            return state, fold_step_metrics(acc, tots, tasks, gs)

        if donate:
            return jax.jit(train_superstep, donate_argnums=(0, 1))
        return jax.jit(train_superstep)

    device_loss = make_eval_loss_fn(model, cfg, compute_grad_energy)
    eval_over_devices = _weighted_eval_over_devices(device_loss)

    def eval_superstep(state, acc, batches):
        def body(carry, stacked):
            stacked = cast_batch(stacked, compute_dtype)
            g = jnp.sum(stacked.graph_mask).astype(jnp.float32)
            tot, task = eval_over_devices(
                state.params, state.batch_stats, stacked
            )
            return carry, (tot, task, g)

        _, (tots, tasks, gs) = jax.lax.scan(body, 0, batches)
        return fold_step_metrics(acc, tots, tasks, gs)

    if donate:
        return jax.jit(eval_superstep, donate_argnums=(1,))
    return jax.jit(eval_superstep)


def _masked_out(b: GraphBatch) -> GraphBatch:
    """Copy of a (host) batch with every validity mask zeroed — used as
    shape-preserving remainder padding that contributes nothing."""
    return b.replace(
        node_mask=np.zeros_like(np.asarray(b.node_mask)),
        edge_mask=np.zeros_like(np.asarray(b.edge_mask)),
        graph_mask=np.zeros_like(np.asarray(b.graph_mask)),
    )


class DPLoader:
    """Wraps a GraphLoader to emit [D, ...]-stacked, mesh-sharded batches.

    The data-parallel analog of DistributedSampler + per-rank loaders
    (reference load_data.py:240-282): every device sees its own
    sub-batch; shapes are identical across devices by construction.

    Multi-host: the wrapped loader holds this process's dataset shard
    (runtime.shard_dataset_for_process); each process stacks only the
    sub-batches for its local slice of the ``data`` axis and the stack
    becomes a global array spanning all processes.

    ``superstep_k > 1`` additionally folds runs of K consecutive
    SAME-SPEC steps into one ``[K, D, ...]``-stacked ``MacroBatch``
    (one dispatch of K scanned dp steps — ``make_dp_superstep_fn``).
    Grouping happens in the PLAN domain (``padschedule.dp_step_plan``
    over the wrapped chain's ``epoch_plan`` +
    ``padschedule.superstep_groups``), exactly like the single-scheme
    wrappers, so batch content and order are bit-identical to K=1
    delivery — only the grouping boundaries change. Steps whose spec
    the plan cannot prove equal (and the epoch's short remainder step)
    are delivered as plain ``[D, ...]`` batches.
    """

    def __init__(
        self,
        loader: GraphLoader,
        mesh: Mesh,
        axis: str = "data",
        pad_remainder: bool = True,
        superstep_k: int = 1,
    ):
        self.loader = loader
        self.mesh = mesh
        self.axis = axis
        self.pad_remainder = pad_remainder
        self.superstep_k = max(1, int(superstep_k))
        self._epoch = 0
        self._skip_next = 0
        self.n_global = int(mesh.shape[axis])
        p = jax.process_count()
        if self.n_global % p != 0:
            raise ValueError(
                f"data axis size {self.n_global} not divisible by "
                f"{p} processes"
            )
        self.n = self.n_global // p  # local sub-batches per step
        if self.superstep_k > 1 and self._plan_loader() is None:
            raise TypeError(
                "DPLoader(superstep_k > 1) groups steps from the "
                "wrapped chain's epoch_plan; got a chain without one "
                f"({type(loader)})"
            )

    def _plan_loader(self):
        """The epoch_plan-bearing loader inside the wrapped chain (the
        pipeline wrapper exposes its GraphLoader as ``.loader``)."""
        from hydragnn_tpu.data.loader import iter_loader_chain

        for ld in iter_loader_chain(self.loader):
            if hasattr(ld, "epoch_plan"):
                return ld
        return None

    def _step_groups(self, epoch: int):
        """Superstep grouping of this epoch's FULL steps: a list of
        group lengths (1 = plain step, K = one macro dispatch), built
        purely from the plan so serial and pipeline feeds group
        identically (the PR-4 grouping-purity invariant)."""
        from hydragnn_tpu.data.padschedule import (
            dp_step_plan,
            superstep_groups,
        )

        base = self._plan_loader()
        steps, _ = dp_step_plan(base.epoch_plan(epoch), self.n)
        return [
            len(g) for g in superstep_groups(steps, self.superstep_k)
        ]

    @staticmethod
    def required_hold(
        mesh: Mesh, axis: str = "data", superstep_k: int = 1
    ) -> int:
        """Packed-buffer validity window a ParallelPipelineLoader
        feeding this DPLoader must honor: a device group buffers up to
        ``n`` host batches before ``stack_batches`` copies them (plus
        one for the batch being collated into the next group) — and a
        superstep group buffers ``K`` device groups before the
        ``[K, D, ...]`` stack. The pipeline recycles a yielded batch's
        buffers only after ``hold`` further deliveries, so
        hold >= K * n + 1 keeps every buffered batch alive until its
        stack."""
        n_global = int(mesh.shape[axis])
        n = n_global // jax.process_count()
        return max(2, n * max(1, int(superstep_k)) + 1)

    def set_epoch(self, epoch: int) -> None:
        self._epoch = int(epoch)
        # Clears the wrapped chain's armed cursor too (their set_epoch
        # does the same) — a cursor never outlives its epoch.
        self.loader.set_epoch(epoch)
        self._skip_next = 0

    def skip_to(self, step: int) -> None:
        """One-shot mid-epoch resume cursor in dp OPTIMIZER steps: the
        wrapped chain (pipeline or GraphLoader) fast-forwards
        ``step * n`` base batches — never collating the consumed ones —
        and the superstep grouping drops the groups the cursor covers
        (cut from the FULL plan, so resumed ``[K, D, ...]`` macros are
        the uninterrupted run's exact delivery suffix)."""
        step = max(0, int(step))
        if not hasattr(self.loader, "skip_to"):
            raise TypeError(
                "DPLoader.skip_to needs a wrapped chain with skip_to "
                f"(pipeline or GraphLoader); got {type(self.loader)}"
            )
        self.loader.skip_to(step * self.n)
        self._skip_next = step

    def __len__(self) -> int:
        """Delivered items this epoch (macro groups count once)."""
        if not len(self.loader):
            return 0
        n_steps = (
            -(-len(self.loader) // self.n)
            if self.pad_remainder
            else len(self.loader) // self.n
        )
        if self.superstep_k <= 1:
            return n_steps
        groups = self._step_groups(self._epoch)
        n_grouped_steps = sum(groups)
        return len(groups) + (n_steps - n_grouped_steps)

    def _yield_step(self, buf: List[GraphBatch]):
        stacked = stack_batches(buf)
        return shard_stacked_batch(stacked, self.mesh, self.axis)

    def _yield_macro(self, buf: List[GraphBatch], k: int):
        """One [K, D, ...] macro from k*n host batches: host-side
        stack (numpy — the batches are host arrays under the dp feed
        contract), ONE sharded device commit, step axis replicated."""
        from hydragnn_tpu.data.graph import (
            MacroBatch,
            stack_batches as stack_macro_steps,
        )
        from hydragnn_tpu.parallel.mesh import shard_superstacked_batch

        steps = [
            stack_batches(buf[t * self.n : (t + 1) * self.n])
            for t in range(k)
        ]
        macro = stack_macro_steps(steps).batch
        return MacroBatch(
            batch=shard_superstacked_batch(macro, self.mesh, self.axis),
            k=k,
        )

    def __iter__(self):
        from hydragnn_tpu.utils import telemetry

        skip = self._skip_next
        self._skip_next = 0
        if self.superstep_k > 1:
            yield from self._iter_superstep(skip)
            return
        # K=1: the wrapped chain already fast-forwarded skip * n base
        # batches; stacking just proceeds on what arrives.
        buf: List[GraphBatch] = []
        for batch in self.loader:
            buf.append(batch)
            if len(buf) == self.n:
                # Heartbeat liveness counter (fleet observability): a
                # per-process feed that wedges mid-epoch shows as a
                # frozen counter across beats. Pure host dict store,
                # no-op with the stream off.
                telemetry.bump("dp_batches")
                yield self._yield_step(buf)
                buf = []
        if buf and self.pad_remainder:
            telemetry.bump("dp_batches")
            yield self._yield_remainder(buf)

    def _yield_remainder(self, buf: List[GraphBatch]):
        # Pad the last device group by repeating ITS OWN batches
        # with ALL masks zeroed: shapes match within the group even
        # under a per-step spec schedule (earlier groups may carry
        # different bucketed shapes), and the repeats contribute
        # nothing to losses, metrics, or per-sample collection —
        # unlike the reference's DistributedSampler, which
        # overweights the repeated graphs.
        n_real = len(buf)
        i = 0
        while len(buf) < self.n:
            buf.append(_masked_out(buf[i % n_real]))
            i += 1
        return self._yield_step(buf)

    def _iter_superstep(self, skip: int = 0):
        """Grouped delivery: plan-domain step groups drive how many
        consecutive [D, ...] steps stack into one macro. Content and
        order match K=1 delivery exactly; a short epoch tail takes the
        masked-pad remainder path unchanged. A resume cursor drops the
        groups it covers (full-plan grouping first — the suffix
        contract of ``loader.drop_consumed_groups``; a mid-group
        cursor degrades that group's remainder to per-step [D, ...]
        deliveries, loudly)."""
        groups = self._step_groups(self._epoch)
        if skip:
            from hydragnn_tpu.data.loader import drop_consumed_groups

            # Group LENGTHS here, not plan entries: reuse the shared
            # cursor arithmetic on unit placeholders.
            groups = [
                len(g)
                for g in drop_consumed_groups(
                    [[None] * L for L in groups], skip
                )
            ]
        it = iter(self.loader)
        buf: List[GraphBatch] = []
        gi = 0
        want = groups[0] * self.n if groups else 0
        for batch in it:
            if gi >= len(groups):  # loader outran the plan's full steps
                buf.append(batch)
                continue
            buf.append(batch)
            if len(buf) == want:
                from hydragnn_tpu.utils import telemetry

                k = groups[gi]
                telemetry.bump("dp_batches", k)
                if k == 1:
                    yield self._yield_step(buf)
                else:
                    yield self._yield_macro(buf, k)
                buf = []
                gi += 1
                want = groups[gi] * self.n if gi < len(groups) else 0
        # Remainder: entries past the plan's full steps (< n of them by
        # construction — dp_step_plan folds every full step into a
        # group) take the existing masked-pad path.
        while len(buf) >= self.n:  # defensive: ungrouped full steps
            yield self._yield_step(buf[: self.n])
            buf = buf[self.n :]
        if buf and self.pad_remainder:
            yield self._yield_remainder(buf)
