"""Multibranch task-parallel training (GFM workload).

TPU-native equivalent of the reference's MultiTaskModelMP
(hydragnn/models/MultiTaskModelMP.py:269-532) + the multibranch driver's
process-group setup (examples/multibranch/train.py:223-284):

Reference semantics:
  - world is split into per-dataset branch groups, proportional to
    dataset sizes or uniform;
  - the shared encoder's gradients are averaged over WORLD
    (MultiTaskModelMP.gradient_all_reduce -> average_gradients(encoder,
    shared_pg), :458-460);
  - each branch decoder's gradients are averaged over its branch group
    only; other branches' heads are pruned from the module (:300-333);
  - a DualOptimizer steps encoder and decoder param groups separately
    (:493-532).

TPU mapping: ONE pjit over the full mesh. Every device is statically
assigned a branch; its batches contain only that branch's samples. All
branch decoders live in the same (replicated) param pytree — XLA's
gradient mean over the mesh then computes sum_d g_d / D for every leaf.
For encoder params that IS world averaging; for branch b's decoder
params the correct branch-group mean is sum_{d in b} g_d / D_b, and
devices outside b contribute zero gradient (their samples never touch
branch b's heads). So rescaling decoder-branch leaves by D / D_b after
the mesh-mean reproduces the reference's two process-group reduction
exactly — no manual collectives, no parameter surgery.

``no_sync`` gradient accumulation (examples/multibranch/train.py:90,
498-517) maps to optax.MultiSteps (sync every k-th step).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

from hydragnn_tpu.data.graph import GraphBatch, GraphSample
from hydragnn_tpu.data.loader import GraphLoader
from hydragnn_tpu.models.base import MultiHeadGraphModel
from hydragnn_tpu.models.spec import ModelConfig
from hydragnn_tpu.parallel.mesh import shard_stacked_batch, stack_batches
from hydragnn_tpu.train.losses import multihead_loss
from hydragnn_tpu.train.state import TrainState, cast_batch


def _assert_same_across_processes(values, what: str) -> None:
    """Allgather a small integer fingerprint and require it identical on
    every process (multibranch inputs must match host-for-host)."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    fp = np.asarray(list(values), np.int64)
    all_fp = multihost_utils.process_allgather(fp)
    if not (all_fp == all_fp[0]).all():
        raise ValueError(
            f"multibranch {what} differ across processes; every process "
            f"must pass the SAME full per-branch datasets. "
            f"fingerprints:\n{all_fp}"
        )


def proportional_branch_split(
    dataset_sizes: Sequence[int], n_devices: int
) -> List[int]:
    """Devices per branch, proportional to dataset sizes, >= 1 each
    (reference proportional process_list, examples/multibranch/train.py
    :173-221 with HYDRAGNN_TASK_PARALLEL_PROPORTIONAL_SPLIT)."""
    k = len(dataset_sizes)
    if n_devices < k:
        raise ValueError(f"{n_devices} devices < {k} branches")
    total = float(sum(dataset_sizes))
    raw = [max(1, int(n_devices * s / total)) for s in dataset_sizes]
    # Fix rounding drift deterministically: trim the largest / grow the
    # smallest allocation until the sum matches.
    while sum(raw) > n_devices:
        raw[int(np.argmax(raw))] -= 1
    while sum(raw) < n_devices:
        raw[int(np.argmin(raw))] += 1
    if min(raw) < 1:
        raise ValueError(f"branch with zero devices: {raw}")
    return raw


def branch_of_device(devices_per_branch: Sequence[int]) -> np.ndarray:
    """[D] branch id of each device slot (branch-major order)."""
    return np.repeat(
        np.arange(len(devices_per_branch)), devices_per_branch
    ).astype(np.int32)


def _branch_name_index(cfg: ModelConfig) -> Dict[str, int]:
    """Branch name -> branch index, over BOTH graph and node branch lists
    (their names are usually the uniform "branch-i" set; if they differ,
    every name still resolves to its own list index)."""
    names: Dict[str, int] = {}
    for lst in (cfg.graph_branches, cfg.node_branches):
        for bi, b in enumerate(lst):
            names.setdefault(b.name, bi)
    return names


def _decoder_branch_of_path(
    path: Tuple, names_by_len: Sequence[str], name_index: Dict[str, int]
) -> Optional[int]:
    """Which branch a decoder param leaf belongs to, from its tree path.

    Decoder modules are named ``graph_shared_<branch>`` /
    ``head<i>_<branch>`` (hydragnn_tpu/models/base.py MultiHeadDecoder);
    encoder leaves (under ``stack``/``gps``) return None. Longest name
    matched first so a branch name that is an underscore-suffix of
    another ("energy" vs "free_energy") cannot be misattributed.
    """
    keys = [getattr(p, "key", None) for p in path]
    if not any(k is not None and k.startswith("decoder") for k in keys):
        return None
    for k in keys:
        if k is None:
            continue
        for name in names_by_len:
            if k.endswith(f"_{name}"):
                return name_index[name]
    return None


def rescale_decoder_grads(
    grads, cfg: ModelConfig, n_devices: int, devices_per_branch: Sequence[int]
):
    """After a full-mesh gradient mean, rescale branch-decoder leaves by
    D / D_b so they equal the branch-group mean (see module docstring)."""
    name_index = _branch_name_index(cfg)
    names_by_len = sorted(name_index, key=len, reverse=True)

    def _scale(path, g):
        bi = _decoder_branch_of_path(path, names_by_len, name_index)
        if bi is None:
            return g
        return g * (n_devices / devices_per_branch[bi])

    return jax.tree_util.tree_map_with_path(_scale, grads)


def branch_guard_labels(n_branches: int) -> List[str]:
    """The per-slot labels of the multibranch guard's predicate vector
    (train/guard.GuardMonitor ``branches``): one slot per branch
    decoder, plus the shared encoder as the LAST slot — the order
    ``make_multibranch_train_step(guard=True)`` emits ``ok``/``gnorm``
    in."""
    return [f"branch-{i}" for i in range(n_branches)] + ["encoder"]


def make_multibranch_train_step(
    model: MultiHeadGraphModel,
    tx,
    cfg: ModelConfig,
    mesh: Mesh,
    devices_per_branch: Sequence[int],
    compute_dtype=jnp.float32,
    compute_grad_energy: bool = False,
    guard: bool = False,
) -> Callable:
    """Jitted task-parallel train step over stacked per-device batches.

    Identical structure to the DP step (hydragnn_tpu/parallel/dp.py) plus
    the decoder gradient rescale. The equal-device (unweighted) mean is
    load-bearing here: the D/D_b decoder rescale math (module docstring)
    assumes every device contributes weight 1/D.

    ``guard`` builds the divergence-guarded variant with PER-BRANCH
    containment (docs/DURABILITY.md "Divergence recovery"). The task-
    parallel gradient structure localizes most poisons: branch b's
    decoder gradients flow only through branch b's device losses
    (other devices' zero-weighted head terms contribute structural
    zeros), so e.g. a poisoned LABEL on branch a corrupts branch a's
    decoder gradients and the world-mean'd SHARED ENCODER gradients,
    while branch b's decoder gradients stay finite — and bitwise what
    a clean step would have computed for them. The commit select is
    therefore per parameter GROUP, keyed by the same tree-path
    resolution the D/D_b rescale uses, with the predicate read
    DIRECTLY off each group's gradient health (the loss function
    itself is byte-identical to the unguarded build — an extra
    differentiated aux would move fusion boundaries and cost the
    healthy-run bitwise contract an ulp, measured):

    - slot b (branch decoder): commits iff
      ``isfinite(global_norm(branch b decoder grads))`` — one branch's
      poison NEVER suppresses another branch's healthy decoder update;
    - the encoder slot (encoder leaves + every leaf with no branch in
      its path — shared optimizer scalars, the mean'd batch_stats):
      commits iff the mean loss AND the encoder grad norm are finite
      (a poisoned branch's contribution is already inside the
      world-mean'd encoder gradient and batch stats).

    All predicate inputs are post-all-reduce replicated values, so
    every process decides identically with zero extra collectives.
    Metric masking stays GLOBAL (``tot``/``tasks``/graph-weight zeroed
    when ANY slot fails): the scalar mean loss cannot be partially
    unpicked, so a step with any poison contributes nothing to the
    epoch accumulator — exactly what the monitor records for it. The
    step returns ``(state, tot, tasks, ng, ok, gnorm)`` with
    ``ok``/``gnorm`` as ``[n_branches + 1]`` vectors in
    ``branch_guard_labels`` order; GuardMonitor keeps a bad-step
    window PER SLOT. Two documented bounds: dual_optimizer groups all
    decoders under one optax chain, so its shared step count (an
    encoder-slot leaf) keeps the encoder predicate while per-branch
    moments stay exactly apply_if_finite; and a poison that NUMERICALLY
    reaches every branch (NaN inputs — ``0 * NaN`` in the masked head
    terms propagates to every decoder's gradients) correctly reads as
    all-slot-bad: containment follows where the corruption actually
    flowed, never the blame's origin.

    Armed ``nan:<site>@<step>`` fault rules are traced into BOTH
    variants at build time; ``loss``/``grad``/``batch`` sites poison
    mesh-wide values, so per-branch drills poison a single branch's
    labels host-side instead (tests/test_guard.py).
    """
    from functools import partial

    from hydragnn_tpu.train import guard as guard_mod
    from hydragnn_tpu.train.loop import make_loss_fn

    n_devices = int(mesh.shape["data"])
    n_branches = len(devices_per_branch)
    device_loss = make_loss_fn(model, cfg, compute_grad_energy)
    rules = guard_mod.nan_injections()
    name_index = _branch_name_index(cfg)
    names_by_len = sorted(name_index, key=len, reverse=True)

    def _slot_of_path(path) -> int:
        bi = _decoder_branch_of_path(path, names_by_len, name_index)
        return n_branches if bi is None else bi  # encoder slot last

    def loss_over_devices(params, batch_stats, stacked: GraphBatch):
        tots, (tasks, new_bn) = jax.vmap(
            lambda b: device_loss(params, batch_stats, b)
        )(stacked)
        new_bn = jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=0), new_bn)
        return jnp.mean(tots), (jnp.mean(tasks, axis=0), new_bn)

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
        raw_grads = grads
        grads = rescale_decoder_grads(
            grads, cfg, n_devices, tuple(devices_per_branch)
        )
        new_state = state.apply_gradients(grads, tx)
        new_state = new_state.replace(batch_stats=new_bn)
        if not guard:
            return new_state, tot, tasks
        import optax

        mean_ok = jnp.isfinite(tot)
        # Per-slot grad norms, read off the PRE-rescale gradients: the
        # D/D_b rescale is a finite positive per-leaf scalar, so the
        # finiteness verdict is identical — and the rescale multiply
        # keeps its single consumer (the optimizer update). Giving
        # that multiply a second consumer moves XLA's fusion
        # boundaries and re-opens the PR-10 1-ulp fp-contract hazard
        # on HEALTHY steps (measured), which would break the
        # guard-on == guard-off bitwise contract.
        grad_slots: List[List] = [[] for _ in range(n_branches + 1)]
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            raw_grads
        )[0]:
            grad_slots[_slot_of_path(path)].append(leaf)
        gnorm = jnp.stack(
            [
                optax.global_norm(g) if g else jnp.zeros(())
                for g in grad_slots
            ]
        )
        ok = jnp.stack(
            [
                jnp.isfinite(gnorm[b])
                for b in range(n_branches)
            ]
            + [mean_ok & jnp.isfinite(gnorm[-1])]
        )

        def _commit(path, n, o):
            return jnp.where(ok[_slot_of_path(path)], n, o)

        committed = jax.tree_util.tree_map_with_path(
            _commit, new_state, state
        )
        committed = committed.replace(step=state.step + 1)
        ok_all = jnp.all(ok)
        tot = jnp.where(ok_all, tot, jnp.zeros_like(tot))
        tasks = jnp.where(ok_all, tasks, jnp.zeros_like(tasks))
        ng = jnp.where(ok_all, ng, jnp.zeros_like(ng))
        # ``new_state`` rides out as an EXTRA jit output, discarded by
        # the wrapper below. Load-bearing, not decorative: as an
        # output ROOT the update cluster terminates identically in the
        # guarded and unguarded builds, so XLA's fusion (and LLVM's
        # fp-contract decisions inside the rescale→Adam arithmetic)
        # cannot differ between them — without it the select's extra
        # consumer re-fuses the update and drifts healthy decoder
        # params by 1 ulp (measured; optimization_barrier and a
        # trip-1 scan fence are both erased before the decision that
        # matters). Costs one extra state-tree write per guarded
        # multibranch step.
        return committed, tot, tasks, ng, ok, gnorm, new_state

    if not guard:
        return train_step

    def step(state: TrainState, stacked: GraphBatch):
        return train_step(state, stacked)[:6]

    # AOT-lowering hook for the telemetry executable capture
    # (StepClock._maybe_capture lowers the step it dispatched).
    step.lower = train_step.lower
    return step


class MultiBranchLoader:
    """Per-device branch-local loaders -> stacked mesh-sharded batches.

    Each device slot draws batches from its branch's dataset only
    (reference: per-branch AdiosDataset + create_dataloaders(group=
    branch_group), examples/multibranch/train.py:302-442). Epoch length
    = min over ALL device slots of available batches (the reference
    enforces rank lockstep with nbatch = allreduce(MIN),
    train_validate_test.py:672 — static here by construction).

    Multi-host: every process receives the FULL branch datasets and
    builds every slot's loader deterministically (so the global min
    epoch length needs no collective), but iterates only its own
    contiguous slice of device slots; the local stack becomes a global
    array spanning processes (shard_stacked_batch).
    """

    def __init__(
        self,
        branch_datasets: Sequence[Sequence[GraphSample]],
        devices_per_branch: Sequence[int],
        batch_size: int,
        mesh: Mesh,
        *,
        shuffle: bool = True,
        seed: int = 0,
        with_triplets: bool = False,
        variable_pad: "bool | str" = False,
    ):
        """``variable_pad`` pads each step up a shared bucket ladder
        instead of the permanent worst-case spec: all device slots of
        step t take ONE spec covering every slot's t-th batch
        (data/padschedule.slot_spec_schedule — process-consistent
        because every process builds all slot loaders). ``"auto"``
        takes the ladder only when the simulated spec count stays
        within the bucket budget. Triplet-bearing models always use
        the fixed worst case."""
        import dataclasses

        self.mesh = mesh
        self._skip_next = 0  # one-shot mid-epoch resume cursor
        # Fail fast BEFORE any constructor error can fire asymmetrically
        # (divergent datasets -> different devices_per_branch -> one
        # process raises while the other blocks in a later collective):
        # agree on per-branch sizes + the device split first.
        _assert_same_across_processes(
            [len(b) for b in branch_datasets] + list(devices_per_branch),
            "per-branch dataset sizes / device split",
        )
        # One pytree structure across ALL branches and device shards:
        # each global step stacks batches from every slot, so the
        # optional-field map comes from scanning the concatenation of
        # all branch datasets — zero-fill widths must agree and
        # label/position presence must be uniform across branches, not
        # just within each one.
        from hydragnn_tpu.data.graph import optional_field_widths

        shared_fields = optional_field_widths(
            [s for b in branch_datasets for s in b]
        )

        self.loaders: List[GraphLoader] = []
        for bi, n_dev in enumerate(devices_per_branch):
            # Copy samples: dataset_id routing must not leak into other
            # consumers of the same GraphSample objects.
            samples = [
                dataclasses.replace(s, dataset_id=bi)
                for s in branch_datasets[bi]
            ]
            # Split the branch dataset across its devices.
            for di in range(n_dev):
                shard = samples[di::n_dev]
                if not shard:
                    raise ValueError(
                        f"Branch {bi}: device shard {di}/{n_dev} is empty "
                        f"({len(samples)} samples over {n_dev} devices); "
                        "reduce devices_per_branch or add data"
                    )
                self.loaders.append(
                    GraphLoader(
                        shard,
                        batch_size,
                        shuffle=shuffle,
                        seed=seed + 1000 * bi + di,
                        with_triplets=with_triplets,
                        ensure_fields=shared_fields,
                    )
                )
        # This process's contiguous slice of device slots.
        n_slots = len(self.loaders)
        p = jax.process_count()
        if n_slots % p != 0:
            raise ValueError(
                f"{n_slots} device slots not divisible by {p} processes"
            )
        per_proc = n_slots // p
        self._lo = jax.process_index() * per_proc
        self._hi = self._lo + per_proc
        # Stacking along the device axis requires identical padded
        # shapes on every device slot per step. Variable pad: one
        # shared bucketed spec per STEP (max over every slot's batch).
        if variable_pad and not with_triplets:
            from hydragnn_tpu.data.padschedule import slot_spec_schedule

            sched = slot_spec_schedule(self.loaders)
            if variable_pad != "auto" or sched.ladder_is_small():
                for ld in self.loaders:
                    ld.spec_schedule = sched
                    ld.pad_spec = None
                    ld.fixed_pad = False
                _assert_same_across_processes(
                    [len(ld) for ld in self.loaders]
                    + sched.fingerprint(),
                    "per-slot batch counts / shared spec schedule",
                )
                return
        # Fixed worst case: the elementwise max PadSpec across all
        # branch loaders, pinned everywhere.
        from hydragnn_tpu.data.graph import PadSpec

        specs = [ld.pad_spec for ld in self.loaders if ld.pad_spec]
        if specs:
            trips = [s.num_triplets for s in specs if s.num_triplets]
            shared = PadSpec(
                num_nodes=max(s.num_nodes for s in specs),
                num_edges=max(s.num_edges for s in specs),
                num_graphs=max(s.num_graphs for s in specs),
                num_triplets=max(trips) if trips else None,
            )
            for ld in self.loaders:
                ld.pad_spec = shared
            # Agree on the SHARED padded shapes + per-slot batch counts
            # (each process derives them locally, no collective; a
            # divergent copy of any branch dataset would otherwise hang
            # the job inside an XLA collective with no diagnostic).
            _assert_same_across_processes(
                [len(ld) for ld in self.loaders]
                + [
                    shared.num_nodes,
                    shared.num_edges,
                    shared.num_graphs,
                    shared.num_triplets or -1,
                ],
                "per-slot batch counts / shared padded shapes",
            )

    def set_epoch(self, epoch: int) -> None:
        for ld in self.loaders:
            ld.set_epoch(epoch)
        # A slot cursor never outlives its epoch (GraphLoader.set_epoch
        # just cleared the per-slot ones; this is the stacking level's).
        self._skip_next = 0

    def skip_to(self, step) -> None:
        """One-shot mid-epoch resume cursor (docs/DURABILITY.md): the
        next iteration starts at global step ``step`` of the current
        epoch. Every device slot's loader fast-forwards its own
        deterministic ``epoch_plan`` replay (``GraphLoader.skip_to`` —
        spec arithmetic only, consumed entries are never collated), so
        the resumed stacked deliveries are the uninterrupted run's
        exact suffix.

        ``step`` may also be the manifest's per-branch cursor list
        (``branch_steps``): the loop consumes every branch in LOCKSTEP
        — one batch per slot per global step — so the values must
        agree; a drifted list is rejected here rather than silently
        replaying one branch's consumed steps."""
        if isinstance(step, (list, tuple)):
            vals = {int(s) for s in step}
            if len(vals) > 1:
                raise ValueError(
                    "multibranch per-branch cursors disagree "
                    f"({list(step)}): the feed consumes branches in "
                    "lockstep and cannot fast-forward them unequally"
                )
            step = vals.pop() if vals else 0
        step = max(0, int(step))
        # Arm only this process's iterated slots; non-local slot
        # loaders never iterate (their cursor would just go stale
        # until the next set_epoch).
        for ld in self.loaders[self._lo : self._hi]:
            ld.skip_to(step)
        self._skip_next = step

    def __len__(self) -> int:
        # Global min over ALL slots: identical on every process.
        return min(len(ld) for ld in self.loaders)

    def __iter__(self):
        from hydragnn_tpu.utils import telemetry

        skip = self._skip_next
        self._skip_next = 0
        iters = [iter(ld) for ld in self.loaders[self._lo : self._hi]]
        for _ in range(max(0, len(self) - skip)):
            batches = [next(it) for it in iters]
            stacked = stack_batches(batches)
            # Heartbeat liveness counter (fleet observability): one
            # host dict store per stacked delivery, no-op with the
            # stream off — a branch feed wedged mid-epoch shows as a
            # frozen counter across this process's beats.
            telemetry.bump("mb_batches")
            yield shard_stacked_batch(stacked, self.mesh, "data")


def dual_optimizer(
    training_cfg: dict, decoder_lr: Optional[float] = None
) -> optax.GradientTransformation:
    """DualOptimizer equivalent (reference MultiTaskModelMP.py:493-532):
    separate optimizer instances for encoder vs decoder param groups via
    optax.multi_transform. ``decoder_lr`` defaults to the shared lr."""
    from hydragnn_tpu.train.optimizer import select_optimizer

    enc_tx = select_optimizer(training_cfg)
    dec_cfg = dict(training_cfg)
    if decoder_lr is not None:
        opt = dict(dec_cfg.get("Optimizer", {}))
        opt["learning_rate"] = decoder_lr
        dec_cfg["Optimizer"] = opt
    dec_tx = select_optimizer(dec_cfg)

    def _label(path, _):
        keys = [getattr(p, "key", "") for p in path]
        return (
            "decoder"
            if any(k and k.startswith("decoder") for k in keys)
            else "encoder"
        )

    return optax.multi_transform(
        {"encoder": enc_tx, "decoder": dec_tx},
        lambda params: jax.tree_util.tree_map_with_path(_label, params),
    )


def accumulate(tx, every: int) -> optax.GradientTransformation:
    """no_sync gradient accumulation (reference --nosync,
    examples/multibranch/train.py:498-517): local accumulation with a
    sync/apply every ``every`` steps, via optax.MultiSteps."""
    return optax.MultiSteps(tx, every_k_schedule=every)
