"""Numerical-health guard: on-device divergence detection/containment
and the host-side skip → rollback → halt policy ladder
(docs/DURABILITY.md "Divergence recovery").

A single non-finite optimizer step used to be a lost run: nothing
checked loss/grad finiteness, so a bad batch or a bf16 overflow
silently poisoned the params — and the checkpoint writer then durably
published the corruption as ``latest``. This module turns every prior
PR's determinism contract into a recovery guarantee:

- **On-device detection + containment** (``guarded_commit``): the
  jitted train step computes a finiteness predicate over the loss AND
  the global gradient norm, and SELECTS the committed state — the
  freshly-updated tree when the predicate holds, the pre-step tree when
  it fails (``optax.apply_if_finite`` semantics, expressed as a
  tree-level ``lax.cond`` whose branches only pass a tree through, so
  the optimizer state keeps its exact structure). A poisoned batch
  becomes a no-op step even inside a
  ``[K, ...]`` superstep macro that commits K steps atomically, because
  the select runs in the scan body per inner step. The masked metric
  contributions (loss/tasks/graph-weight zeroed on a bad step) make the
  epoch accumulator bitwise equal to a run that never saw the poisoned
  batch — ``jnp.where``/``lax.select`` is an exact passthrough, ``x *
  1.0`` and ``x + 0.0`` are bitwise ``x``, so a HEALTHY run with the
  guard enabled is bitwise identical (losses AND params) to one with it
  disabled (tests/test_guard.py pins this through serial, pipeline and
  superstep feeds; fold_step_metrics' fusion-fence discipline is
  untouched because the select feeds the scan's ys, never the
  accumulation body). The STATE is committed through a conditional,
  not a select: a select fuses into the optimizer's update fusions, and
  on XLA:CPU the fused ``select(ok, b1*mu + (1-b1)*g, mu)`` contracts a
  different multiply into its FMA than the unguarded ``b1*mu +
  (1-b1)*g`` does (seen in the dp step's Adam moments, for the leaves
  whose gradient reaches the update through a layout-changing bitcast)
  — a conditional is a fusion fence no backend crosses, so the update
  fusions of the two builds are the same computation.

- **Zero added host-syncs by default**: the per-step predicate and
  grad norm travel as DEFERRED device refs held by ``GuardMonitor``
  (the same discipline as the telemetry StepClock) and are resolved in
  ONE batched fetch at the existing epoch-end point. An opt-in sampled
  cadence (``Guard.check_interval_steps`` > 0) resolves mid-epoch so
  the policy ladder can react within an epoch, at the documented cost
  of a host sync every N steps.

- **Policy ladder** (``Guard.policy``): ``skip`` records bad steps
  (telemetry ``health`` rows + a loud print) and relies on the
  on-device no-op; ``rollback`` additionally restores the last-known-
  good checkpoint once more than ``max_bad_steps`` land inside a
  ``window_steps`` window — with LR backoff, fast-forwarding past the
  poisoned region via PR 6's ``skip_to``/manifest machinery — and
  halts after ``max_rollbacks``; ``halt`` raises immediately at the
  threshold with an actionable report. The CheckpointWriter's
  validate-finite gate (utils/checkpoint.py) guarantees the rollback
  target is good: a non-finite state is never published as ``latest``.

- **Fault injection** (``poison_*`` + utils/faults.py ``nan:<site>@
  <step>``): the drill harness. Injection triggers on the ON-DEVICE
  ``state.step`` counter, so it lands identically inside superstep
  scans; the committed state always advances ``step`` (even on a
  skipped update) so one armed rule fires exactly once.

Config: ``Training.Guard {enabled, policy, max_bad_steps,
window_steps, check_interval_steps, lr_backoff, max_rollbacks}``
(eagerly validated in config.update_config). Containment is wired for
EVERY scheme's step builders: single (serial / pipeline / superstep
feeds), dp (``parallel/dp.py`` — the same select in the dp step and
its scan body, with the predicate read from the post-all-reduce
REPLICATED loss/grad-norm so every process decides identically at
zero added collectives), and multibranch (``parallel/multibranch.py``
— per-branch parameter-group selects; the monitor then keeps a
bad-step window per branch slot via ``branches``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from hydragnn_tpu.utils import tracer as tr

__all__ = [
    "GuardSettings",
    "guard_settings",
    "GuardRollback",
    "GuardHalt",
    "GuardMonitor",
    "nan_injections",
    "poison_scalar",
    "poison_tree",
    "poison_batch",
    "guarded_commit",
]

_POLICIES = ("skip", "rollback", "halt")


@dataclass(frozen=True)
class GuardSettings:
    """Resolved ``Training.Guard`` block. ``Guard: true`` is shorthand
    for ``{"enabled": true}`` (skip policy, epoch-end cadence)."""

    enabled: bool = False
    policy: str = "skip"
    max_bad_steps: int = 3  # tolerated per window; escalate ABOVE this
    window_steps: int = 100
    check_interval_steps: int = 0  # 0 = epoch-end only (zero added syncs)
    lr_backoff: float = 0.5
    max_rollbacks: int = 2


def guard_settings(training: dict) -> GuardSettings:
    """Resolve ``Training.Guard`` into settings. Unknown keys are
    rejected eagerly by config.update_config — a misspelled
    ``max_bad_steps`` silently never escalating is exactly the failure
    class the guard exists to end."""
    raw = training.get("Guard") or {}
    if isinstance(raw, bool):
        raw = {"enabled": raw}
    elif not isinstance(raw, dict):
        raise ValueError(
            "Training.Guard must be a bool or an object "
            '{"enabled", "policy", "max_bad_steps", "window_steps", '
            '"check_interval_steps", "lr_backoff", "max_rollbacks"}'
        )
    policy = str(raw.get("policy", "skip"))
    if policy not in _POLICIES:
        raise ValueError(
            f"Training.Guard.policy {policy!r} not in {_POLICIES}"
        )
    backoff = float(raw.get("lr_backoff", 0.5))
    if not 0.0 < backoff <= 1.0:
        # A factor > 1 would RAISE the LR on every rollback and
        # re-walk the poisoned region hotter — the opposite of the
        # knob's purpose; <= 0 yields a non-positive LR.
        raise ValueError(
            f"Training.Guard.lr_backoff must be in (0, 1], got {backoff}"
        )
    return GuardSettings(
        enabled=bool(raw.get("enabled", False)),
        policy=policy,
        max_bad_steps=max(0, int(raw.get("max_bad_steps", 3))),
        window_steps=max(1, int(raw.get("window_steps", 100))),
        check_interval_steps=max(
            0, int(raw.get("check_interval_steps", 0))
        ),
        lr_backoff=backoff,
        max_rollbacks=max(0, int(raw.get("max_rollbacks", 2))),
    )


class GuardRollback(Exception):
    """Raised by the monitor when the bad-step window exceeds the
    threshold under the ``rollback`` policy — the epoch loop catches it,
    restores the last-known-good checkpoint, backs the LR off, and
    fast-forwards past the poisoned region."""

    def __init__(self, bad_steps: List[int], message: str):
        super().__init__(message)
        self.bad_steps = list(bad_steps)


class GuardHalt(RuntimeError):
    """The ladder's last rung: training cannot safely continue. The
    message is the actionable report (counts, provenance, where the
    last-known-good artifact lives)."""


# ----------------------------------------------------------------------
# Build-time fault injection (the drill harness). Every helper is a
# plain-Python no-op — zero traced ops — when no nan rule is armed.
# ----------------------------------------------------------------------


def nan_injections() -> Dict[str, List[int]]:
    """Armed ``nan:<site>@<step>`` rules, read ONCE at step-build time
    (utils/faults.nan_rules): ``{} `` means every ``poison_*`` call
    below returns its input object untouched."""
    from hydragnn_tpu.utils import faults

    return faults.nan_rules()


def _trigger(steps: List[int], step_counter):
    """Traced bool: does the on-device optimizer-step counter match an
    armed injection step? ``state.step`` always advances (guarded_commit
    re-applies the increment outside the select), so a rule consumes
    exactly one batch even when that batch's update is skipped."""
    import jax.numpy as jnp

    hit = jnp.asarray(False)
    for s in steps:
        hit = hit | (step_counter == jnp.asarray(s, step_counter.dtype))
    return hit


def poison_scalar(rules: Dict[str, List[int]], site: str, step_counter, x):
    """SELECT NaN at the armed steps. A select, never an add: an
    additive poison (``x + 0.0`` on untriggered steps) plants a
    ``mul + add`` pattern right after the value's producer, which
    LLVM's fp-contract pass fuses into an FMA inside scan bodies —
    a 1-ulp divergence on every HEALTHY step of an armed run (the
    PR-4 fusion hazard, measured). ``where`` passes the untaken side
    through bitwise."""
    steps = rules.get(site) if rules else None
    if not steps:
        return x
    import jax.numpy as jnp

    return jnp.where(
        _trigger(steps, step_counter), jnp.full_like(x, jnp.nan), x
    )


def poison_tree(rules: Dict[str, List[int]], site: str, step_counter, tree):
    """NaN every float leaf of ``tree`` (the gradient pytree) at the
    armed steps — same select-not-add discipline as poison_scalar.

    CAVEAT (measured on XLA:CPU): wrapping the gradient
    leaves in a select changes how XLA fuses the backward pass with
    the optimizer arithmetic, and LLVM's fp-contract decisions move
    with the fusion boundaries — an armed-but-untriggered ``grad``
    rule drifts params ~1 ulp per step vs an unarmed build (loss and
    batch sites measure exact). The bitwise drill contracts therefore
    ride the ``loss``/``batch`` sites; the ``grad`` site exists to
    exercise the grad-norm side of the predicate (skip-on-grad-NaN,
    state bitwise unchanged vs the same build's pre-step state)."""
    steps = rules.get(site) if rules else None
    if not steps:
        return tree
    import jax
    import jax.numpy as jnp

    hit = _trigger(steps, step_counter)

    def _p(g):
        if not jnp.issubdtype(g.dtype, jnp.floating):
            return g
        return jnp.where(hit, jnp.full_like(g, jnp.nan), g)

    return jax.tree_util.tree_map(_p, tree)


def poison_batch(rules: Dict[str, List[int]], step_counter, batch):
    """NaN the batch's node features at the armed steps — the bad-data
    case: loss AND grads both go non-finite downstream. Select, not
    add (see poison_scalar)."""
    steps = rules.get("batch") if rules else None
    if not steps:
        return batch
    import jax.numpy as jnp

    return batch.replace(
        x=jnp.where(
            _trigger(steps, step_counter),
            jnp.full_like(batch.x, jnp.nan),
            batch.x,
        )
    )


# ----------------------------------------------------------------------
# On-device detection + containment (traced into every guarded step —
# graftlint HOT_SEEDS covers these: a stray host sync here would fence
# every dispatch).
# ----------------------------------------------------------------------


@tr.scoped("guard")
def guarded_commit(old_state, new_state, tot, tasks, grads):
    """The guard's traced core: predicate + containment + metric mask.

    Returns ``(committed, tot_m, tasks_m, ok, gnorm)`` where

    - ``ok`` = ``isfinite(loss) & isfinite(global_grad_norm)`` — the
      finiteness predicate over both failure surfaces (a bf16 overflow
      can blow the grads while the loss still reads finite, and vice
      versa for a poisoned label);
    - ``committed`` is ``new_state`` when ok else ``old_state``, whole
      trees through a ``lax.cond`` (an exact passthrough, and a fusion
      fence: the optimizer's update fusions stay the unguarded build's,
      so a healthy run's params are bitwise the unguarded run's;
      optimizer state, BN stats and the Adam count all stay untouched
      on a skip, matching ``optax.apply_if_finite``), with ``step``
      ALWAYS advanced — fault/telemetry step addressing must tick once
      per batch, skipped or not;
    - ``tot_m`` / ``tasks_m`` are the loss terms with bad steps zeroed,
      so the epoch accumulator's op chain reproduces the
      poisoned-step-excluded run bitwise (``0 * w = 0`` folds, ``x +
      0.0 = x``).
    """
    import jax
    import jax.numpy as jnp
    import optax

    gnorm = optax.global_norm(grads)
    ok = jnp.isfinite(tot) & jnp.isfinite(gnorm)
    committed = jax.lax.cond(
        ok, lambda n, o: n, lambda n, o: o, new_state, old_state
    )
    committed = committed.replace(step=old_state.step + 1)
    tot_m = jnp.where(ok, tot, jnp.zeros_like(tot))
    tasks_m = jnp.where(ok, tasks, jnp.zeros_like(tasks))
    return committed, tot_m, tasks_m, ok, gnorm


# ----------------------------------------------------------------------
# Host-side monitor: deferred refs, window counting, the policy ladder.
# ----------------------------------------------------------------------


class GuardMonitor:
    """Drives the policy ladder from the deferred per-step predicate
    refs the guarded steps emit. ``observe`` runs between every
    dispatch (HOT path: list appends only, unless the opt-in sampled
    cadence is due); ``epoch_end`` resolves the epoch's refs in one
    batched fetch — AFTER the loop's own metrics fetch, which has
    already drained the device queue — emits the ``health`` row, and
    escalates per policy."""

    def __init__(
        self,
        settings: GuardSettings,
        verbosity: int = 0,
        branches: Optional[List[str]] = None,
    ):
        self.settings = settings
        self.verbosity = verbosity
        self.epoch = 0
        # ``branches``: slot labels when the guarded step emits a
        # PER-SLOT predicate vector instead of a scalar — the
        # multibranch scheme's ``[n_branches + 1]`` (branch decoders +
        # shared encoder; parallel.multibranch.branch_guard_labels).
        # The bad-step WINDOW is then kept per slot: escalation fires
        # when any single slot exceeds ``max_bad_steps`` in its
        # window, so one branch's repeated poison never escalates on
        # the strength of another branch's unrelated bad step.
        self.branches = list(branches) if branches else None
        # run-level ladder state. The window lives in RUN-GLOBAL step
        # coordinates: the epoch loop numbers steps per epoch, so a
        # per-epoch basis would never age a bad step out of a window
        # longer than one epoch. ``bad_steps_recent`` therefore holds
        # (global_step, epoch, epoch_step, bad_slots) tuples — global
        # for expiry, per-epoch for the rollback's plan-domain cursor,
        # slots for the per-branch windows.
        self.skipped_total = 0
        self.rollbacks = 0
        self.bad_steps_recent: List[tuple] = []  # cleared on rollback
        self.bad_steps_all: List[tuple] = []  # (epoch, epoch_step)
        self._last_gstep = 0
        self._epoch_base = 0  # global steps before the current epoch
        self._epoch_max_step = 0
        # epoch-level accounting (reset by note_epoch)
        self.epoch_bad: List[int] = []
        self._gn_min = float("inf")
        self._gn_max = 0.0
        self._gn_sum = 0.0
        self._gn_count = 0
        self._pending: List[tuple] = []  # (first_step, k, ok_ref, gnorm_ref)
        self._since_check = 0

    # -- loop-facing ---------------------------------------------------

    def note_epoch(self, epoch: int) -> None:
        self._epoch_base += self._epoch_max_step
        self._epoch_max_step = 0
        self.epoch = int(epoch)
        self.epoch_bad = []
        self._gn_min, self._gn_max = float("inf"), 0.0
        self._gn_sum, self._gn_count = 0.0, 0
        self._pending = []
        self._since_check = 0

    def observe(self, *, step: int, k: int, ok_ref, gnorm_ref) -> None:
        """One dispatch: ``step`` is the cumulative optimizer-step count
        AFTER it, ``k`` the steps it covered; ``ok_ref``/``gnorm_ref``
        are the step's predicate outputs — scalars for a single step,
        ``[K]`` vectors for a superstep macro. Holding a ref adds no
        arithmetic and no sync (they are fresh outputs, never donated
        back in); the sampled mid-epoch resolution below is the one
        opt-in host sync in the guard path."""
        self._pending.append((int(step) - int(k), int(k), ok_ref, gnorm_ref))
        if self.settings.check_interval_steps > 0:
            self._since_check += int(k)
            if self._since_check >= self.settings.check_interval_steps:
                self._since_check = 0
                self.check()

    def epoch_end(self) -> None:
        """Resolve the epoch's remaining refs, emit the per-epoch
        ``health`` row, escalate per policy. Runs at the existing
        epoch-end fetch point — the default cadence's only resolution,
        adding zero host syncs of its own (the loop's metrics fetch has
        just drained the queue)."""
        try:
            self.check()
        finally:
            self._emit_health("epoch")

    # -- resolution + ladder -------------------------------------------

    def check(self) -> None:
        """Resolve pending refs (ONE batched fetch) and run the ladder.
        Raises ``GuardRollback``/``GuardHalt`` per policy."""
        import jax
        import numpy as np

        if not self._pending:
            return
        pending, self._pending = self._pending, []
        refs = [r for p in pending for r in (p[2], p[3])]
        # graftlint: disable-next-line=host-sync -- the guard's designed resolution point: epoch-end (after the loop's own metrics fetch) or the opt-in Guard.check_interval_steps sampled cadence — never the default per-step path
        vals = jax.device_get(refs)
        new_bad: List[tuple] = []  # (epoch_step, bad_slot_indices)
        for i, (first_step, k, _, _) in enumerate(pending):
            # [k, n_slots]: scalar predicates (single/dp schemes) read
            # as one slot; multibranch emits one slot per branch
            # decoder + the shared encoder (branch_guard_labels order).
            oks = np.asarray(vals[2 * i]).reshape(k, -1)
            gns = np.asarray(vals[2 * i + 1], np.float64).reshape(k, -1)
            if gns.shape[1] > 1:
                # Per-slot partial norms (multibranch): the slots
                # partition the gradient tree, so the root-sum-square
                # IS the step's true global grad norm — the envelope
                # stats must keep the same semantics as the scalar
                # schemes' gnorm, not average partial norms (biased
                # low, count inflated by the slot count).
                gns = np.sqrt((gns**2).sum(axis=1))
            gns = gns.reshape(-1)
            finite_gns = gns[np.isfinite(gns)]
            if finite_gns.size:
                self._gn_min = min(self._gn_min, float(finite_gns.min()))
                self._gn_max = max(self._gn_max, float(finite_gns.max()))
                self._gn_sum += float(finite_gns.sum())
                self._gn_count += int(finite_gns.size)
            for j in range(k):
                if not bool(oks[j].all()):
                    new_bad.append(
                        (
                            first_step + j,
                            tuple(np.flatnonzero(~oks[j])),
                        )
                    )
            self._epoch_max_step = max(
                self._epoch_max_step, first_step + k
            )
            self._last_gstep = max(
                self._last_gstep, self._epoch_base + first_step + k
            )
        if not new_bad:
            return
        self.skipped_total += len(new_bad)
        self.epoch_bad.extend(b for b, _ in new_bad)
        self.bad_steps_recent.extend(
            (self._epoch_base + b, self.epoch, b, slots)
            for b, slots in new_bad
        )
        self.bad_steps_all.extend((self.epoch, b) for b, _ in new_bad)
        where = ""
        if self.branches:
            names = sorted(
                {
                    self.branches[s]
                    for _, slots in new_bad
                    for s in slots
                    if s < len(self.branches)
                }
            )
            where = f" [slots: {', '.join(names)}]"
        self._warn(
            f"non-finite step(s) SKIPPED on-device at optimizer "
            f"step(s) {[b for b, _ in new_bad]} (epoch {self.epoch})"
            f"{where} — loss/grad-norm predicate failed; the affected "
            "params/optimizer state untouched"
        )
        self._escalate()

    def _escalate(self) -> None:
        s = self.settings
        lo = self._last_gstep - s.window_steps
        self.bad_steps_recent = [
            b for b in self.bad_steps_recent if b[0] > lo
        ]
        # Escalation count: total bad steps in the window (scalar-
        # predicate schemes), or the WORST single slot's count under a
        # per-slot predicate — branch a's poison and branch b's poison
        # are independent incidents and must not sum into one
        # escalation (the per-branch window isolation contract).
        if self.branches is None:
            window_bad = len(self.bad_steps_recent)
        else:
            per_slot: Dict[int, int] = {}
            for _, _, _, slots in self.bad_steps_recent:
                for sl in slots:
                    per_slot[sl] = per_slot.get(sl, 0) + 1
            window_bad = max(per_slot.values(), default=0)
        if s.policy == "skip" or window_bad <= s.max_bad_steps:
            return
        if s.policy == "halt" or self.rollbacks >= s.max_rollbacks:
            self._emit_health("halt")
            raise GuardHalt(self.report(window_bad))
        # The rollback's plan-domain cursor wants CURRENT-epoch step
        # indices only (a previous epoch's bad steps aren't addresses
        # in this epoch's plan).
        raise_steps = [
            es for _, ep, es, _ in self.bad_steps_recent
            if ep == self.epoch
        ]
        self._emit_health("rollback")
        raise GuardRollback(
            raise_steps,
            f"{window_bad} bad step(s) within the last "
            f"{s.window_steps} steps (> max_bad_steps={s.max_bad_steps})"
            " — rolling back to the last-known-good checkpoint",
        )

    def note_rollback(self, cursor_step: int, new_lr: float) -> None:
        """Bookkeeping after the loop restored a checkpoint: count the
        rollback, clear the window (the replayed region must earn a new
        escalation), record the action."""
        self.rollbacks += 1
        self.bad_steps_recent = []
        self._pending = []
        self._since_check = 0
        self._warn(
            f"ROLLBACK #{self.rollbacks}: restored last-known-good "
            f"cursor step {cursor_step} of epoch {self.epoch}, lr backed "
            f"off to {new_lr:.3e}"
        )

    def report(self, window_bad: Optional[int] = None) -> str:
        """The actionable halt report."""
        from hydragnn_tpu.utils import faults

        recent = [
            f"e{ep}:s{es}" for ep, es in self.bad_steps_all[-16:]
        ]
        return (
            "training HALTED by the divergence guard: "
            f"{self.skipped_total} non-finite step(s) total "
            f"({window_bad if window_bad is not None else len(self.bad_steps_recent)}"
            f" in the last {self.settings.window_steps}-step window, "
            f"threshold {self.settings.max_bad_steps}), "
            f"{self.rollbacks}/{self.settings.max_rollbacks} rollback(s) "
            f"spent; recent bad optimizer steps {recent} "
            f"(epoch {self.epoch}); injected fault plan: "
            f"{faults.plan_spec()!r}. The last-known-good checkpoint is "
            "the newest artifact under logs/<run>/ (the writer's "
            "validate-finite gate never published a non-finite state). "
            "Likely causes: corrupted/outlier input data around those "
            "steps, an LR too hot for this precision, or bf16 "
            "activation overflow — inspect the telemetry `health` rows "
            "(tools/graftboard.py report), lower "
            "Training.Optimizer.learning_rate or set "
            "Training.Optimizer.clip_grad_norm, then `continue` from "
            "the checkpoint."
        )

    # -- reporting -----------------------------------------------------

    def gnorm_stats(self) -> Optional[dict]:
        if not self._gn_count:
            return None
        return {
            "gnorm_min": self._gn_min,
            "gnorm_max": self._gn_max,
            "gnorm_mean": self._gn_sum / self._gn_count,
            "gnorm_steps": self._gn_count,
        }

    def _emit_health(self, action: str) -> None:
        """One ``health`` row onto the active telemetry stream (a cheap
        no-op when telemetry is off) — the schema documented in
        docs/OBSERVABILITY.md."""
        from hydragnn_tpu.utils import faults, telemetry

        row: Dict[str, Any] = {
            "t": "health",
            "action": action,
            "epoch": self.epoch,
            "bad_steps": self.epoch_bad[-64:],
            "bad_count": len(self.epoch_bad),
            "window_bad": len(self.bad_steps_recent),
            "skipped_total": self.skipped_total,
            "rollbacks": self.rollbacks,
            "policy": self.settings.policy,
        }
        if self.branches:
            counts: Dict[str, int] = {}
            for _, _, _, slots in self.bad_steps_recent:
                for sl in slots:
                    name = (
                        self.branches[sl]
                        if sl < len(self.branches)
                        else f"slot{sl}"
                    )
                    counts[name] = counts.get(name, 0) + 1
            row["window_bad_by_branch"] = counts
        gn = self.gnorm_stats()
        if gn:
            row.update(gn)
        spec = faults.plan_spec()
        if spec:
            row["fault_plan"] = spec
        telemetry.emit(row)

    def _warn(self, msg: str) -> None:
        # Level-0 distributed print: guard events are always-on but
        # land once (process 0), matching the loop's print convention.
        from hydragnn_tpu.utils.print_utils import print_distributed

        print_distributed(self.verbosity, 0, f"[guard] {msg}")
