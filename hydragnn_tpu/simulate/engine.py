"""On-device MD rollout engine: scan-resident velocity-Verlet over MLIP
forces with guarded neighbor rebuilds (docs/SIMULATION.md).

The models this repo trains are interatomic potentials
(``train/mlip.energy_and_forces``: forces = -dE/dpos by construction);
this module is what an MLIP exists FOR — molecular dynamics. The whole
physics step lives on the accelerator:

- **Superstep discipline (PR 4)**: one Python dispatch runs K physics
  steps through a ``lax.scan`` whose body is (neighbor check → force →
  velocity-Verlet). Zero host round-trips inside a macro; the host's
  only per-macro work is one bounded flag fetch at the policy point.
- **Guarded neighbor rebuilds**: the fixed-capacity
  ``ops/neighbors.radius_graph_jax`` builder (the map-sparse-onto-
  dense thesis of arxiv 1906.11786 applied to the neighbor list) runs
  under a skin-distance displacement check INSIDE the scan — most
  steps reuse the cached list, and a rebuild is an on-device
  ``lax.cond`` event, never a host decision.
- **Containment (PR-10 idiom)**: an overflowed neighbor capacity or a
  non-finite energy/force/position flips a sticky on-device predicate,
  and every subsequent step of the macro commits via select-not-add —
  the poisoned suffix is a no-op and the state at the last good step
  is bit-preserved. The host policy ladder then rebuilds with larger
  capacity (overflow), halves dt (non-finite), or halts — never
  silent corruption.
- **Durability (PR 6)**: trajectory checkpoints ride the async
  ``CheckpointWriter`` (validate-finite gate included); a rollout
  resumes bitwise from the container (the ``md_replay_drill``
  contract).
- **Observability (PR 7)**: every macro emits a ``rollout`` row on the
  telemetry stream (steps/dispatch, rebuild count, overflow/non-finite
  flags, energy drift, ns/day); ``graftboard report`` renders them as
  the simulation section (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.data.graph import GraphBatch
from hydragnn_tpu.models.spec import ModelConfig
from hydragnn_tpu.ops.neighbors import radius_graph_jax
from hydragnn_tpu.simulate import integrators
from hydragnn_tpu.simulate.state import (
    MDState,
    kinetic_energy,
    maxwell_boltzmann_velocities,
    md_template_batch,
)
from hydragnn_tpu.train import mlip
from hydragnn_tpu.train.guard import nan_injections, poison_scalar

__all__ = [
    "NeighborSettings",
    "SimGuardSettings",
    "SimulationSettings",
    "simulation_settings",
    "RolloutHalt",
    "RolloutResult",
    "RolloutEngine",
    "run_simulation",
]

# Boltzmann constant in eV/K — the right ``kb`` for eV/Angstrom MLIPs
# (md17-class data). Reduced-unit systems (the LJ example/drills) set
# ``Simulation.kb: 1.0``.
KB_EV_PER_K = 8.617333262e-5

_THERMOSTATS = ("none", "langevin")
_REBUILD_POLICIES = ("displacement", "always", "never")
_NONFINITE_POLICIES = ("dt_halve", "halt")


@dataclass(frozen=True)
class NeighborSettings:
    """``Simulation.neighbor``: the fixed-capacity skin list. The list
    is built at ``cutoff + skin`` and stays valid while no atom moved
    more than ``skin/2`` since the build (the classic Verlet-skin
    invariant, checked on-device every step)."""

    skin: float = 0.5
    max_edges: int = 512
    rebuild_policy: str = "displacement"


@dataclass(frozen=True)
class SimGuardSettings:
    """``Simulation.guard``: the containment policy ladder. Overflow →
    grow capacity (``capacity_growth``x, at most
    ``max_capacity_growths`` times); non-finite → halve dt (at most
    ``max_dt_halvings`` times) or halt, per ``on_nonfinite``. The
    ladder's floor is always a loud ``RolloutHalt`` — never silent
    corruption."""

    enabled: bool = True
    max_capacity_growths: int = 2
    capacity_growth: float = 2.0
    max_dt_halvings: int = 2
    on_nonfinite: str = "dt_halve"


@dataclass(frozen=True)
class SimulationSettings:
    """Resolved top-level ``Simulation`` config block."""

    steps: int = 100
    dt: float = 1e-3
    superstep_k: int = 16
    temperature_k: float = 0.0
    thermostat: str = "none"
    friction: float = 1.0
    kb: float = KB_EV_PER_K
    mass: float = 1.0
    seed: int = 0
    record_trajectory: bool = False
    log_name: str = "md_rollout"
    checkpoint_enabled: bool = False
    checkpoint_interval_steps: int = 0
    neighbor: NeighborSettings = field(default_factory=NeighborSettings)
    guard: SimGuardSettings = field(default_factory=SimGuardSettings)


def simulation_settings(config: dict) -> SimulationSettings:
    """Resolve ``config["Simulation"]`` into settings. Unknown keys are
    rejected eagerly by config.update_config — a misspelled
    ``superstep_k`` silently running per-step dispatch is exactly the
    throughput cliff the macro engine exists to end."""
    raw = (config.get("Simulation") or {}) if config else {}
    nb = raw.get("neighbor") or {}
    gd = raw.get("guard")
    if isinstance(gd, bool):
        gd = {"enabled": gd}
    gd = gd or {}
    ck = raw.get("checkpoint")
    if isinstance(ck, bool):
        ck = {"enabled": ck}
    ck = ck or {}
    thermostat = str(raw.get("thermostat", "none"))
    if thermostat not in _THERMOSTATS:
        raise ValueError(
            f"Simulation.thermostat {thermostat!r} not in {_THERMOSTATS}"
        )
    policy = str(nb.get("rebuild_policy", "displacement"))
    if policy not in _REBUILD_POLICIES:
        raise ValueError(
            f"Simulation.neighbor.rebuild_policy {policy!r} not in "
            f"{_REBUILD_POLICIES}"
        )
    on_nf = str(gd.get("on_nonfinite", "dt_halve"))
    if on_nf not in _NONFINITE_POLICIES:
        raise ValueError(
            f"Simulation.guard.on_nonfinite {on_nf!r} not in "
            f"{_NONFINITE_POLICIES}"
        )
    steps = int(raw.get("steps", 100))
    dt = float(raw.get("dt", 1e-3))
    if steps <= 0 or dt <= 0.0:
        raise ValueError(
            f"Simulation.steps ({steps}) and Simulation.dt ({dt}) must "
            "be positive"
        )
    growth = float(gd.get("capacity_growth", 2.0))
    if growth <= 1.0:
        # A growth factor <= 1 can never outgrow an overflow: the
        # rebuild rung of the ladder would spin forever at the same
        # capacity.
        raise ValueError(
            f"Simulation.guard.capacity_growth must be > 1, got {growth}"
        )
    return SimulationSettings(
        steps=steps,
        dt=dt,
        superstep_k=max(1, int(raw.get("superstep_k", 16))),
        temperature_k=float(raw.get("temperature_k", 0.0)),
        thermostat=thermostat,
        friction=float(raw.get("friction", 1.0)),
        kb=float(raw.get("kb", KB_EV_PER_K)),
        mass=float(raw.get("mass", 1.0)),
        seed=int(raw.get("seed", 0)),
        record_trajectory=bool(raw.get("record_trajectory", False)),
        log_name=str(raw.get("log_name", "md_rollout")),
        checkpoint_enabled=bool(ck.get("enabled", False)),
        checkpoint_interval_steps=max(
            0, int(ck.get("interval_steps", 0))
        ),
        neighbor=NeighborSettings(
            skin=float(nb.get("skin", 0.5)),
            max_edges=int(nb.get("max_edges", 512)),
            rebuild_policy=policy,
        ),
        guard=SimGuardSettings(
            enabled=bool(gd.get("enabled", True)),
            max_capacity_growths=max(
                0, int(gd.get("max_capacity_growths", 2))
            ),
            capacity_growth=growth,
            max_dt_halvings=max(0, int(gd.get("max_dt_halvings", 2))),
            on_nonfinite=on_nf,
        ),
    )


def macro_plan(n_steps: int, superstep_k: int) -> List[int]:
    """Per-dispatch trip counts for a clean rollout of ``n_steps``:
    the exact chunking ``RolloutEngine.run`` walks when no containment
    event fires — full K macros plus one shorter tail. Pure host
    arithmetic; tests/test_simulate.py holds a real rollout's
    dispatches to exactly this plan."""
    k = max(1, int(superstep_k))
    out: List[int] = []
    left = int(n_steps)
    while left > 0:
        out.append(min(k, left))
        left -= out[-1]
    return out


class RolloutHalt(RuntimeError):
    """The containment ladder's floor: the rollout cannot safely
    continue (capacity growths / dt halvings exhausted, or the policy
    is ``halt``). The message is the actionable report; ``state``
    carries the bit-preserved last good MDState."""

    def __init__(self, message: str, state: Optional[MDState] = None):
        super().__init__(message)
        self.state = state


@dataclass
class RolloutResult:
    """Host-side rollout outcome. ``energies``/``kinetic`` hold one
    entry per COMMITTED physics step (containment no-ops are filtered
    out); ``trajectory``/``velocities`` are ``[steps, N, 3]`` when
    recording was on, else None."""

    state: Any
    energies: np.ndarray
    kinetic: np.ndarray
    trajectory: Optional[np.ndarray]
    velocities: Optional[np.ndarray]
    stats: Dict[str, Any]


class RolloutEngine:
    """Compiles and drives the scan-resident MD step.

    Static per engine: the model + variables, the template batch
    (species/masks, edge arrays at neighbor capacity E), masses,
    cutoff/skin, the thermostat kind and K. Dynamic per dispatch: the
    MDState carry and the (dt, friction, kT) scalars — passed as
    traced device scalars so the dt-halving policy rung never
    recompiles. Growing the neighbor capacity DOES recompile (shapes
    are static); that is the policy ladder's documented cost and the
    reason overflow is a macro-boundary event, not a per-step one.
    """

    def __init__(
        self,
        model,
        variables: dict,
        cfg: ModelConfig,
        template: GraphBatch,
        settings: SimulationSettings,
    ):
        if cfg.radius is None:
            raise ValueError(
                "RolloutEngine needs Architecture.radius (the model "
                "cutoff) to build neighbor lists"
            )
        self.model = model
        self.variables = variables
        self.cfg = cfg
        self.template = template
        self.settings = settings
        self.cutoff = float(cfg.radius)
        self.max_edges = int(settings.neighbor.max_edges)
        n = template.node_mask.shape[0]
        self.masses = jnp.full((n, 1), float(settings.mass), jnp.float32)
        mask = template.node_mask.astype(jnp.float32)[:, None]
        # Padding rows get inv_mass 0 so padded velocities stay 0.
        self.inv_masses = mask / self.masses
        self.capacity_growths = 0
        self.dt_halvings = 0
        self.dt = float(settings.dt)
        self._macros: Dict[Tuple[int, bool], Any] = {}
        self._nan_rules = nan_injections()
        self._neighbor = jax.jit(self._neighbor_impl)
        self._init_forces = jax.jit(self._init_forces_impl)

    # -- traced pieces -------------------------------------------------

    def _list_radius(self) -> float:
        return self.cutoff + float(self.settings.neighbor.skin)

    def _neighbor_impl(self, pos):
        """Fixed-capacity skin list at the current capacity. Traced
        into the scan body's rebuild branch (and jitted standalone for
        init / capacity growth)."""
        t = self.template
        return radius_graph_jax(
            pos,
            self._list_radius(),
            t.node_graph_idx,
            t.node_mask,
            self.max_edges,
        )

    def _energy_forces(self, pos, senders, receivers, edge_mask):
        batch = self.template.replace(
            pos=pos,
            senders=senders,
            receivers=receivers,
            edge_mask=edge_mask,
        )
        graph_e, forces, _ = mlip.energy_and_forces(
            self.model, self.variables, batch, self.cfg, train=False
        )
        # One real graph in slot 0 (slot 1 is the padding graph).
        return graph_e[0], forces

    def _init_forces_impl(self, state: MDState) -> MDState:
        """Forces/energy at the state's positions under its CURRENT
        neighbor list — the rollout's t=0 force pass (and the post-
        capacity-growth refresh)."""
        energy, forces = self._energy_forces(
            state.pos, state.senders, state.receivers, state.edge_mask
        )
        return state.replace(energy=energy, forces=forces)

    def _build_macro(self, k: int, record: bool):
        """The jitted K-step macro: ``(state, dt, gamma, kt) ->
        (state, ys)``. The scan body is the hottest region of the
        subsystem — it runs millions of times per simulation
        (graftlint HOT_SEEDS covers it; zero host syncs, pure traced
        work)."""
        s = self.settings
        thermostat = s.thermostat
        policy = s.neighbor.rebuild_policy
        skin = float(s.neighbor.skin)
        node_mask = self.template.node_mask
        inv_m = self.inv_masses
        masses = self.masses
        rules = self._nan_rules

        def macro(state, dt, gamma, kt):
            def body(st: MDState, _):
                key = st.key
                vel = st.vel
                if thermostat == "langevin":
                    vel, key = integrators.ou_half_step(
                        vel, key, gamma, kt, masses, node_mask, dt
                    )
                vel = integrators.half_kick(vel, st.forces, inv_m, dt)
                pos = integrators.drift(st.pos, vel, dt)

                # Verlet-skin displacement check: rebuild when any
                # real atom moved > skin/2 since the cached list was
                # built. Padding rows never move, so the unmasked max
                # is exact.
                if policy == "always":
                    need = jnp.asarray(True)
                elif policy == "never":
                    need = jnp.asarray(False)
                else:
                    d2 = jnp.sum((pos - st.ref_pos) ** 2, axis=-1)
                    need = jnp.max(d2) > (0.5 * skin) ** 2

                def _rebuild(p):
                    snd, rcv, em, ovf = self._neighbor_impl(p)
                    return snd, rcv, em, p, ovf, jnp.asarray(True)

                def _reuse(p):
                    return (
                        st.senders,
                        st.receivers,
                        st.edge_mask,
                        st.ref_pos,
                        jnp.asarray(0, jnp.int32),
                        jnp.asarray(False),
                    )

                snd, rcv, em, ref_pos, ovf, rebuilt = jax.lax.cond(
                    need, _rebuild, _reuse, pos
                )

                energy, forces = self._energy_forces(pos, snd, rcv, em)
                # Fault-injection site (utils/faults.py
                # ``nan:force@step``): a SELECT, never an add — the
                # PR-10 fp-contract discipline keeps untriggered steps
                # bitwise inert.
                forces = poison_scalar(rules, "force", st.step, forces)

                vel = integrators.half_kick(vel, forces, inv_m, dt)
                if thermostat == "langevin":
                    vel, key = integrators.ou_half_step(
                        vel, key, gamma, kt, masses, node_mask, dt
                    )

                # Containment predicate: finite energy/forces/positions
                # AND a neighbor list that fit its capacity. The select
                # commits the new state only while the macro is clean;
                # the poisoned suffix is a no-op and the last good
                # step's state is bit-preserved (jnp.where passes the
                # taken side through exactly).
                ok = (
                    jnp.isfinite(energy)
                    & jnp.all(jnp.isfinite(forces))
                    & jnp.all(jnp.isfinite(pos))
                    & (ovf == 0)
                )
                alive = ok & ~st.poisoned

                def sel(new, old):
                    return jnp.where(alive, new, old)

                committed = MDState(
                    pos=sel(pos, st.pos),
                    vel=sel(vel, st.vel),
                    forces=sel(forces, st.forces),
                    energy=sel(energy, st.energy),
                    senders=sel(snd, st.senders),
                    receivers=sel(rcv, st.receivers),
                    edge_mask=sel(em, st.edge_mask),
                    ref_pos=sel(ref_pos, st.ref_pos),
                    key=sel(key, st.key),
                    # ``step`` ALWAYS advances (outside the select):
                    # fault addressing must tick once per scan
                    # iteration so one armed rule fires exactly once,
                    # committed or not.
                    step=st.step + 1,
                    good_steps=st.good_steps + alive.astype(jnp.int32),
                    rebuilds=st.rebuilds
                    + (alive & rebuilt).astype(jnp.int32),
                    # Diagnostics survive containment: the host policy
                    # needs the overflow size it must outgrow even
                    # though the overflowed list was never committed.
                    overflow=jnp.maximum(st.overflow, ovf),
                    poisoned=st.poisoned | ~ok,
                )
                ke = kinetic_energy(committed.vel, masses, node_mask)
                ys = (committed.energy, ke, alive, rebuilt & alive)
                if record:
                    ys = ys + (committed.pos, committed.vel)
                return committed, ys

            return jax.lax.scan(body, state, None, length=k)

        return jax.jit(macro)

    def _macro(self, k: int, record: bool):
        key = (int(k), bool(record))
        fn = self._macros.get(key)
        if fn is None:
            fn = self._build_macro(int(k), bool(record))
            self._macros[key] = fn
        return fn

    # -- host-side lifecycle -------------------------------------------

    def init_state(self, pos=None, *, seed: Optional[int] = None) -> MDState:
        """Fresh MDState at ``pos`` (default: the template positions):
        thermal velocities, a freshly built neighbor list, and the t=0
        force pass."""
        s = self.settings
        t = self.template
        pos = t.pos if pos is None else jnp.asarray(pos, jnp.float32)
        if pos.shape != t.pos.shape:
            raise ValueError(
                f"pos shape {pos.shape} != template {t.pos.shape} — "
                "build the template from the same configuration"
            )
        key = jax.random.PRNGKey(s.seed if seed is None else int(seed))
        key, vkey = jax.random.split(key)
        kt = s.kb * s.temperature_k
        if kt > 0.0:
            vel = maxwell_boltzmann_velocities(
                vkey, t.node_mask, self.masses, kt
            )
        else:
            vel = jnp.zeros_like(pos)
        snd, rcv, em, ovf = self._neighbor(pos)
        state = MDState(
            pos=pos,
            vel=vel,
            forces=jnp.zeros_like(pos),
            energy=jnp.asarray(0.0, jnp.float32),
            senders=snd,
            receivers=rcv,
            edge_mask=em,
            ref_pos=pos,
            key=key,
            step=jnp.asarray(0, jnp.int32),
            good_steps=jnp.asarray(0, jnp.int32),
            rebuilds=jnp.asarray(0, jnp.int32),
            overflow=ovf.astype(jnp.int32),
            poisoned=jnp.asarray(False),
        )
        # An initial configuration that already overflows the capacity
        # is a containment event at t=0: flagged here, escalated at
        # run()'s first policy check — never a silently truncated list.
        # graftlint: disable-next-line=host-sync -- one-shot rollout init: reads the t=0 overflow count once, before the macro loop starts
        if int(jax.device_get(ovf)) > 0:
            return state.replace(poisoned=jnp.asarray(True))
        return self._init_forces(state)

    def reset_containment(self, state: MDState) -> MDState:
        """Host-side, between macros: clear the sticky poison flag and
        the overflow high-water mark after a policy action."""
        return state.replace(
            poisoned=jnp.asarray(False),
            overflow=jnp.asarray(0, jnp.int32),
        )

    def grow_capacity(self, state: MDState, need: int) -> MDState:
        """Overflow rung of the ladder: grow ``max_edges`` past the
        reported need, drop the compiled macros (shapes changed),
        rebuild the neighbor list at the preserved positions, and
        refresh forces under the complete list."""
        growth = self.settings.guard.capacity_growth
        new_cap = int(np.ceil(self.max_edges * growth))
        while new_cap < self.max_edges + need:
            new_cap = int(np.ceil(new_cap * growth))
        self.max_edges = new_cap
        self.capacity_growths += 1
        pad_node = self.template.node_mask.shape[0] - 1
        self.template = self.template.replace(
            senders=jnp.full((new_cap,), pad_node, jnp.int32),
            receivers=jnp.full((new_cap,), pad_node, jnp.int32),
            edge_mask=jnp.zeros((new_cap,), bool),
        )
        self._macros = {}
        self._neighbor = jax.jit(self._neighbor_impl)
        self._init_forces = jax.jit(self._init_forces_impl)
        snd, rcv, em, ovf = self._neighbor(state.pos)
        state = self.reset_containment(state).replace(
            senders=snd,
            receivers=rcv,
            edge_mask=em,
            ref_pos=state.pos,
            overflow=ovf.astype(jnp.int32),
        )
        # graftlint: disable-next-line=host-sync -- policy-ladder rung (macro boundary): reads the post-growth overflow count once per capacity growth
        if int(jax.device_get(ovf)) > 0:
            # Still too small (pathological density spike): mark and
            # let the ladder spend another growth or halt.
            return state.replace(poisoned=jnp.asarray(True))
        return self._init_forces(state)

    def spec(self) -> str:
        n = int(self.template.node_mask.shape[0])
        return f"n{n}_e{self.max_edges}"

    # -- ladder persistence (the resume contract) ----------------------

    def ladder_state(self) -> Dict[str, Any]:
        """The policy ladder's host-side state, persisted in every
        trajectory checkpoint's manifest (the writer's ``loop`` slot):
        a resumed rollout must integrate at the dt the run had reached
        and at the neighbor capacity its state arrays were saved at —
        config alone names only the STARTING rungs."""
        return {
            "dt": self.dt,
            "dt_halvings": self.dt_halvings,
            "max_edges": self.max_edges,
            "capacity_growths": self.capacity_growths,
        }

    def adopt_ladder(self, ladder: Optional[Dict[str, Any]]) -> None:
        """Restore the ladder from a checkpoint manifest BEFORE the
        restored MDState is used: the saved edge arrays carry the
        capacity at save time, so the template/compiled shapes must
        match it, and the saved trajectory was integrated at the saved
        dt, so continuing at the config dt would silently diverge."""
        if not ladder:
            return
        self.dt = float(ladder.get("dt", self.dt))
        self.dt_halvings = int(ladder.get("dt_halvings", self.dt_halvings))
        self.capacity_growths = int(
            ladder.get("capacity_growths", self.capacity_growths)
        )
        cap = int(ladder.get("max_edges", self.max_edges))
        if cap != self.max_edges:
            self.max_edges = cap
            pad_node = self.template.node_mask.shape[0] - 1
            self.template = self.template.replace(
                senders=jnp.full((cap,), pad_node, jnp.int32),
                receivers=jnp.full((cap,), pad_node, jnp.int32),
                edge_mask=jnp.zeros((cap,), bool),
            )
            self._macros = {}
            self._neighbor = jax.jit(self._neighbor_impl)
            self._init_forces = jax.jit(self._init_forces_impl)

    # -- the rollout loop ----------------------------------------------

    def run(
        self,
        state: MDState,
        n_steps: Optional[int] = None,
        *,
        record: Optional[bool] = None,
        writer=None,
    ) -> RolloutResult:
        """Drive ``n_steps`` committed physics steps from ``state``.

        The loop dispatches K-step macros (a tail shorter than K is a
        separately compiled trip count of the same scan body — the
        per-step arithmetic is identical, which is what the replay
        drill's K-macro == serial bitwise contract rides on). After
        each dispatch ONE bounded fetch reads the flags + per-step ys;
        that is the designed policy point — amortized over K physics
        steps — where containment events escalate through the ladder
        and the ``rollout`` telemetry row is emitted. ``writer`` (a
        PR-6 CheckpointWriter) saves the MDState every
        ``checkpoint_interval_steps`` committed steps.
        """
        from hydragnn_tpu.utils import telemetry

        s = self.settings
        if n_steps is None:
            n_steps = s.steps
        if record is None:
            record = s.record_trajectory
        k_cfg = max(1, int(s.superstep_k))
        energies: List[np.ndarray] = []
        kinetic: List[np.ndarray] = []
        traj: List[np.ndarray] = []
        vels: List[np.ndarray] = []
        events: List[dict] = []
        macro_idx = 0
        e0: Optional[float] = None
        t_run0 = time.perf_counter()

        # A state initialized/restored into a containment event is a
        # policy decision BEFORE the first macro.
        state = self._policy_gate(state, events)

        # graftlint: disable-next-line=host-sync -- one-shot rollout entry: reads the resume cursor once before the macro loop
        good = int(jax.device_get(state.good_steps))
        base_good = good
        # Checkpoint cadence anchors at the resume cursor, not 0 — a
        # resumed rollout must not re-save on its first macro.
        last_ckpt = base_good
        target = base_good + int(n_steps)
        while good < target:
            k = min(k_cfg, target - good)
            fn = self._macro(k, record)
            t0 = time.perf_counter()
            state, ys = fn(
                state,
                jnp.asarray(self.dt, jnp.float32),
                jnp.asarray(s.friction, jnp.float32),
                jnp.asarray(s.kb * s.temperature_k, jnp.float32),
            )
            # The designed per-macro resolution point: ONE bounded
            # fetch of the containment flags + per-step rows, amortized
            # over the K physics steps the dispatch covered — the
            # rollout analog of the guard's sampled cadence.
            # graftlint: disable-next-line=host-sync -- the per-macro policy point: one bounded flag/ys fetch per K-step dispatch (docs/SIMULATION.md)
            fetched = jax.device_get(
                (
                    state.good_steps,
                    state.rebuilds,
                    state.overflow,
                    state.poisoned,
                    state.energy,
                    ys,
                )
            )
            dispatch_ms = 1e3 * (time.perf_counter() - t0)
            good_now, rebuilds, overflow, poisoned, energy, ys_h = fetched
            good_now = int(good_now)
            alive = np.asarray(ys_h[2], bool)
            energies.append(np.asarray(ys_h[0])[alive])
            kinetic.append(np.asarray(ys_h[1])[alive])
            if record:
                traj.append(np.asarray(ys_h[4])[alive])
                vels.append(np.asarray(ys_h[5])[alive])
            if e0 is None:
                for arr in energies:
                    if arr.size:
                        e0 = float(arr[0])
                        break
            drift = float(energy) - e0 if e0 is not None else 0.0
            wall_s = max(time.perf_counter() - t_run0, 1e-9)
            steps_per_sec = (good_now - base_good) / wall_s
            telemetry.emit(
                {
                    "t": "rollout",
                    "macro": macro_idx,
                    "step": good_now,
                    "k": int(k),
                    "committed": good_now - good,
                    "dt": self.dt,
                    "spec": self.spec(),
                    "energy": float(energy),
                    "drift": drift,
                    "rebuilds": int(rebuilds),
                    "overflow": int(overflow),
                    "nonfinite": bool(poisoned) and int(overflow) == 0,
                    "dispatch_ms": round(dispatch_ms, 3),
                    "steps_per_sec": round(steps_per_sec, 3),
                    # dt is interpreted in femtoseconds for this rate
                    # (docs/SIMULATION.md "Units") — reduced-unit runs
                    # read it as a relative throughput only.
                    "ns_per_day": round(
                        steps_per_sec * self.dt * 86400.0 / 1e6, 6
                    ),
                }
            )
            macro_idx += 1
            good = good_now
            if bool(poisoned):
                state = self._policy_gate(state, events)
            if (
                writer is not None
                and s.checkpoint_interval_steps > 0
                and good - last_ckpt >= s.checkpoint_interval_steps
            ):
                writer.save(
                    state,
                    kind="auto",
                    epoch=0,
                    step=good,
                    loop=self.ladder_state(),
                )
                last_ckpt = good
        if writer is not None:
            writer.save(
                state,
                kind="final",
                epoch=0,
                step=good,
                loop=self.ladder_state(),
            )

        energies_np = (
            np.concatenate(energies) if energies else np.zeros(0)
        )
        kinetic_np = np.concatenate(kinetic) if kinetic else np.zeros(0)
        stats = {
            "steps": good - base_good,
            "macros": macro_idx,
            "rebuilds": int(rebuilds) if macro_idx else 0,
            "dt": self.dt,
            "dt_halvings": self.dt_halvings,
            "capacity": self.max_edges,
            "capacity_growths": self.capacity_growths,
            "events": events,
            "energy_drift": (
                float(energies_np[-1] + kinetic_np[-1])
                - float(energies_np[0] + kinetic_np[0])
                if energies_np.size
                else 0.0
            ),
            "steps_per_sec": (good - base_good)
            / max(time.perf_counter() - t_run0, 1e-9),
        }
        return RolloutResult(
            state=state,
            energies=energies_np,
            kinetic=kinetic_np,
            trajectory=np.concatenate(traj) if traj else None,
            velocities=np.concatenate(vels) if vels else None,
            stats=stats,
        )

    # -- policy ladder -------------------------------------------------

    def _policy_gate(self, state: MDState, events: List[dict]) -> MDState:
        """Escalate a poisoned state through the ladder: overflow →
        grow capacity, non-finite → halve dt, exhaustion/halt-policy →
        RolloutHalt. A clean state passes through untouched."""
        # graftlint: disable-next-line=host-sync -- macro-boundary policy decision: two scalars, read after the run loop's batched fetch already drained the macro
        poisoned, overflow = jax.device_get(
            (state.poisoned, state.overflow)
        )
        if not bool(poisoned):
            return state
        guard = self.settings.guard
        if not guard.enabled:
            raise RolloutHalt(
                self._halt_report(state, int(overflow), "guard disabled"),
                state,
            )
        if int(overflow) > 0:
            if self.capacity_growths >= guard.max_capacity_growths:
                self._emit_event(events, "halt", overflow=int(overflow))
                raise RolloutHalt(
                    self._halt_report(
                        state,
                        int(overflow),
                        "neighbor capacity growths exhausted",
                    ),
                    state,
                )
            old_cap = self.max_edges
            state = self.grow_capacity(state, int(overflow))
            self._emit_event(
                events,
                "rebuild",
                overflow=int(overflow),
                capacity_from=old_cap,
                capacity_to=self.max_edges,
            )
            # Pathological case: still overflowing — recurse up the
            # ladder (bounded by max_capacity_growths).
            return self._policy_gate(state, events)
        # Non-finite energy/forces/positions.
        if (
            guard.on_nonfinite == "halt"
            or self.dt_halvings >= guard.max_dt_halvings
        ):
            self._emit_event(events, "halt", nonfinite=True)
            raise RolloutHalt(
                self._halt_report(
                    state,
                    0,
                    "non-finite energy/forces"
                    + (
                        ""
                        if guard.on_nonfinite == "halt"
                        else " (dt halvings exhausted)"
                    ),
                ),
                state,
            )
        self.dt *= 0.5
        self.dt_halvings += 1
        self._emit_event(events, "dt_halve", dt=self.dt)
        return self.reset_containment(state)

    def _emit_event(self, events: List[dict], action: str, **kw) -> None:
        from hydragnn_tpu.utils import telemetry
        from hydragnn_tpu.utils.print_utils import print_distributed

        row = {"t": "rollout_event", "action": action, **kw}
        events.append({"action": action, **kw})
        telemetry.emit(row)
        print_distributed(0, 0, f"[rollout] containment: {row}")

    def _halt_report(self, state: MDState, overflow: int, why: str) -> str:
        from hydragnn_tpu.utils import faults

        # graftlint: disable-next-line=host-sync -- halt path: the rollout is over; the report reads one scalar
        good = int(jax.device_get(state.good_steps))
        return (
            f"rollout HALTED by the containment guard: {why} at "
            f"committed step {good} (neighbor capacity "
            f"{self.max_edges}, overflow {overflow}, dt {self.dt}, "
            f"{self.capacity_growths} capacity growth(s), "
            f"{self.dt_halvings} dt halving(s) spent; injected fault "
            f"plan: {faults.plan_spec()!r}). The returned state is the "
            "last good step, bit-preserved — raise "
            "Simulation.neighbor.max_edges, lower Simulation.dt, or "
            "inspect the telemetry `rollout` rows (tools/graftboard.py "
            "report)."
        )


# ----------------------------------------------------------------------
# Public entry


def run_simulation(
    config: dict,
    *,
    sample=None,
    model=None,
    cfg: Optional[ModelConfig] = None,
    state=None,
    variables: Optional[dict] = None,
    log_name: Optional[str] = None,
    resume: bool = False,
) -> RolloutResult:
    """Run the ``Simulation`` block of ``config`` over an MLIP.

    ``sample`` is the initial configuration (a GraphSample with ``x``
    and ``pos``); ``model``/``cfg`` + (``state`` | ``variables``)
    supply the potential — typically the returns of ``run_training``.
    When model/cfg are omitted they are created from the config
    (random-init weights: still a smooth potential — what the
    conservation drill integrates). ``resume=True`` restores the
    newest trajectory checkpoint written by a previous run under the
    same log name and continues until ``Simulation.steps`` committed
    steps.
    """
    from hydragnn_tpu.utils import telemetry
    from hydragnn_tpu.utils.checkpoint import (
        CheckpointWriter,
        load_resume_checkpoint,
    )

    s = simulation_settings(config)
    if sample is None:
        raise ValueError(
            "run_simulation needs an initial configuration "
            "(sample=GraphSample with x and pos)"
        )
    if model is None or cfg is None:
        from hydragnn_tpu.models.create import create_model_config

        model, cfg = create_model_config(config)
    if variables is None:
        if state is not None:
            variables = {
                "params": state.params,
                "batch_stats": state.batch_stats,
            }
        else:
            from hydragnn_tpu.data.graph import collate
            from hydragnn_tpu.models.create import init_params

            params, bs = init_params(model, collate([sample]))
            variables = {"params": params, "batch_stats": bs}

    template = md_template_batch(
        np.asarray(sample.x), np.asarray(sample.pos), s.neighbor.max_edges
    )
    engine = RolloutEngine(model, variables, cfg, template, s)
    log = log_name or s.log_name

    own_stream = None
    if not telemetry.active():
        training = (
            config.get("NeuralNetwork", {}).get("Training", {})
            if config
            else {}
        )
        own_stream = telemetry.configure(training, log)

    writer = None
    md0 = engine.init_state()
    done_steps = 0
    if resume:
        restored, manifest = load_resume_checkpoint(log, md0)
        if manifest is not None:
            # The ladder must be adopted BEFORE the state is used: the
            # saved edge arrays carry the capacity at save time, and
            # the run had reached the saved dt — integrating at the
            # config rungs would trace at the wrong shape or silently
            # diverge from the interrupted trajectory.
            engine.adopt_ladder(manifest.get("loop"))
            md0 = restored
            done_steps = int(manifest.get("step", 0))
    if s.checkpoint_enabled:
        writer = CheckpointWriter(log)
    try:
        result = engine.run(
            md0, max(0, s.steps - done_steps), writer=writer
        )
    finally:
        if writer is not None:
            writer.close()
        if own_stream is not None:
            telemetry.close_run(own_stream)
    return result
