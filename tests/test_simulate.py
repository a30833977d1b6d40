"""MD rollout engine (hydragnn_tpu/simulate/, docs/SIMULATION.md):
conservation on the NVE path, the bitwise K-macro == serial replay
contract (with neighbor rebuilds and the Langevin thermostat in the
loop), containment of injected overflow/non-finite events through the
policy ladder, interrupt/resume through the PR-6 writer, rollout
telemetry rows, and the config surface."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tests._cpu  # noqa: F401

from hydragnn_tpu.data.graph import GraphSample, collate
from hydragnn_tpu.models.create import create_model, init_params
from hydragnn_tpu.ops.neighbors import radius_graph
from hydragnn_tpu.simulate import (
    RolloutEngine,
    RolloutHalt,
    md_template_batch,
    run_simulation,
    simulation_settings,
    total_momentum,
)
from hydragnn_tpu.utils import faults
from tests.test_interatomic_potential import _mlip_config

N_ATOMS = 10
CUTOFF = 2.5


@pytest.fixture(scope="module")
def potential():
    """One tiny SchNet MLIP shared by every rollout test (random-init
    weights are a perfectly smooth potential — conservation and replay
    are properties of the ENGINE, not of training quality)."""
    rng = np.random.default_rng(0)
    pos = rng.uniform(0, 3.0, (N_ATOMS, 3)).astype(np.float32)
    x = np.ones((N_ATOMS, 1), np.float32)
    cfg = _mlip_config("node")
    model = create_model(cfg)
    ei = radius_graph(pos, CUTOFF)
    sample = GraphSample(
        x=x,
        pos=pos,
        edge_index=ei,
        energy=0.0,
        forces=np.zeros((N_ATOMS, 3), np.float32),
    )
    params, bs = init_params(model, collate([sample]))
    variables = {"params": params, "batch_stats": bs}
    return model, variables, cfg, sample


def _engine(potential, *, k=8, steps=24, max_edges=256, **sim):
    model, variables, cfg, sample = potential
    block = {
        "steps": steps,
        "dt": 2e-3,
        "superstep_k": k,
        "temperature_k": 0.2,
        "kb": 1.0,
        "seed": 3,
        "neighbor": {"skin": 0.2, "max_edges": max_edges},
    }
    block.update(sim)
    s = simulation_settings({"Simulation": block})
    tmpl = md_template_batch(
        np.asarray(sample.x), np.asarray(sample.pos), s.neighbor.max_edges
    )
    return RolloutEngine(model, variables, cfg, tmpl, s)


def test_nve_conservation_and_momentum(potential):
    """NVE velocity-Verlet over the MLIP: total energy drift stays
    bounded at this dt, and total momentum is conserved to fp
    tolerance (SchNet is translation-invariant, so forces sum to ~0)."""
    eng = _engine(potential, k=8, steps=40, dt=1e-3)
    res = eng.run(eng.init_state())
    assert res.stats["steps"] == 40
    total = res.energies + res.kinetic
    scale = max(abs(float(total[0])), float(res.kinetic[0]), 1e-3)
    drift = float(np.max(np.abs(total - total[0])))
    assert drift < 1e-3 * scale, (drift, scale)
    p = np.asarray(
        total_momentum(
            jnp.asarray(res.state.vel), eng.masses, eng.template.node_mask
        )
    )
    assert np.max(np.abs(p)) < 1e-4, p


def test_macro_bitwise_equals_serial(potential):
    """Same seed + same initial state ⇒ BITWISE-identical trajectory
    across serial (K=1) and K-macro dispatch, with the Langevin
    thermostat AND mid-run neighbor rebuilds in the loop (skin small
    enough that the displacement check fires)."""
    kw = dict(
        steps=32,
        thermostat="langevin",
        friction=0.5,
        neighbor={"skin": 0.02, "max_edges": 256},
    )
    e1 = _engine(potential, k=1, **kw)
    r1 = e1.run(e1.init_state(), record=True)
    e8 = _engine(potential, k=8, **kw)
    r8 = e8.run(e8.init_state(), record=True)
    assert r1.stats["rebuilds"] == r8.stats["rebuilds"] > 0
    assert np.array_equal(r1.trajectory, r8.trajectory)
    assert np.array_equal(r1.velocities, r8.velocities)
    assert np.array_equal(r1.energies, r8.energies)


def test_tail_macro_shorter_than_k(potential):
    """steps not divisible by K: the tail compiles a shorter trip
    count of the same body and stays bitwise on the serial curve."""
    e1 = _engine(potential, k=1, steps=11)
    r1 = e1.run(e1.init_state(), record=True)
    e4 = _engine(potential, k=4, steps=11)
    r4 = e4.run(e4.init_state(), record=True)
    assert r4.stats["steps"] == 11
    assert np.array_equal(r1.trajectory, r4.trajectory)


def test_overflow_containment_and_capacity_growth(potential):
    """An undersized neighbor capacity is a contained event: the
    overflow is detected on-device, the state never sees a truncated
    list, the ladder grows the capacity, and the completed trajectory
    is the same physics the roomy engine produces."""
    clean = _engine(potential, k=8, steps=24)
    res_clean = clean.run(clean.init_state(), record=True)
    tiny = _engine(potential, k=8, steps=24, max_edges=32)
    st = tiny.init_state()
    assert bool(jax.device_get(st.poisoned))  # t=0 overflow flagged
    res = tiny.run(st, record=True)
    assert res.stats["steps"] == 24
    assert res.stats["capacity_growths"] >= 1
    assert res.stats["capacity"] > 32
    assert [e["action"] for e in res.stats["events"]] == ["rebuild"] * res.stats[
        "capacity_growths"
    ]
    assert np.array_equal(res.trajectory, res_clean.trajectory)


def test_overflow_growths_exhausted_halts(potential):
    eng = _engine(
        potential,
        k=8,
        max_edges=32,
        guard={"max_capacity_growths": 0},
    )
    with pytest.raises(RolloutHalt, match="capacity growths exhausted"):
        eng.run(eng.init_state())


def test_injected_nonfinite_force_dt_halve(potential):
    """faults.py ``nan:force@10``: the poisoned step is a no-op, the
    state at the last good step is bit-preserved (trajectory prefix
    bitwise equals the clean run), dt halves, and the rollout still
    delivers every committed step."""
    clean = _engine(potential, k=8, steps=24)
    res_clean = clean.run(clean.init_state(), record=True)
    faults.install("nan:force@10")
    try:
        eng = _engine(potential, k=8, steps=24)
        res = eng.run(eng.init_state(), record=True)
    finally:
        faults.reset()
    assert res.stats["steps"] == 24
    assert res.stats["dt_halvings"] == 1
    assert res.stats["dt"] == pytest.approx(1e-3)
    assert [e["action"] for e in res.stats["events"]] == ["dt_halve"]
    # Steps 0..9 ran at the original dt before the injection landed:
    # bit-identical to the clean run; the post-policy suffix continues
    # at dt/2 from the PRESERVED step-9 state.
    assert np.array_equal(res.trajectory[:10], res_clean.trajectory[:10])
    assert not np.array_equal(
        res.trajectory[10:], res_clean.trajectory[10:]
    )
    assert np.all(np.isfinite(res.trajectory))


def test_injected_nonfinite_halt_policy(potential):
    faults.install("nan:force@5")
    try:
        eng = _engine(
            potential, k=8, guard={"on_nonfinite": "halt"}
        )
        with pytest.raises(RolloutHalt, match="non-finite"):
            eng.run(eng.init_state())
    finally:
        faults.reset()


def test_dt_halvings_exhausted_halts(potential):
    faults.install("nan:force@5")
    try:
        eng = _engine(
            potential, k=8, guard={"max_dt_halvings": 0}
        )
        with pytest.raises(RolloutHalt, match="halvings exhausted"):
            eng.run(eng.init_state())
    finally:
        faults.reset()


def test_checkpoint_interrupt_resume_bitwise(potential, tmp_path):
    """Trajectory checkpoint through the PR-6 CheckpointWriter: a
    rollout interrupted at step 16 and resumed from the container
    continues BITWISE on the uninterrupted trajectory."""
    from hydragnn_tpu.utils.checkpoint import (
        CheckpointWriter,
        load_resume_checkpoint,
    )

    kw = dict(steps=32, thermostat="langevin", friction=0.5)
    full = _engine(potential, k=8, **kw)
    res_full = full.run(full.init_state(), record=True)

    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        w = CheckpointWriter("md_resume_test")
        first = _engine(potential, k=8, **kw)
        res_half = first.run(first.init_state(), 16, record=True)
        w.save(res_half.state, kind="auto", epoch=0, step=16)
        w.close()
        second = _engine(potential, k=8, **kw)
        template_state = second.init_state()
        restored, manifest = load_resume_checkpoint(
            "md_resume_test", template_state
        )
        assert manifest is not None and manifest["step"] == 16
        res_rest = second.run(restored, 16, record=True)
    finally:
        os.chdir(cwd)
    whole = np.concatenate([res_half.trajectory, res_rest.trajectory])
    assert np.array_equal(whole, res_full.trajectory)


def test_resume_adopts_policy_ladder(potential, tmp_path):
    """A resumed rollout must continue at the rungs the interrupted
    run had REACHED, not the config's starting rungs: the checkpoint
    manifest persists the ladder (dt, halvings, capacity, growths),
    and run_simulation adopts it before the restored state is used —
    otherwise the grown edge arrays trace at the wrong static shape
    and the trajectory silently integrates at the wrong dt."""
    model, variables, cfg, sample = potential
    config = {
        "Simulation": {
            "steps": 16,
            "dt": 2e-3,
            "superstep_k": 8,
            "temperature_k": 0.2,
            "kb": 1.0,
            "seed": 3,
            "log_name": "md_ladder_resume",
            "checkpoint": {"enabled": True, "interval_steps": 8},
            # Undersized: t=0 overflow forces a capacity growth.
            "neighbor": {"skin": 0.2, "max_edges": 32},
        }
    }
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        faults.install("nan:force@4")  # forces one dt halving too
        try:
            first = run_simulation(
                config,
                sample=sample,
                model=model,
                cfg=cfg,
                variables=variables,
            )
        finally:
            faults.reset()
        assert first.stats["capacity_growths"] >= 1
        assert first.stats["dt_halvings"] == 1
        grown = first.stats["capacity"]
        halved_dt = first.stats["dt"]

        config["Simulation"]["steps"] = 32
        second = run_simulation(
            config,
            sample=sample,
            model=model,
            cfg=cfg,
            variables=variables,
            resume=True,
        )
    finally:
        os.chdir(cwd)
    # Adopted, not reset: the continuation ran at the reached rungs
    # (a non-adopted engine would trace-fail on the grown [E'] edge
    # arrays, or silently integrate at the config dt).
    assert second.stats["dt"] == pytest.approx(halved_dt)
    assert second.stats["capacity"] == grown
    assert second.stats["steps"] == 16  # the remaining half only
    assert second.stats["events"] == []  # no re-escalation on resume
    assert np.all(np.isfinite(second.energies))


def test_rollout_telemetry_rows(potential, tmp_path):
    """Every macro emits a ``rollout`` row (docs/OBSERVABILITY.md);
    the rows carry the documented fields and graftboard aggregates
    them into the simulation section."""
    from hydragnn_tpu.simulate.engine import macro_plan
    from hydragnn_tpu.utils import telemetry

    stream_path = str(tmp_path / "telemetry.jsonl")
    stream = telemetry.configure(
        {"Telemetry": {"enabled": True, "stream_path": stream_path}},
        "md_rows",
    )
    try:
        eng = _engine(potential, k=8, steps=24)
        eng.run(eng.init_state())
    finally:
        telemetry.close_run(stream)
    rows = [
        json.loads(line) for line in open(stream_path) if line.strip()
    ]
    rollout = [r for r in rows if r.get("t") == "rollout"]
    assert len(rollout) == 3  # 24 steps / K=8
    # a clean rollout dispatches exactly the chunking macro_plan names:
    # full K macros and one shorter tail, 16x fewer dispatches at K=16
    assert macro_plan(24, 8) == [r["committed"] for r in rollout]
    assert macro_plan(11, 4) == [4, 4, 3]
    assert len(macro_plan(128, 1)) == 16 * len(macro_plan(128, 16))
    required = {
        "macro",
        "step",
        "k",
        "committed",
        "dt",
        "spec",
        "energy",
        "drift",
        "rebuilds",
        "overflow",
        "nonfinite",
        "dispatch_ms",
        "steps_per_sec",
        "ns_per_day",
    }
    for r in rollout:
        assert required <= set(r), sorted(required - set(r))
    assert rollout[-1]["step"] == 24
    assert all(r["overflow"] == 0 and not r["nonfinite"] for r in rollout)
    assert all(
        r["dispatch_ms"] > 0.0 and np.isfinite(r["energy"]) for r in rollout
    )

    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    try:
        import graftboard

        rep = graftboard.build_report(stream_path)
    finally:
        sys.path.pop(0)
    rs = rep["rollout_summary"]
    assert rs["macros"] == 3
    assert rs["steps"] == 24
    assert rs["halts"] == 0 and rs["overflow_events"] == 0


def test_run_simulation_api(potential):
    """The public entry: config-driven rollout from a GraphSample over
    supplied variables."""
    model, variables, cfg, sample = potential
    config = {
        "Simulation": {
            "steps": 8,
            "dt": 1e-3,
            "superstep_k": 4,
            "temperature_k": 0.1,
            "kb": 1.0,
            "seed": 1,
            "record_trajectory": True,
            "neighbor": {"skin": 0.3, "max_edges": 256},
        }
    }
    res = run_simulation(
        config, sample=sample, model=model, cfg=cfg, variables=variables
    )
    assert res.stats["steps"] == 8
    assert res.trajectory.shape[0] == 8
    assert np.all(np.isfinite(res.energies))


def test_simulation_settings_validation():
    with pytest.raises(ValueError, match="thermostat"):
        simulation_settings({"Simulation": {"thermostat": "nose"}})
    with pytest.raises(ValueError, match="rebuild_policy"):
        simulation_settings(
            {"Simulation": {"neighbor": {"rebuild_policy": "sometimes"}}}
        )
    with pytest.raises(ValueError, match="on_nonfinite"):
        simulation_settings(
            {"Simulation": {"guard": {"on_nonfinite": "retry"}}}
        )
    with pytest.raises(ValueError, match="must be positive"):
        simulation_settings({"Simulation": {"steps": 0}})
    with pytest.raises(ValueError, match="capacity_growth"):
        simulation_settings(
            {"Simulation": {"guard": {"capacity_growth": 1.0}}}
        )


def test_update_config_rejects_unknown_simulation_keys():
    from hydragnn_tpu.config import update_config

    cfg = {"Simulation": {"steps": 4, "dtt": 1e-3}}
    with pytest.raises(ValueError, match="Simulation: unknown keys"):
        update_config(cfg)
    cfg = {"Simulation": {"neighbor": {"max_edge": 64}}}
    with pytest.raises(ValueError, match="Simulation.neighbor"):
        update_config(cfg)
    cfg = {"Simulation": {"guard": {"on_nonfinit": "halt"}}}
    with pytest.raises(ValueError, match="Simulation.guard"):
        update_config(cfg)
    # A well-formed block passes.
    update_config(
        {
            "Simulation": {
                "steps": 4,
                "dt": 1e-3,
                "neighbor": {"skin": 0.2, "max_edges": 64},
                "guard": {"on_nonfinite": "halt"},
                "checkpoint": {"enabled": True, "interval_steps": 8},
            }
        }
    )
