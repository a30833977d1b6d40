"""The vocabulary of scopes and spans (ISSUE 27, utils/tracer.py,
docs/OBSERVABILITY.md "Profiler alignment").

Device scopes are compile-time metadata: they are checked in the LOWERED
text of a tiny SchNet step (nothing compiles, nothing runs), where every
op's location carries its ``op_name`` path. Host spans and regions are
checked against a RegionTimer and a stand-in for the profiler's
``TraceAnnotation``. All cheap: no process, no compile at a cell's size.
"""

import json
import re

import pytest

import tests._cpu  # noqa: F401

import jax
import jax.numpy as jnp

from hydragnn_tpu.utils import telemetry
from hydragnn_tpu.utils import tracer as tr
from tests.test_superstep import _config, _mols


@pytest.fixture(scope="module")
def lowered():
    """{program: lowered text with locations} of the four jitted programs
    of a tiny SchNet (2 layers, width 8), the train ones guarded."""
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.models.create import create_model_config, init_params
    from hydragnn_tpu.train import loop
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.state import create_train_state

    samples = _mols(16, seed=3)
    cfgd = update_config(_config(), samples)
    model, cfg = create_model_config(cfgd)
    batch = next(iter(GraphLoader(samples, 4)))
    params, bs = init_params(model, batch)
    tx = select_optimizer(cfgd["NeuralNetwork"]["Training"])
    state = create_train_state(params, tx, bs)
    stacked = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), batch)
    acc = (jnp.zeros(()), jnp.zeros((1,)), jnp.zeros(()))
    text = lambda fn, *a: fn.lower(*a).as_text(debug_info=True)
    return {
        "train_step": text(
            loop.make_train_step(model, tx, cfg, guard=True, donate=False),
            state, batch,
        ),
        "eval_step": text(loop.make_eval_step(model, cfg), state, batch),
        "train_superstep": text(
            loop.make_superstep_fn(
                model, tx, cfg, train=True, guard=True, donate=False
            ),
            state, acc, stacked,
        ),
        "eval_superstep": text(
            loop.make_superstep_fn(
                model, tx, cfg, train=False, donate=False
            ),
            state, acc, stacked,
        ),
    }


def _paths(text):
    """Every op_name path of a lowered text. Inside a scan's body they
    are relative to the body (XLA joins the call site on when it
    compiles): ``optimizer/mul`` there, ``jit(train_step)/optimizer/mul``
    at top level."""
    return set(re.findall(r'loc\("([^"]*)"', text))


def _under(paths, scope):
    inside = re.compile(r"(^|[/(])" + re.escape(scope) + r"[/)]")
    return [p for p in paths if inside.search(p)]


@pytest.mark.parametrize(
    "program",
    ["train_step", "eval_step", "train_superstep", "eval_superstep"],
)
def test_jitted_programs_are_named_apart(lowered, program):
    """The trace's XLA Modules line (and the compile cache's key) tells
    train from evaluation and a step from a K-step scan by name."""
    text = lowered[program]
    assert f"module @jit_{program} " in text, text[:200]
    assert any(p.startswith(f"jit({program})/") for p in _paths(text))


@pytest.mark.parametrize(
    "scope",
    [
        "edge_geometry", "edge_aggregate", "segment/sum", "pool", "loss",
        "optimizer", "guard",
    ],
)
def test_train_programs_carry_every_scope_the_model_exercises(
    lowered, scope
):
    for program in ("train_step", "train_superstep"):
        assert _under(_paths(lowered[program]), scope), (
            f"no op of {program} under scope {scope!r}"
        )


def test_edge_aggregate_plain_and_under_transpose(lowered):
    """Forward and backward need no scope of their own: JAX writes
    ``transpose(`` into the same path, and the reader tells them apart.
    The scope holds the sender gather and, nested, the primitive."""
    paths = _under(_paths(lowered["train_step"]), "edge_aggregate")
    fwd = [p for p in paths if "transpose(" not in p]
    bwd = [p for p in paths if "transpose(" in p]
    assert any(p.endswith("/gather") for p in fwd), sorted(fwd)[:5]
    assert any("/edge_aggregate/segment/sum/" in p for p in fwd)
    assert bwd, "no edge_aggregate op on a transpose( path"
    # the scope sits inside the Flax module that holds the layer
    assert any("/conv_1/edge_aggregate/" in p for p in bwd)
    # evaluation has the forward scopes and no backward, no optimizer
    ev = _paths(lowered["eval_step"])
    assert any("/edge_aggregate/" in p for p in ev)
    assert not any("transpose(" in p or "/optimizer/" in p for p in ev)


@pytest.mark.parametrize("promised", [True, False])
def test_sorted_scatter_keeps_the_scope_and_reports_its_dispatch(
    tmp_path, promised
):
    """ISSUE 34: with receiver-sorted batches the layers' scatters lower
    with ``indices_are_sorted`` under the same scope as before,
    ``edge_aggregate/segment/sum``, so the per-layer metrics go on
    reading the same block; the program's ``segment_dispatch`` row says
    how many of its call sites could make the promise."""
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.models.create import create_model_config, init_params
    from hydragnn_tpu.train import loop
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.state import create_train_state

    samples = _mols(16, seed=3)
    cfgd = update_config(_config(), samples)
    model, cfg = create_model_config(cfgd)
    batch = next(iter(GraphLoader(samples, 4, sort_receivers=promised)))
    assert batch.receivers_sorted is promised
    params, bs = init_params(model, batch)
    tx = select_optimizer(cfgd["NeuralNetwork"]["Training"])
    state = create_train_state(params, tx, bs)
    stream = telemetry.TelemetryStream(str(tmp_path / "t.jsonl"))
    telemetry.install(stream)
    try:
        text = loop.make_train_step(model, tx, cfg, donate=False).lower(
            state, batch
        ).as_text(debug_info=True)
    finally:
        telemetry.close_run(stream)
    (row,) = [
        r for r in _rows(str(tmp_path / "t.jsonl"))
        if r.get("phase") == "segment_dispatch"
    ]
    sites = cfg.num_conv_layers  # one reduce a layer
    assert row["t"] == "setup" and row["program"] == "jit_train_step"
    assert (row["sorted_scatter"], row["scatter"]) == (
        (sites, 0) if promised else (0, sites)
    )
    assert (row["triplet_sorted_scatter"], row["triplet_scatter"]) == (0, 0)
    # each layer's forward reduce is the one scatter that carries the
    # promise (the sender gather's transpose is a scatter too, unsorted),
    # and it sits where the parent's did
    sorted_scatters = re.findall(
        r'"stablehlo.scatter"\([^\n]*indices_are_sorted = true', text
    )
    assert len(sorted_scatters) == (sites if promised else 0)
    reduces = [
        p for p in _under(_paths(text), "edge_aggregate")
        if p.endswith("/segment/sum/scatter-add") and "transpose(" not in p
    ]
    assert len(reduces) == sites


def test_scope_names_are_a_vocabulary():
    def f(x):
        with tr.scope("edge_aggregate"):
            with tr.scope("segment/sum"):
                return jnp.sin(x)

    text = jax.jit(f).lower(jnp.ones(3)).as_text(debug_info=True)
    assert "jit(f)/edge_aggregate/segment/sum/sin" in text
    with pytest.raises(ValueError, match="SCOPES"):
        tr.scope("my_layer")


def test_forces_scope_around_the_inner_grad():
    """MLIP training differentiates the energy w.r.t. positions inside
    the loss: that inner grad reads as ``forces`` in a trace."""
    from hydragnn_tpu.train import mlip

    class Model:
        def apply(self, variables, batch, train, mutable):
            e = jnp.sum(batch.pos ** 2, axis=-1, keepdims=True)
            return [e * variables["w"]], {}

    class Batch:
        pos = jnp.ones((4, 3))
        node_mask = jnp.ones(4, bool)
        graph_mask = jnp.ones(2, bool)
        node_graph_idx = jnp.array([0, 0, 1, 1])
        num_graphs = 2

        def replace(self, pos):
            out = Batch()
            out.pos = pos
            return out

    class Head:
        dim, type = 1, "node"

    class Cfg:
        heads = [Head()]

    def f(w):
        return mlip.energy_and_forces(Model(), {"w": w}, Batch(), Cfg())[1]

    text = jax.jit(f).lower(jnp.float32(2.0)).as_text(debug_info=True)
    assert re.search(r'jit\(f\)/forces/[^"]*transpose\(', text), text[-600:]


def test_region_off_is_the_shared_noop_and_touches_no_tracer(monkeypatch):
    monkeypatch.setattr(tr, "_TRACERS", {})
    assert tr.jax_trace_active() is False
    assert tr.region("train/feed_wait") is tr._NULL_CTX
    assert tr.span("feed/collate") is tr._NULL_CTX
    assert tr.step_annotation("train_step", 3) is tr._NULL_CTX
    with tr.region("train/step", annotate=False):
        pass
    assert tr._TRACERS == {}


def test_region_records_under_the_documented_csv_key(monkeypatch):
    """docs/input_pipeline.md reads the feed rows of timing.p0.csv "next
    to the ``train/step`` regions": the dispatch site keeps that key."""
    timer = tr.RegionTimer()
    monkeypatch.setattr(tr, "_TRACERS", {"RegionTimer": timer})
    with tr.region("epoch/train"):
        with tr.region("train/step", annotate=False):
            pass
    assert timer.counts == {"epoch/train": 1, "epoch/train/train/step": 1}
    timer.reset()
    with tr.region("train/step", annotate=False):
        pass
    assert timer.counts == {"train/step": 1}


def test_region_spans_only_while_a_capture_is_live(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(tr, "_TRACERS", {})
    with tr.region("train/feed_wait"):
        pass
    assert seen == []  # no capture: nothing entered
    monkeypatch.setattr(tr, "_JAX_TRACE_ACTIVE", True)
    with tr.region("train/feed_wait"):
        with tr.region("train/step", annotate=False):  # the step
            pass  # annotation's site: no second span
        with tr.span("feed/h2d"):
            pass
    assert seen == [
        ("enter", "train/feed_wait"), ("enter", "feed/h2d"),
        ("exit", "feed/h2d"), ("exit", "train/feed_wait"),
    ]
    # no span may end in _step: the readers find the loop's thread by it
    assert not any(name.endswith("_step") for _, name in seen)


@pytest.mark.parametrize("python_tracer", [False, True])
def test_trace_starts_with_python_tracer_off_unless_asked(
    monkeypatch, tmp_path, python_tracer
):
    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda log_dir, **kw: calls.append((log_dir, kw)),
    )
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    config = {"NeuralNetwork": {"Training": {"Profiling": {
        "enabled": True, "epoch": 0, "trace_dir": str(tmp_path),
        **({"python_tracer": True} if python_tracer else {}),
    }}}}
    prof = tr.Profiler(config)
    prof.on_epoch_start(0)
    try:
        assert tr.jax_trace_active() is True
    finally:
        prof.on_epoch_end(0)
    assert tr.jax_trace_active() is False
    (log_dir, kw), = calls
    options = kw["profiler_options"]
    assert log_dir == str(tmp_path)
    assert options.python_tracer_level == (1 if python_tracer else 0)
    assert options.host_tracer_level == 2


def test_update_config_takes_python_tracer_and_rejects_unknown_keys():
    from hydragnn_tpu.config import update_config

    samples = _mols(8, seed=1)
    cfg = _config()
    cfg["NeuralNetwork"]["Training"]["Profiling"] = {
        "enabled": True, "epoch": 1, "python_tracer": True,
    }
    update_config(cfg, samples)
    cfg = _config()
    cfg["NeuralNetwork"]["Training"]["Profiling"] = {"python_trace": True}
    with pytest.raises(ValueError, match="Profiling"):
        update_config(cfg, samples)


def test_tracer_surface_after_the_removal():
    """The region timer is the one installable tracer, its CSV has the
    six timing columns and no device column, and the vocabulary's
    helpers are public."""
    import inspect

    assert {"scope", "scoped", "span", "region", "SCOPES"} <= set(tr.__all__)
    assert all(hasattr(tr, name) for name in tr.__all__)
    with pytest.raises(KeyError):
        tr.initialize(["NoSuchTracer"])
    assert list(inspect.signature(tr.RegionTimer.save_csv).parameters) == [
        "self", "path",
    ]
    src = inspect.getsource(tr)
    assert src.count("jax.profiler.start_trace(") == 1
    assert len(src.splitlines()) < 627  # shorter than before the PR


def _rows(path):
    return [json.loads(line) for line in open(path)]


def test_setup_rows_are_held_until_the_stream_opens(tmp_path):
    """run_training's first phases end before its stream is configured:
    their rows wait in memory and land when it opens, in order."""
    path = str(tmp_path / "t.jsonl")
    clock = telemetry.SetupClock()
    clock.phase("config")
    clock.phase("loaders")
    clock.phase("stream")
    stream = telemetry.TelemetryStream(path)
    telemetry.install(stream)
    try:
        clock.stream_ready()
        clock.end_phase()
        with telemetry.setup_phase("writers"):
            pass
        telemetry.setup_row("epoch_0", 12.5)
    finally:
        clock.close()
        telemetry.close_run(stream)
    setup = [r for r in _rows(path) if r["t"] == "setup"]
    assert [r["phase"] for r in setup] == [
        "config", "loaders", "stream", "writers", "epoch_0",
    ]
    assert all(r["ms"] >= 0 for r in setup) and setup[-1]["ms"] == 12.5
    # a row type graftboard does not know: it passes over it
    import tools.graftboard as graftboard

    assert graftboard.build_report(path)["skipped_lines"] == 0
    # with no stream, nothing is kept: a closed clock holds no rows
    clock = telemetry.SetupClock()
    clock.phase("config")
    clock.close()
    assert telemetry._SETUP_HELD is None


def test_compile_observer_writes_its_setup_row_once(tmp_path):
    path = str(tmp_path / "t.jsonl")
    stream = telemetry.TelemetryStream(path)
    obs = telemetry.CompileObserver(stream)
    obs.compile_count, obs.compile_ms = 3, 1234.5678
    obs.cache_hits, obs.cache_misses = 2, 1
    obs.set_phase(0)  # still warming up: nothing yet
    obs.set_phase(1)  # the warm-up is over: the row
    obs.compile_ms = 9999.0
    obs.set_phase(2)
    obs.close()
    stream.close()
    rows = [r for r in _rows(path) if r["t"] == "setup"]
    assert rows == [{
        "t": "setup", "phase": "compile", "ms": 1234.568,
        "compile_count": 3, "cache_hits": 2, "cache_misses": 1,
        **{k: v for k, v in rows[0].items() if k not in (
            "t", "phase", "ms", "compile_count", "cache_hits",
            "cache_misses")},
    }]


def test_run_epoch_names_its_host_work(monkeypatch):
    """The loop's sites, as the RegionTimer keys them: the feed wait
    once per delivery and once more for the end, the dispatch, the
    epoch-end fetch. No paired tr.start/tr.stop is left in the loop."""
    import inspect

    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.train import loop

    timer = tr.RegionTimer()
    monkeypatch.setattr(tr, "_TRACERS", {"RegionTimer": timer})
    loader = GraphLoader(_mols(12, seed=5), 4)
    n = len(loader)
    step = lambda state, batch: (jnp.float32(1.0), jnp.ones(1))
    hook_calls = []
    _, loss, _ = loop._run_epoch(
        step, None, loader, train=False,
        step_hook=lambda *a: hook_calls.append(a),
    )
    assert loss == pytest.approx(1.0) and len(hook_calls) == n
    regions = {k: v for k, v in timer.counts.items() if k in (
        "eval/feed_wait", "eval/step", "eval/clock_record",
        "eval/guard_observe", "eval/step_hook", "eval/epoch_fetch",
        "eval/dataload",
    )}
    assert regions == {
        "eval/feed_wait": n + 1, "eval/step": n, "eval/step_hook": n,
        "eval/epoch_fetch": 1,
    }
    src = inspect.getsource(loop)
    assert "tr.start(" not in src and "tr.stop(" not in src


def test_step_clock_refs_survive_a_donating_superstep(tmp_path):
    """An epoch that begins with a single step and goes on with a K-step
    scan: the scan donates the accumulator, and the single step's real-
    graph count, which the step clock still holds as a deferred ref,
    must not be the accumulator's own buffer (found on the v5e by the
    traced chip_smoke-sized run of PR 27: ``Array has been deleted``)."""
    from hydragnn_tpu.data.graph import stack_batches
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.train import loop

    batches = list(GraphLoader(_mols(12, seed=5), 4, fixed_pad=True))
    feed = [batches[0], stack_batches(batches[1:3])]
    step = lambda state, batch: (jnp.float32(2.0), jnp.ones(1))
    superstep = jax.jit(
        lambda state, acc, b: tuple(a + 1.0 for a in acc),
        donate_argnums=(1,),
    )
    stream = telemetry.TelemetryStream(
        str(tmp_path / "t.jsonl"), cost_analysis=False
    )
    telemetry.install(stream)
    try:
        _, loss, _ = loop._run_epoch(
            step, None, feed, train=False, superstep_fn=superstep, n_tasks=1
        )
    finally:
        telemetry.close_run(stream)
    steps = [r for r in _rows(str(tmp_path / "t.jsonl")) if r["t"] == "step"]
    assert [r["k"] for r in steps] == [1, 2]
    assert steps[0]["graphs"] == 4


def test_dimenet_triplet_scopes_forward_and_transpose():
    """DimeNet++'s triplet exchange reads as ``triplet`` in every block,
    forward and under ``transpose(``, with the reduce nested as
    ``triplet/segment/sum``; the angles and the spherical basis read as
    ``triplet_basis``. The benchmark's triplet readers key on them."""
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.models.create import create_model_config, init_params
    from hydragnn_tpu.train import loop
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.state import create_train_state

    samples = _mols(8, seed=4)
    config = _config()
    config["NeuralNetwork"]["Architecture"].update(
        mpnn_type="DimeNet", num_radial=3, num_spherical=2,
        envelope_exponent=5, int_emb_size=4, basis_emb_size=2,
        out_emb_size=8,
    )
    cfgd = update_config(config, samples)
    model, cfg = create_model_config(cfgd)
    batch = next(iter(GraphLoader(samples, 4, with_triplets=True)))
    params, bs = init_params(model, batch)
    tx = select_optimizer(cfgd["NeuralNetwork"]["Training"])
    state = create_train_state(params, tx, bs)
    text = loop.make_train_step(model, tx, cfg, donate=False).lower(
        state, batch
    ).as_text(debug_info=True)
    paths = _paths(text)
    trip = _under(paths, "triplet")
    for i in range(cfg.num_conv_layers):
        block = [p for p in trip if f"/inter_{i}/triplet/" in p]
        assert any("transpose(" not in p for p in block), i
        assert any("transpose(" in p for p in block), i
        assert any("/triplet/segment/sum/" in p for p in block), i
    basis = _under(paths, "triplet_basis")
    assert basis and all("/inter_" not in p for p in basis)
