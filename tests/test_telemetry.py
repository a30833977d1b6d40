"""Run-telemetry subsystem (ISSUE 7, docs/OBSERVABILITY.md): the
bounded non-blocking stream writer (incl. fault posture via
utils/faults.py), the step clock across every feed/scheme combination
(serial, pipeline, superstep, dp), per-epoch rollups bit-equal to the
loop's History, live MFU consistent with utils/flops.py's arithmetic
to 1e-9 relative, the compile/retrace observer, graftboard parsing (incl.
the truncated-tail tolerance), and the RegionTimer.reset regression.

Training runs use a uniform-size dataset so the packed plan is a
single budget spec — epoch 0 warms every executable and the
zero-post-warmup-recompiles assertions are deterministic.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

import tests._cpu  # noqa: F401  (side effect: pin 8-device CPU platform)

import jax
import jax.numpy as jnp

from hydragnn_tpu.data.graph import GraphSample
from hydragnn_tpu.data.loader import split_dataset
from hydragnn_tpu.ops.neighbors import radius_graph
from hydragnn_tpu.utils import faults
from hydragnn_tpu.utils import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import graftboard  # noqa: E402

sys.path.remove(os.path.join(REPO, "tools"))


@pytest.fixture(autouse=True)
def _clean_telemetry_state():
    """No cross-test leakage: detach any active stream/observer and
    disarm faults before AND after every test."""
    telemetry.install(None)
    obs = telemetry.observer()
    if obs is not None:
        obs.close()
    faults.reset()
    yield
    telemetry.install(None)
    obs = telemetry.observer()
    if obs is not None:
        obs.close()
    faults.reset()


def _uniform_samples(n, seed=11, n_nodes=6):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, 3.0, size=(n_nodes, 3))
    x = rng.integers(0, 3, size=(n_nodes, 1)).astype(np.float32)
    ei = radius_graph(pos, 2.5, max_neighbours=16)
    return [
        GraphSample(
            x=x.copy(),
            pos=pos.astype(np.float32),
            edge_index=ei.copy(),
            y_graph=np.array([rng.normal()], dtype=np.float32),
        )
        for _ in range(n)
    ]


def _tiny_config(batch_size=4, num_epoch=2, **parallelism):
    cfg = {
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "SchNet",
                "radius": 2.5,
                "max_neighbours": 16,
                "num_gaussians": 8,
                "num_filters": 8,
                "hidden_dim": 8,
                "num_conv_layers": 2,
                "output_heads": {
                    "graph": {
                        "num_sharedlayers": 1,
                        "dim_sharedlayers": 8,
                        "num_headlayers": 1,
                        "dim_headlayers": [8],
                    }
                },
                "task_weights": [1.0],
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_names": ["energy"],
                "output_index": [0],
                "type": ["graph"],
                "output_dim": [1],
            },
            "Training": {
                "batch_size": batch_size,
                "num_epoch": num_epoch,
                "Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
            },
        }
    }
    if parallelism:
        cfg["NeuralNetwork"]["Training"]["Parallelism"] = parallelism
    return cfg


def _run(tmp_path, config, n_samples=48, seed=0, sync_interval=0):
    from hydragnn_tpu.runner import run_training

    stream_path = str(tmp_path / "telemetry.jsonl")
    config["NeuralNetwork"]["Training"]["Telemetry"] = {
        "enabled": True,
        "stream_path": stream_path,
        "sync_interval_steps": sync_interval,
    }
    samples = _uniform_samples(n_samples)
    tr, va, te = split_dataset(samples, 0.8)
    state, model, cfg, hist, _ = run_training(
        config, datasets=(tr, va, te), seed=seed
    )
    rows = [json.loads(line) for line in open(stream_path)]
    return rows, hist, cfg, stream_path


# ---------------------------------------------------------------------------
# RegionTimer.reset regression (satellite 1)


def test_region_timer_reset_preserves_enabled():
    """reset() used to re-run __init__, silently re-enabling a tracer
    that was explicitly disabled."""
    from hydragnn_tpu.utils.tracer import RegionTimer

    t = RegionTimer()
    t.start("r")
    t.stop("r")
    t.disable()
    t.reset()
    assert t.enabled is False, "reset() re-enabled a disabled tracer"
    t.start("r")
    t.stop("r")
    assert t.totals == {}, "disabled tracer recorded after reset()"
    t.enable()
    t.reset()
    assert t.enabled is True  # and reset keeps an enabled one enabled
    t.start("r")
    t.stop("r")
    assert "r" in t.totals


# ---------------------------------------------------------------------------
# Stream writer + fault posture (satellite 2)


def test_stream_roundtrip_header_first_and_close_accounting(tmp_path):
    p = str(tmp_path / "t.jsonl")
    s = telemetry.TelemetryStream(p, meta={"log_name": "x"})
    for i in range(20):
        assert s.emit({"t": "step", "i": i})
    s.close()
    rows = [json.loads(line) for line in open(p)]
    assert rows[0]["t"] == "header"
    assert rows[0]["schema"] == telemetry.SCHEMA_VERSION
    assert rows[0]["log_name"] == "x"
    assert [r["i"] for r in rows if r["t"] == "step"] == list(range(20))
    close = rows[-1]
    assert close["t"] == "close"
    assert close["dropped"] == 0 and close["write_errors"] == 0
    # closed stream refuses quietly
    assert s.emit({"t": "late"}) is False


def test_stream_overflow_drops_with_counter_never_blocks(tmp_path):
    """A stalled writer (slow_write fault on the stream path) must
    never stall emit(): rows drop with a counter instead."""
    p = str(tmp_path / "slow" / "t.jsonl")
    faults.install("slow_write:slow:5.0:100")
    s = telemetry.TelemetryStream(p, queue_depth=64)
    t0 = time.perf_counter()
    for i in range(500):
        s.emit({"t": "step", "i": i})
    emit_s = time.perf_counter() - t0
    assert emit_s < 1.0, f"emit() stalled the caller: {emit_s:.2f}s"
    assert s.dropped > 0, "queue overflow did not count drops"
    faults.reset()
    s.close()


def test_stream_write_failure_never_crashes_or_stalls(tmp_path):
    """All writes failing: training-side emit stays fast, the stream
    surfaces on write_errors/last_error, close() does not raise."""
    p = str(tmp_path / "fail" / "t.jsonl")
    faults.install("write_fail:fail:9999")
    s = telemetry.TelemetryStream(p, queue_depth=256)
    for i in range(100):
        s.emit({"t": "step", "i": i})
    s.flush(10.0)
    s.close()
    assert s.write_errors > 0
    assert s.last_error is not None
    assert s.lost_rows > 0
    # accounting invariant: every emitted row is written XOR lost,
    # never double-counted (flush()'s drained test depends on it)
    assert s.written + s.lost_rows <= s.emitted
    faults.reset()


def test_stream_recovers_after_transient_write_failure(tmp_path):
    p = str(tmp_path / "flaky" / "t.jsonl")
    s = telemetry.TelemetryStream(p, queue_depth=256)
    s.emit({"t": "a"})
    assert s.flush(10.0)
    faults.install("write_fail:flaky:1")
    s.emit({"t": "b"})
    s.flush(10.0)
    faults.reset()
    s.emit({"t": "c"})
    s.close()
    kinds = [json.loads(line)["t"] for line in open(p)]
    assert "a" in kinds and "c" in kinds  # 'b' was the injected loss
    assert s.write_errors >= 1


def test_graftboard_skips_truncated_tail_line(tmp_path):
    """A SIGKILL mid-write leaves a truncated tail line; graftboard
    must skip-and-count it, never die."""
    p = str(tmp_path / "t.jsonl")
    s = telemetry.TelemetryStream(p)
    s.emit({"t": "epoch", "epoch": 0, "train_loss": 1.5})
    s.close()
    with open(p, "a") as f:
        f.write('{"t":"step","epoch":1,"trunc')  # no newline, cut mid-key
    rep = graftboard.build_report(p)
    assert rep["skipped_lines"] == 1
    assert rep["train_loss_by_epoch"] == [1.5]


# ---------------------------------------------------------------------------
# Config grammar


def test_telemetry_settings_block_and_envs(monkeypatch):
    st = telemetry.telemetry_settings(
        {"Telemetry": {"enabled": True, "sync_interval_steps": 7}}
    )
    assert st.enabled and st.sync_interval_steps == 7
    assert telemetry.telemetry_settings({"Telemetry": True}).enabled
    assert not telemetry.telemetry_settings({}).enabled
    monkeypatch.setenv("HYDRAGNN_TPU_TELEMETRY", "1")
    monkeypatch.setenv("HYDRAGNN_TPU_TELEMETRY_STREAM", "/tmp/x.jsonl")
    monkeypatch.setenv("HYDRAGNN_TPU_TELEMETRY_SYNC", "5")
    st = telemetry.telemetry_settings({})
    assert st.enabled and st.stream_path == "/tmp/x.jsonl"
    assert st.sync_interval_steps == 5
    monkeypatch.setenv("HYDRAGNN_TPU_TELEMETRY", "0")
    assert not telemetry.telemetry_settings(
        {"Telemetry": {"enabled": True}}
    ).enabled  # env wins both ways


def test_update_config_rejects_unknown_telemetry_key():
    from hydragnn_tpu.config import update_config

    cfg = _tiny_config()
    cfg["NeuralNetwork"]["Training"]["Telemetry"] = {
        "enabled": True,
        "sync_interval": 5,  # misspelled: must fail EAGERLY
    }
    with pytest.raises(ValueError, match="Telemetry"):
        update_config(cfg, _uniform_samples(8))


# ---------------------------------------------------------------------------
# The step clock across feeds/schemes + bit-equal rollups + MFU


def _breakdown_keys(rows):
    return {
        (r["region"], r["feed"], r["scheme"])
        for r in rows
        if r["t"] == "step"
    }


def _assert_losses_bit_equal(rows, hist):
    ep = sorted(
        (r for r in rows if r["t"] == "epoch"),
        key=lambda r: r["epoch"],
    )
    assert [r["train_loss"] for r in ep] == hist.train_loss
    assert [r["val_loss"] for r in ep] == hist.val_loss
    assert [r["test_loss"] for r in ep] == hist.test_loss


def _assert_mfu_consistent(rows, cfg):
    """The acceptance contract: per-spec MFU in the stream reproduces
    utils/flops.py's arithmetic (the SAME function over the row's own
    emitted fields) to 1e-9 relative."""
    from hydragnn_tpu.utils.flops import model_flops_per_graph

    mfu_rows = [
        r for r in rows if r["t"] == "spec_rollup" and "mfu" in r
    ]
    assert mfu_rows, "no MFU rows in the stream"
    for r in mfu_rows:
        mf = model_flops_per_graph(cfg, r["mean_nodes"], r["mean_edges"])
        expect = mf * r["graphs"] / (r["wall_ms"] / 1e3) / r["peak_flops"]
        assert abs(r["mfu"] - expect) <= 1e-9 * abs(expect), (
            r["spec"],
            r["mfu"],
            expect,
        )
        assert r["model_flops_per_graph"] == mf


@pytest.mark.parametrize(
    "F,G,L,H,expect",
    [
        # hidden_dim == num_filters: the count from before lin1/lin2
        # were told apart (both F x F), to the last digit
        (
            128.0, 50.0, 6.0, 128.0,
            3.0 * (
                6.0 * (
                    2 * 100 * (50.0 * 128.0 + 128.0 * 128.0)
                    + 2 * 10 * (2 * 128.0 * 128.0)
                    + 2 * 100 * 128.0
                )
                + 2 * 10 * 128.0 * 128.0
                + 6 * 128.0 * 128.0
            ),
        ),
        # schnet_oc20's widths, hidden 1024 / 256 filters, by hand for
        # n=10, e=100. A layer: filter MLP 2*100*(200*256 + 256*256)
        # = 23,347,200; lin1 (H x F) and lin2 (F x H) 2*10*(262,144 +
        # 262,144) = 10,485,760; product and sum 2*100*256 = 51,200;
        # 33,884,160 a layer, 169,420,800 for 5. Embed 2*10*1024^2 =
        # 20,971,520, heads 6*1024^2 = 6,291,456: 196,683,776 forward,
        # times 3.
        (256.0, 200.0, 5.0, 1024.0, 590051328.0),
    ],
)
def test_schnet_flops_tells_lin1_and_lin2_by_hidden_and_filters(
    F, G, L, H, expect
):
    """``lin1`` is hidden x filters and ``lin2`` filters x hidden: the
    count behind telemetry's MFU column where they differ."""
    from hydragnn_tpu.utils.flops import schnet_flops

    assert schnet_flops(10, 100, F, G, L, H) == expect


def test_serial_feed_stream(tmp_path):
    rows, hist, cfg, path = _run(
        tmp_path,
        _tiny_config(
            scheme="single",
            pipeline={"workers": 0},
            packing={"enabled": True},
        ),
        sync_interval=3,
    )
    keys = _breakdown_keys(rows)
    assert ("train", "prefetch", "single") in keys or (
        "train",
        "serial",
        "single",
    ) in keys
    _assert_losses_bit_equal(rows, hist)
    _assert_mfu_consistent(rows, cfg)
    # sampled device fences appeared (sync_interval=3) but ONLY there
    fenced = [
        r
        for r in rows
        if r["t"] == "step" and "device_complete_ms" in r
    ]
    assert fenced, "sync_interval_steps=3 produced no fence samples"
    # per-step rows carry spec + plan-domain real sizes + loss + lr
    st = [r for r in rows if r["t"] == "step" and r["region"] == "train"]
    assert all("spec" in r and "loss" in r and "lr" in r for r in st)
    assert all(
        r["nodes"] <= r["nodes_pad"] and r["graphs_plan"] <= r["graphs_pad"]
        for r in st
        if "nodes" in r
    )
    # zero post-warmup recompiles on the stable packed run
    rep = graftboard.build_report(path)
    assert rep["post_warmup_compiles"] == 0
    assert rep["drops"] == 0


def test_pipeline_feed_stream(tmp_path):
    rows, hist, _, _ = _run(
        tmp_path,
        _tiny_config(
            scheme="single",
            pipeline={"workers": 2, "depth": 2},
            packing={"enabled": True},
        ),
    )
    keys = _breakdown_keys(rows)
    assert any(
        k[0] == "train" and "pipeline" in k[1] for k in keys
    ), keys
    _assert_losses_bit_equal(rows, hist)
    # pipeline counters routed into the same stream
    assert any(r["t"] == "pipeline" for r in rows)


def test_superstep_feed_stream(tmp_path):
    rows, hist, _, _ = _run(
        tmp_path,
        _tiny_config(
            scheme="single",
            pipeline={"workers": 0},
            packing={"enabled": True},
            superstep={"steps": 4},
        ),
    )
    st = [r for r in rows if r["t"] == "step" and r["region"] == "train"]
    macro = [r for r in st if r["k"] > 1]
    assert macro, "superstep run emitted no K>1 dispatch rows"
    assert all(r["k"] == 4 for r in macro)
    assert all("loss_sum" in r for r in macro), (
        "macro rows must carry the cumulative loss_sum ref"
    )
    assert any("superstep" in k[1] for k in _breakdown_keys(rows))
    # K steps per dispatch: plan sizes aggregate k*d entries
    assert all(
        r["graphs_plan"] >= r["k"] for r in macro if "graphs_plan" in r
    )
    _assert_losses_bit_equal(rows, hist)


def test_dp_feed_stream(tmp_path):
    assert len(jax.devices()) >= 8
    rows, hist, cfg, _ = _run(
        tmp_path,
        _tiny_config(
            batch_size=2,
            scheme="dp",
            data=8,
            pipeline={"workers": 0},
            packing={"enabled": True},
        ),
        n_samples=160,
    )
    st = [r for r in rows if r["t"] == "step" and r["region"] == "train"]
    assert st and all(r["lanes"] == 8 for r in st)
    assert all(r["scheme"] == "dp" for r in st)
    assert any("dp" in k[1] for k in _breakdown_keys(rows))
    _assert_losses_bit_equal(rows, hist)
    _assert_mfu_consistent(rows, cfg)


def test_telemetry_off_is_inert(tmp_path):
    """No active stream: epoch_clock returns None and the loop runs
    the pre-telemetry path (no stream file, no context mutation)."""
    from hydragnn_tpu.data.loader import GraphLoader

    telemetry.install(None)
    assert telemetry.epoch_clock(
        GraphLoader(_uniform_samples(8), 4), "train"
    ) is None
    assert telemetry.emit({"t": "x"}) is False


# ---------------------------------------------------------------------------
# Compile observer (satellite 3)


def test_compile_observer_flags_shape_unstable_fn():
    obs = telemetry.install_observer()
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones((3,)))  # warmup phase 0
    n_warm = obs.compile_count
    assert n_warm > 0
    obs.set_phase(1)
    f(jnp.ones((3,)))  # cache hit: no compile
    assert obs.compile_count == n_warm
    assert obs.post_warmup == []
    f(jnp.ones((9,)))  # NEW shape after warmup = retrace leak
    assert obs.compile_count > n_warm
    assert obs.post_warmup, "shape-unstable fn not flagged"
    assert all(ev["epoch"] == 1 for ev in obs.post_warmup)
    obs.close()


def test_compile_observer_stable_run_is_clean():
    obs = telemetry.install_observer()
    g = jax.jit(lambda x: x - 1)
    g(jnp.ones((4,)))
    obs.set_phase(1)
    for _ in range(3):
        g(jnp.ones((4,)))  # stable spec: replayed executable
    assert obs.post_warmup == []
    obs.close()


def test_compile_observer_idempotent_install_and_clean_close():
    obs1 = telemetry.install_observer()
    obs1.install()  # double install: no double counting
    h = jax.jit(lambda x: x + 3)
    h(jnp.ones((5,)))
    count1 = obs1.compile_count
    assert count1 >= 1
    obs1.close()
    # a closed observer receives nothing (no cross-test leakage)
    h(jnp.ones((6,)))
    assert obs1.compile_count == count1
    # and a NEW observer takes over cleanly
    obs2 = telemetry.install_observer()
    h(jnp.ones((7,)))
    assert obs2.compile_count >= 1
    assert obs1.compile_count == count1
    obs2.close()
    assert telemetry.observer() is None


def test_compile_observer_emits_rows_and_summary(tmp_path):
    p = str(tmp_path / "t.jsonl")
    s = telemetry.TelemetryStream(p)
    obs = telemetry.CompileObserver(s, warmup_phase=1).install()
    f = jax.jit(lambda x: x * 5)
    f(jnp.ones((3,)))
    obs.set_phase(2)
    f(jnp.ones((4,)))
    obs.close()
    s.close()
    rows = [json.loads(line) for line in open(p)]
    compiles = [r for r in rows if r["t"] == "compile"]
    assert compiles
    assert any(r["retrace_leak"] and r["epoch"] == 2 for r in compiles)
    summary = [r for r in rows if r["t"] == "compile_summary"]
    assert summary and summary[0]["post_warmup_compiles"] >= 1


# ---------------------------------------------------------------------------
# graftboard report + diff


def test_graftboard_report_and_diff_cli(tmp_path, capsys):
    cfg_a = _tiny_config(
        scheme="single",
        pipeline={"workers": 0},
        packing={"enabled": True},
    )
    rows_a, hist_a, _, path_a = _run(tmp_path / "a", cfg_a)
    cfg_b = _tiny_config(
        scheme="single",
        pipeline={"workers": 0},
        packing={"enabled": True},
    )
    rows_b, hist_b, _, path_b = _run(tmp_path / "b", cfg_b)
    assert graftboard.main(["report", path_a]) == 0
    out = capsys.readouterr().out
    assert "step-time breakdown" in out and "compiles:" in out
    # identical config+seed => identical loss curves in the diff
    assert graftboard.main(["diff", path_a, path_b, "--json"]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["loss_identical"] is True
    assert d["train_loss_a"] == hist_a.train_loss
    assert d["post_warmup_compiles"]["a"] == 0
    # directory resolution: logs/<name>/telemetry.jsonl layout
    run_dir = tmp_path / "dir"
    run_dir.mkdir()
    os.rename(path_a, run_dir / "telemetry.jsonl")
    assert graftboard.build_report(str(run_dir))["rows"] > 0
    assert graftboard.main(["report", str(tmp_path / "missing")]) == 2


def test_checkpoint_rows_routed_into_stream(tmp_path):
    cfg = _tiny_config(
        scheme="single",
        pipeline={"workers": 0},
        packing={"enabled": True},
    )
    cfg["NeuralNetwork"]["Training"]["Checkpoint"] = {
        "enabled": True,
        "async": True,
        "interval_steps": 3,
    }
    os.chdir(tmp_path)  # checkpoints land under ./logs
    try:
        rows, _, _, _ = _run(tmp_path, cfg)
    finally:
        os.chdir(REPO)
    ck = [r for r in rows if r["t"] == "checkpoint"]
    saves = [r for r in ck if r["event"] == "save"]
    writes = [r for r in ck if r["event"] == "write"]
    assert saves and writes
    assert all("snapshot_block_ms" in r for r in saves)
    assert all("serialize_write_ms" in r for r in writes)
    assert not any(r.get("failed") for r in writes)


# ---------------------------------------------------------------------------
# Roofline attribution (ISSUE 8): header self-description, executable
# cost/memory rows, hw rollups, memory rows, profiler alignment,
# graftboard roofline/diff


def test_header_self_description(tmp_path):
    """graftboard roofline/diff resolve their peak basis from the
    header instead of guessing: device/jax/host facts + both peaks."""
    jax.devices()  # ensure the backend is live (order-independence)
    p = str(tmp_path / "t.jsonl")
    s = telemetry.TelemetryStream(p)
    s.close()
    hdr = json.loads(open(p).readline())
    assert hdr["t"] == "header"
    assert hdr["device_kind"] == "cpu" and hdr["platform"] == "cpu"
    assert hdr["jax_version"] == jax.__version__
    assert hdr["hostname"] and hdr["device_count"] >= 1
    assert hdr["process_count"] == 1
    # CPU host: both peaks fall back to the ROOFLINE anchor, flagged
    assert hdr["peak_flops"] > 0 and hdr["peak_basis"] == "roofline_anchor"
    assert hdr["peak_hbm_bytes_per_sec"] > 0
    assert hdr["peak_hbm_basis"] == "roofline_anchor"


def test_compiled_cost_stats_matches_raw_cost_analysis():
    """The parse behind the ``executable`` rows: flops/bytes equal
    the raw Compiled.cost_analysis values."""
    from hydragnn_tpu.utils.flops import (
        compiled_cost_stats,
        compiled_memory_stats,
    )

    f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
    compiled = f.lower(jnp.ones((16, 16))).compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    cost = compiled_cost_stats(compiled)
    assert cost["flops"] == float(ca["flops"]) > 0
    assert cost["bytes_accessed"] == float(ca["bytes accessed"]) > 0
    mem = compiled_memory_stats(compiled)
    ma = compiled.memory_analysis()
    assert mem["argument_bytes"] == int(ma.argument_size_in_bytes)
    assert mem["temp_bytes"] == int(ma.temp_size_in_bytes)
    # unavailable backends degrade to {} (never fabricate)
    class _NoCost:
        def cost_analysis(self):
            raise NotImplementedError

        def memory_analysis(self):
            return None

    assert compiled_cost_stats(_NoCost()) == {}
    assert compiled_memory_stats(_NoCost()) == {}


def test_resolve_peak_bandwidth_anchor_and_device():
    from hydragnn_tpu.utils.flops import (
        PEAK_HBM_BYTES_PER_SEC,
        resolve_peak_bandwidth,
    )

    bw, basis = resolve_peak_bandwidth("TPU v4")
    assert bw == PEAK_HBM_BYTES_PER_SEC["TPU v4"] and basis == "device"
    # explicit CPU run -> the anchor chip's row of the table
    bw, basis = resolve_peak_bandwidth("cpu")
    assert basis == "roofline_anchor" and bw == 819.0e9


@pytest.mark.parametrize("kind", ["TPU v9 mega", "NVIDIA Z100"])
def test_resolve_peaks_raise_for_a_chip_not_in_the_table(kind):
    """A real chip the tables do not know is an error, never the
    anchor chip's peaks under another device's name."""
    from hydragnn_tpu.utils.flops import (
        resolve_peak_bandwidth,
        resolve_peak_flops,
    )

    with pytest.raises(ValueError, match="PEAK_FLOPS"):
        resolve_peak_flops(kind)
    with pytest.raises(ValueError, match="PEAK_HBM_BYTES_PER_SEC"):
        resolve_peak_bandwidth(kind)


def _exec_rows(rows):
    return [r for r in rows if r["t"] == "executable"]


def test_executable_rows_hw_rollups_and_roofline_cli(tmp_path, capsys):
    """One end-to-end packed run: every compiled spec gets ONE
    executable row with counted flops/bytes/memory footprint; rollups
    gain hw-MFU + intensity reproducible from their own emitted fields
    to 1e-9; graftboard roofline renders a bound-ness verdict per spec
    (anchor what-if flagged), and diff-against-self reports zero
    intensity/ceiling deltas."""
    rows, hist, cfg, path = _run(
        tmp_path,
        _tiny_config(
            scheme="single",
            pipeline={"workers": 0},
            packing={"enabled": True},
        ),
    )
    ex = _exec_rows(rows)
    assert ex, "no executable rows in the stream"
    # counted flops/bytes > 0 and the memory footprint fields landed
    for r in ex:
        assert r["flops"] > 0 and r["bytes_accessed"] > 0, r
        assert r["temp_bytes"] >= 0 and r["argument_bytes"] > 0, r
        assert "capture_ms" in r and not r.get("post_warmup"), r
    # exactly ONE capture per (region, spec, k, lanes) across epochs
    keys = [(r["region"], r["spec"], r["k"], r["lanes"]) for r in ex]
    assert len(keys) == len(set(keys))
    # every rollup spec is attributed (uniform dataset: stable specs)
    rollups = [r for r in rows if r["t"] == "spec_rollup"]
    assert rollups
    exec_specs = {(r["region"], r["spec"]) for r in ex}
    for r in rollups:
        assert (r["region"], r["spec"]) in exec_specs
        assert r["hw_dispatches"] > 0 and "hw_missing_dispatches" not in r
        # reader-reproducibility contract (1e-9 relative), hw side
        hw_mfu = r["hw_flops"] / (r["wall_ms"] / 1e3) / r["peak_flops"]
        assert abs(r["hw_mfu"] - hw_mfu) <= 1e-9 * abs(hw_mfu)
        intensity = r["hw_flops"] / r["hw_bytes_accessed"]
        assert abs(r["intensity"] - intensity) <= 1e-9 * abs(intensity)
        assert r["peak_hbm_bytes_per_sec"] > 0
        if "model_flops_per_graph" in r:
            ratio = r["hw_flops"] / (
                r["model_flops_per_graph"] * r["graphs"]
            )
            assert abs(r["hw_over_model_flops"] - ratio) <= 1e-9 * ratio
    # close row accounts for the captures
    close = [r for r in rows if r["t"] == "close"][-1]
    assert close["executables"] == len(ex)
    assert close["exec_capture_failures"] == 0
    # graftboard roofline: verdict per spec + anchor what-if note
    assert graftboard.main(["roofline", path]) == 0
    out = capsys.readouterr().out
    assert "memory-bound" in out or "compute-bound" in out
    assert "WHAT-IF" in out
    rl = graftboard.build_roofline(graftboard.build_report(path))
    assert rl["what_if"] is True
    assert rl["specs"] and all(
        e["verdict"] in ("memory-bound", "compute-bound")
        for e in rl["specs"]
    )
    for e in rl["specs"]:
        assert e["roofline_ceiling_flops_per_sec"] == min(
            e["peak_flops"],
            e["intensity"] * e["peak_hbm_bytes_per_sec"],
        )
        assert 0 < e["ceiling_frac"] < 1
    # diff against self: zero deltas, stable verdicts
    assert graftboard.main(["diff", path, path, "--json"]) == 0
    d = json.loads(capsys.readouterr().out)
    roof = d["roofline_delta_by_spec"]
    assert roof
    for spec, v in roof.items():
        assert v["intensity"]["delta"] == 0.0
        assert v["ceiling_frac"]["delta"] == 0.0
        assert v["verdict_a"] == v["verdict_b"]


def test_cost_analysis_off_emits_no_executable_rows(tmp_path):
    cfg = _tiny_config(
        scheme="single",
        pipeline={"workers": 0},
        packing={"enabled": True},
    )
    from hydragnn_tpu.runner import run_training
    from hydragnn_tpu.data.loader import split_dataset as _split

    stream_path = str(tmp_path / "telemetry.jsonl")
    cfg["NeuralNetwork"]["Training"]["Telemetry"] = {
        "enabled": True,
        "stream_path": stream_path,
        "cost_analysis": False,
    }
    tr, va, te = _split(_uniform_samples(48), 0.8)
    run_training(cfg, datasets=(tr, va, te), seed=0)
    rows = [json.loads(line) for line in open(stream_path)]
    assert not _exec_rows(rows)
    rollups = [r for r in rows if r["t"] == "spec_rollup"]
    assert rollups and all("hw_mfu" not in r for r in rollups)
    assert all("hw_missing_dispatches" not in r for r in rollups)
    # roofline degrades honestly: rows render, verdict is None
    rl = graftboard.build_roofline(
        graftboard.build_report(stream_path)
    )
    assert rl["specs"] and all(e["verdict"] is None for e in rl["specs"])


def test_capture_failure_degrades_and_never_retries(tmp_path):
    """A step fn without a working AOT path: ONE capture_error row per
    key, the failure counter moves, rollups carry the miss count and
    OMIT hw-MFU/intensity — and record() never raises."""
    from hydragnn_tpu.data.loader import GraphLoader

    p = str(tmp_path / "t.jsonl")
    s = telemetry.TelemetryStream(p)
    batch = next(iter(GraphLoader(_uniform_samples(8), 4)))

    class _Unlowerable:
        def lower(self, *a):
            raise RuntimeError("no AOT for you")

    clock = telemetry.StepClock(s, region="train", epoch=0)
    for step in (1, 2, 3):
        t = time.perf_counter()
        clock.record(
            step=step,
            k=1,
            batch=batch,
            is_macro=False,
            t_fetch_start=t,
            t_fetch_end=t,
            t_dispatch_start=t,
            t_dispatch_end=t + 1e-4,
            capture_fn=_Unlowerable(),
            capture_args=(None, batch),
        )
    clock.finish()
    s.close()
    rows = [json.loads(line) for line in open(p)]
    errs = [r for r in _exec_rows(rows) if "capture_error" in r]
    assert len(errs) == 1, "failed capture must not retry per step"
    assert s.exec_capture_failures == 1
    roll = [r for r in rows if r["t"] == "spec_rollup"]
    assert roll and roll[0]["hw_missing_dispatches"] == 3
    assert "hw_mfu" not in roll[0] and "intensity" not in roll[0]
    # graftboard: no fabricated verdict for the unattributed spec
    rl = graftboard.build_roofline(graftboard.build_report(p))
    assert all(e["verdict"] is None for e in rl["specs"])


def test_memory_rows_epoch_boundaries_and_compiles(tmp_path):
    """CPU run: memory rows at run start + every epoch boundary +
    after compiles, carrying host RSS (device allocator fields absent
    on CPU — partial, never fabricated)."""
    rows, _, _, _ = _run(
        tmp_path,
        _tiny_config(
            scheme="single",
            pipeline={"workers": 0},
            packing={"enabled": True},
        ),
    )
    mem = [r for r in rows if r["t"] == "memory"]
    assert {r.get("epoch") for r in mem if r["tag"] == "epoch"} == {0, 1}
    assert any(r["tag"] == "run_start" for r in mem)
    assert any(r["tag"] == "compile" for r in mem)
    for r in mem:
        assert r["host_rss_bytes"] > 1 << 20
        assert "bytes_in_use" not in r  # CPU: no allocator stats
    # off-path: emit_memory is inert
    telemetry.install(None)
    assert telemetry.emit_memory("x") is False


def test_profiling_window_and_step_annotations(tmp_path):
    """Training.Profiling {epoch, steps}: the capture starts at the
    target epoch, stops after the step budget, both ends land in the
    stream, and the trace dir materializes."""
    cfg = _tiny_config(
        scheme="single",
        pipeline={"workers": 0},
        packing={"enabled": True},
    )
    trace_dir = str(tmp_path / "trace")
    cfg["NeuralNetwork"]["Training"]["Profiling"] = {
        "enabled": True,
        "epoch": 1,
        "steps": 2,
        "trace_dir": trace_dir,
    }
    rows, _, _, path = _run(tmp_path, cfg)
    prof = [r for r in rows if r["t"] == "profile"]
    assert [r["event"] for r in prof] == ["start", "stop"]
    assert prof[0]["epoch"] == 1 and prof[0]["steps"] == 2
    assert prof[0]["trace_dir"] == trace_dir
    assert prof[1]["reason"] == "step_budget"
    assert os.path.isdir(trace_dir)
    # profiling a steady epoch must not retrace (annotation is outside
    # the jit key) — the stable packed run stays recompile-free
    rep = graftboard.build_report(path)
    assert rep["post_warmup_compiles"] == 0
    from hydragnn_tpu.utils import tracer as tr

    assert tr.jax_trace_active() is False  # window closed cleanly


def test_update_config_rejects_unknown_profiling_key():
    from hydragnn_tpu.config import update_config

    cfg = _tiny_config()
    cfg["NeuralNetwork"]["Training"]["Profiling"] = {
        "enabled": True,
        "target_epoch": 1,  # legacy name: must fail EAGERLY
    }
    with pytest.raises(ValueError, match="Profiling"):
        update_config(cfg, _uniform_samples(8))


def test_header_omits_device_fields_when_backend_uninitialized(
    tmp_path, monkeypatch
):
    """Constructing a stream must NEVER initialize a jax backend:
    with no backend live, the header skips the device fields (peaks
    still resolve from the ROOFLINE anchor) instead of calling
    jax.devices()."""
    from jax._src import xla_bridge

    monkeypatch.setattr(xla_bridge, "_backends", {})
    p = str(tmp_path / "t.jsonl")
    s = telemetry.TelemetryStream(p)
    s.close()
    hdr = json.loads(open(p).readline())
    assert "device_kind" not in hdr and "device_count" not in hdr
    assert hdr["hostname"]
    assert hdr["peak_basis"] == "roofline_anchor"  # anchor-only peaks


def test_capture_compile_not_counted_by_observer(tmp_path):
    """The capture's OWN AOT compile must not reach the compile
    observer: one real post-warmup retrace reads as ONE leak (not
    two), and the capture's cost lands on the row's capture_ms."""
    from hydragnn_tpu.data.loader import GraphLoader

    p = str(tmp_path / "t.jsonl")
    s = telemetry.TelemetryStream(p)
    obs = telemetry.CompileObserver(s, warmup_phase=1).install()
    batch = next(iter(GraphLoader(_uniform_samples(8), 4)))
    f = jax.jit(lambda st, b: (st, jnp.sum(b.x), jnp.zeros((1,))))
    f(0.0, batch)  # warmup compile at phase 0
    obs.set_phase(2)
    state, loss, _ = f(1.0, batch)  # cache hit: no compile
    n_before = obs.compile_count
    assert obs.post_warmup == []
    clock = telemetry.StepClock(s, region="train", epoch=2)
    t = time.perf_counter()
    clock.record(
        step=1,
        k=1,
        batch=batch,
        is_macro=False,
        t_fetch_start=t,
        t_fetch_end=t,
        t_dispatch_start=t,
        t_dispatch_end=t + 1e-4,
        loss_ref=loss,
        capture_fn=f,
        capture_args=(1.0, batch),
    )
    clock.finish()
    obs.close()
    s.close()
    # the AOT capture compiled (flops landed) but the observer saw
    # nothing: no new compiles, no fabricated retrace leak
    rows = [json.loads(line) for line in open(p)]
    ex = [r for r in rows if r["t"] == "executable"]
    assert ex and ex[0]["flops"] > 0 and ex[0]["post_warmup"] is True
    assert obs.compile_count == n_before
    assert obs.post_warmup == []
