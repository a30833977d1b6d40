"""Runtime helpers (walltime stop, memory stats), prefetch loader,
stratified subsampling, and conv-type node heads e2e.
"""

import os
import time

import numpy as np
import pytest

import tests._cpu  # noqa: F401


def test_walltime_deadline_env(monkeypatch):
    from hydragnn_tpu.utils.runtime import check_remaining, job_end_time

    monkeypatch.delenv("HYDRAGNN_WALLCLOCK_DEADLINE", raising=False)
    monkeypatch.delenv("SLURM_JOB_END_TIME", raising=False)
    monkeypatch.delenv("SLURM_JOB_ID", raising=False)
    assert job_end_time() is None
    assert check_remaining() is True  # no scheduler info -> keep going

    monkeypatch.setenv(
        "HYDRAGNN_WALLCLOCK_DEADLINE", str(time.time() + 10_000)
    )
    assert check_remaining(300) is True
    monkeypatch.setenv(
        "HYDRAGNN_WALLCLOCK_DEADLINE", str(time.time() + 100)
    )
    assert check_remaining(300) is False


def test_walltime_stops_training(monkeypatch, tmp_path):
    """The epoch loop must stop early and still run the checkpoint
    callback when the deadline is near."""
    import hydragnn_tpu
    from hydragnn_tpu.data.synthetic import deterministic_graph_data
    from hydragnn_tpu.config import load_config

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv(
        "HYDRAGNN_WALLCLOCK_DEADLINE", str(time.time() + 60)
    )
    data = str(tmp_path / "ds")
    deterministic_graph_data(data, number_configurations=30, seed=3)
    here = os.path.dirname(os.path.abspath(__file__))
    config = load_config(os.path.join(here, "inputs", "ci.json"))
    config["Dataset"]["path"] = {"total": data}
    config["NeuralNetwork"]["Training"]["num_epoch"] = 50
    config["NeuralNetwork"]["Training"]["walltime_min_seconds_left"] = 300
    state, model, cfg, hist, full = hydragnn_tpu.run_training(config)
    assert len(hist.train_loss) < 50  # stopped on walltime, not epochs


def test_memory_stats_shape():
    from hydragnn_tpu.utils.runtime import memory_stats, print_peak_memory

    s = memory_stats()  # CPU backend: usually {}
    assert isinstance(s, dict)
    print_peak_memory(lambda *_: None)


def test_memory_stats_hardened_against_raising_and_partial(monkeypatch):
    """ISSUE 8 regression: older libtpu / PJRT plugins can RAISE from
    ``Device.memory_stats()`` or report only a subset of the allocator
    keys — the helper must degrade to partial/empty dicts, never
    propagate (telemetry ``memory`` rows call it inside the run)."""
    import jax

    from hydragnn_tpu.utils import runtime

    class _Raises:
        def __repr__(self):
            return "dev:raises"

        def memory_stats(self):
            raise RuntimeError("allocator stats unavailable")

    class _Partial:
        def __repr__(self):
            return "dev:partial"

        def memory_stats(self):
            return {"bytes_in_use": 123}  # no peak, no limit

    class _NoneStats:
        def __repr__(self):
            return "dev:none"

        def memory_stats(self):
            return None

    monkeypatch.setattr(
        jax, "devices", lambda: [_Raises(), _Partial(), _NoneStats()]
    )
    s = runtime.memory_stats()
    assert s == {"dev:partial": {"bytes_in_use": 123}}
    # and a devices() that itself raises -> {}
    def _boom():
        raise RuntimeError("backend gone")

    monkeypatch.setattr(jax, "devices", _boom)
    assert runtime.memory_stats() == {}


def test_host_memory_reports_rss():
    from hydragnn_tpu.utils.runtime import host_memory

    hm = host_memory()
    # linux container: both sources exist and are sane (> 1 MiB)
    assert hm.get("host_rss_bytes", 0) > 1 << 20
    assert hm.get("host_peak_rss_bytes", 0) >= hm["host_rss_bytes"] // 2


def test_prefetch_loader_equivalent():
    from hydragnn_tpu.data.graph import GraphSample
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.data.prefetch import PrefetchLoader
    from hydragnn_tpu.ops.neighbors import radius_graph

    r = np.random.default_rng(0)
    samples = []
    for i in range(17):
        k = int(r.integers(4, 8))
        pos = r.uniform(0, 3.0, (k, 3)).astype(np.float32)
        samples.append(
            GraphSample(
                x=np.full((k, 1), float(i), np.float32),
                pos=pos,
                edge_index=radius_graph(pos, 2.5),
                y_graph=np.array([float(i)], np.float32),
            )
        )
    plain = GraphLoader(samples, 4, shuffle=True, seed=1)
    pref = PrefetchLoader(GraphLoader(samples, 4, shuffle=True, seed=1))
    plain.set_epoch(2)
    pref.set_epoch(2)
    a = [np.asarray(b.y_graph) for b in plain]
    b = [np.asarray(b.y_graph) for b in pref]
    assert len(a) == len(b) == len(pref)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_prefetch_loader_propagates_errors():
    from hydragnn_tpu.data.prefetch import PrefetchLoader

    def bad_gen():
        yield 1
        raise RuntimeError("boom")

    class Bad:
        def __iter__(self):
            return bad_gen()

        def __len__(self):
            return 2

    with pytest.raises(RuntimeError, match="boom"):
        list(PrefetchLoader(Bad()))


def test_stratified_sample():
    from hydragnn_tpu.data.graph import GraphSample
    from hydragnn_tpu.data.sampling import stratified_sample

    samples = []
    for comp, n in ((1.0, 100), (2.0, 40), (3.0, 4)):
        for _ in range(n):
            samples.append(
                GraphSample(x=np.full((5, 1), comp, np.float32))
            )
    sub = stratified_sample(samples, 0.25, seed=0)
    comps = np.array([s.x[0, 0] for s in sub])
    assert abs((comps == 1.0).sum() - 25) <= 1
    assert abs((comps == 2.0).sum() - 10) <= 1
    assert (comps == 3.0).sum() >= 1  # rare category survives
    with pytest.raises(ValueError):
        stratified_sample(samples, 0.0)


def test_conv_node_head_e2e():
    import jax

    from hydragnn_tpu.data.graph import GraphSample, collate
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.models.spec import BranchSpec, HeadSpec, ModelConfig
    from hydragnn_tpu.ops.neighbors import radius_graph
    from hydragnn_tpu.train.loop import make_train_step
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.state import create_train_state

    r = np.random.default_rng(0)
    samples = []
    for _ in range(6):
        k = int(r.integers(5, 9))
        pos = r.uniform(0, 3.0, (k, 3)).astype(np.float32)
        x = r.normal(size=(k, 2)).astype(np.float32)
        samples.append(
            GraphSample(
                x=x,
                pos=pos,
                edge_index=radius_graph(pos, 2.5),
                y_node=x[:, :1].copy(),
            )
        )
    batch = collate(samples)
    cfg = ModelConfig(
        mpnn_type="SchNet",
        input_dim=2,
        hidden_dim=8,
        num_conv_layers=2,
        heads=(HeadSpec("n", "node", 1),),
        graph_branches=(BranchSpec(),),
        node_branches=(
            BranchSpec(
                node_head_type="conv",
                dim_headlayers=(8, 8),
                num_headlayers=2,
            ),
        ),
        task_weights=(1.0,),
        radius=2.5,
        num_gaussians=8,
        num_filters=8,
    )
    model = create_model(cfg)
    params, bs = init_params(model, batch)
    tx = select_optimizer(
        {"Optimizer": {"type": "AdamW", "learning_rate": 1e-2}}
    )
    state = create_train_state(params, tx, bs)
    step = make_train_step(model, tx, cfg)
    losses = []
    for _ in range(25):
        state, tot, _ = step(state, batch)
        losses.append(float(tot))
    assert losses[-1] < losses[0] * 0.5


def test_conv_checkpointing_matches_plain():
    """remat must change memory, not math: losses identical."""
    import jax

    from hydragnn_tpu.data.graph import GraphSample, collate
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.models.spec import BranchSpec, HeadSpec, ModelConfig
    from hydragnn_tpu.ops.neighbors import radius_graph
    from hydragnn_tpu.train.loop import make_train_step
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.state import create_train_state

    r = np.random.default_rng(1)
    k = 8
    pos = r.uniform(0, 3.0, (k, 3)).astype(np.float32)
    x = r.normal(size=(k, 1)).astype(np.float32)
    batch = collate(
        [
            GraphSample(
                x=x,
                pos=pos,
                edge_index=radius_graph(pos, 2.5),
                y_graph=np.array([0.3], np.float32),
            )
        ]
    )
    results = []
    for ckpt in (False, True):
        cfg = ModelConfig(
            mpnn_type="SchNet",
            input_dim=1,
            hidden_dim=8,
            num_conv_layers=2,
            heads=(HeadSpec("g", "graph", 1),),
            graph_branches=(BranchSpec(),),
            node_branches=(),
            task_weights=(1.0,),
            radius=2.5,
            num_gaussians=8,
            num_filters=8,
            conv_checkpointing=ckpt,
        )
        model = create_model(cfg)
        params, bs = init_params(model, batch)
        tx = select_optimizer(
            {"Optimizer": {"type": "Adam", "learning_rate": 1e-2}}
        )
        state = create_train_state(params, tx, bs)
        step = make_train_step(model, tx, cfg)
        ls = []
        for _ in range(5):
            state, tot, _ = step(state, batch)
            ls.append(float(tot))
        results.append(ls)
    np.testing.assert_allclose(results[0], results[1], rtol=1e-6)


def test_dump_testdata_env(tmp_path, monkeypatch):
    """HYDRAGNN_TPU_DUMP_TESTDATA writes per-sample test outputs
    (reference HYDRAGNN_DUMP_TESTDATA)."""
    import numpy as np

    from hydragnn_tpu.data.graph import GraphSample
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.models.create import create_model, init_params
    from hydragnn_tpu.models.spec import BranchSpec, HeadSpec, ModelConfig
    from hydragnn_tpu.ops.neighbors import radius_graph
    from hydragnn_tpu.train.loop import test as run_test
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.state import create_train_state

    monkeypatch.setenv("HYDRAGNN_TPU_DUMP_TESTDATA", str(tmp_path / "dump"))
    r = np.random.default_rng(0)
    samples = []
    for _ in range(6):
        k = int(r.integers(4, 8))
        pos = r.uniform(0, 3.0, (k, 3)).astype(np.float32)
        samples.append(
            GraphSample(
                x=r.normal(size=(k, 1)).astype(np.float32),
                pos=pos,
                edge_index=radius_graph(pos, 2.5),
                y_graph=np.array([0.1], np.float32),
            )
        )
    cfg = ModelConfig(
        mpnn_type="SchNet", input_dim=1, hidden_dim=8, num_conv_layers=2,
        heads=(HeadSpec("g", "graph", 1),), graph_branches=(BranchSpec(),),
        node_branches=(), task_weights=(1.0,), radius=2.5,
        num_gaussians=8, num_filters=8,
    )
    model = create_model(cfg)
    loader = GraphLoader(samples, 3)
    params, bs = init_params(model, next(iter(loader)))
    tx = select_optimizer({"Optimizer": {"type": "AdamW"}})
    state = create_train_state(params, tx, bs)
    run_test(model, cfg, state, loader)
    data = np.load(tmp_path / "dump" / "testdata.npz")
    assert data["true_0"].shape == data["pred_0"].shape
    assert data["true_0"].shape[0] == 6


def test_compilation_cache_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself, that
    directory is the cache, and the program sets no other."""
    import jax

    from hydragnn_tpu.utils import runtime as rt

    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    # even where the default would apply, the placed directory wins
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(rt, "_DEFAULT_CACHE_DIR", str(tmp_path / "default"))
    before = jax.config.jax_compilation_cache_dir
    assert rt.maybe_enable_compilation_cache() == placed
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "default").exists()


def test_compilation_cache_off_on_cpu(monkeypatch):
    """Variable unset on an explicit CPU run: the cache stays off."""
    import jax

    from hydragnn_tpu.utils import runtime as rt

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert rt.maybe_enable_compilation_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    assert not rt._COMPILE_CACHE_PATH


def test_compilation_cache_default_dir_on_tpu(monkeypatch, tmp_path):
    """Variable unset on a TPU: the cache is the fixed
    ``<checkout>/.xla_cache`` and is populated through a compile —
    INCLUDING in a process that already compiled something beforehand
    (jax latches the cache module as "initialized, disabled" on the
    first compile; maybe_enable_compilation_cache must reset the latch,
    else this test passes standalone and fails after any earlier
    test)."""
    import jax

    from hydragnn_tpu.utils import runtime as rt

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert rt._DEFAULT_CACHE_DIR == os.path.join(repo, ".xla_cache")

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # Latch the cache module the way a real process does: one compile
    # before the cache dir is configured (order-independence guard).
    jax.jit(lambda x: x - 1.0)(jax.numpy.zeros(())).block_until_ready()

    # the platform is steered here, and the default is pointed at a
    # scratch dir so CPU executables never land in the checkout's cache
    cache_dir = str(tmp_path / "xla_cache")
    monkeypatch.setattr(rt, "_DEFAULT_CACHE_DIR", cache_dir)
    try:
        with monkeypatch.context() as on_tpu:
            on_tpu.setattr(jax, "default_backend", lambda: "tpu")
            assert rt.maybe_enable_compilation_cache() == cache_dir
        assert jax.config.jax_compilation_cache_dir == cache_dir

        @jax.jit
        def f(x):
            return x * 2.0 + 1.0

        f(jax.numpy.ones((8, 8))).block_until_ready()
        assert os.listdir(cache_dir), "cache dir must gain entries"
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 1.0
        )
        jax.config.update("jax_compilation_cache_max_size", -1)
        # Back to pristine: drop the handle on the tmp dir so later
        # tests (and their compiles) see an uninitialized cache module.
        rt.reset_compilation_cache()
