"""Aux subsystems: tracer, visualizer, postprocess denormalize, HPO
helpers, atomic descriptors, LSMS enthalpy conversion (SURVEY.md §2.7/§5).
"""

import os
import time

import numpy as np

import tests._cpu  # noqa: F401


def test_region_timer():
    from hydragnn_tpu.utils import tracer as tr

    tr.initialize(["RegionTimer"])
    tr.reset()
    tr.start("outer")
    time.sleep(0.01)
    tr.start("inner")
    time.sleep(0.01)
    tr.stop("inner")
    tr.stop("outer")
    timer = tr._TRACERS["RegionTimer"]
    assert timer.counts["outer"] == 1
    assert timer.counts["outer/inner"] == 1
    assert timer.totals["outer"] >= timer.totals["outer/inner"]


def test_profile_decorator_and_csv(tmp_path):
    from hydragnn_tpu.utils import tracer as tr

    tr.initialize(["RegionTimer"])
    tr.reset()

    @tr.profile("fn")
    def f(x):
        return x + 1

    for _ in range(3):
        f(1)
    timer = tr._TRACERS["RegionTimer"]
    assert timer.counts["fn"] == 3
    path = str(tmp_path / "timing.csv")
    timer.save_csv(path)
    content = open(path).read()
    assert "fn,3," in content


def test_output_denormalize():
    from hydragnn_tpu.postprocess import output_denormalize

    trues = [np.array([[0.0], [0.5], [1.0]])]
    preds = [np.array([[0.25], [0.5], [0.75]])]
    t, p = output_denormalize([(10.0, 20.0)], trues, preds)
    np.testing.assert_allclose(t[0].reshape(-1), [10.0, 15.0, 20.0])
    np.testing.assert_allclose(p[0].reshape(-1), [12.5, 15.0, 17.5])


def test_visualizer_writes_files(tmp_path, monkeypatch):
    from hydragnn_tpu.data.graph import GraphSample
    from hydragnn_tpu.postprocess import Visualizer

    monkeypatch.chdir(tmp_path)
    viz = Visualizer("viztest", num_heads=1)
    t = [np.random.default_rng(0).normal(size=(50, 1))]
    p = [t[0] + 0.1]
    viz.create_scatter_plots(t, p, output_names=["energy"])
    viz.plot_history([1.0, 0.5, 0.2], [1.1, 0.6, 0.3], [1.2, 0.7, 0.4])
    ds = [
        [GraphSample(x=np.zeros((n, 1), np.float32)) for n in (3, 4, 5)]
    ]
    viz.num_nodes_plot(ds, ["train"])
    rng = np.random.default_rng(1)
    viz.create_error_histograms(t, p, output_names=["energy"])
    viz.create_plot_global(t, p, output_names=["energy"])
    viz.create_parity_plot_vector(
        rng.normal(size=(40, 3)), rng.normal(size=(40, 3)), name="forces"
    )
    viz.plot_task_history(
        [np.array([1.0, 0.5]), np.array([0.8, 0.4]), np.array([0.6, 0.3])],
        task_names=["energy", "forces"],
    )
    out = tmp_path / "logs" / "viztest"
    assert (out / "scatter_energy.png").exists()
    assert (out / "history.png").exists()
    assert (out / "num_nodes.png").exists()
    assert (out / "error_hist_energy.png").exists()
    assert (out / "global_analysis.png").exists()
    assert (out / "parity_forces.png").exists()
    assert (out / "task_history.png").exists()


def test_hpo_random_search():
    from hydragnn_tpu.utils.hpo import apply_trial, random_search

    config = {"NeuralNetwork": {"Architecture": {"hidden_dim": 8}}}
    c2 = apply_trial(
        config, {"NeuralNetwork.Architecture.hidden_dim": 32}
    )
    assert c2["NeuralNetwork"]["Architecture"]["hidden_dim"] == 32
    assert config["NeuralNetwork"]["Architecture"]["hidden_dim"] == 8

    # objective: parabola over the space — search must find the minimum
    def obj(cfg, params):
        h = params["NeuralNetwork.Architecture.hidden_dim"]
        return (h - 16) ** 2

    best_p, best_v, trials = random_search(
        config,
        {"NeuralNetwork.Architecture.hidden_dim": [4, 8, 16, 32]},
        n_trials=20,
        objective=obj,
    )
    assert best_p["NeuralNetwork.Architecture.hidden_dim"] == 16
    assert best_v == 0


def test_atomic_descriptors():
    from hydragnn_tpu.utils.descriptors import atomicdescriptors

    d = atomicdescriptors(element_types=["C", "H", "O"])
    fc = d.get_atom_features("C")
    fh = d.get_atom_features(1)
    assert fc.shape == fh.shape == (7,)
    assert not np.array_equal(fc, fh)
    assert np.all(fc >= 0) and np.all(fc <= 1)

    d1 = atomicdescriptors(element_types=["C", "H", "O"], one_hot=True)
    assert d1.get_atom_features("C").shape == (10,)  # 3 one-hot + 7


def test_smiles_entrypoint_without_rdkit():
    """Without rdkit the descriptors entry point routes through the
    native parser (utils/smiles.py) instead of raising — SMILES
    ingestion works on this rdkit-less image."""
    from hydragnn_tpu.utils.descriptors import (
        generate_graphdata_from_smilestr,
        get_node_attribute_name,
    )

    names, dims = get_node_attribute_name(["C", "H"])
    assert names[0] == "atomC" and len(names) == 8 and dims == [1] * 8
    s = generate_graphdata_from_smilestr(
        "CO", [0.25], {"C": 0, "O": 1, "H": 2}
    )
    assert s.x.shape == (6, 3 + 6)  # CH3OH: 2 heavy + 4 H
    assert s.edge_index.shape == (2, 10)  # 5 bonds, both directions
    np.testing.assert_allclose(s.y_graph, [0.25])


def test_lsms_gibbs_conversion(tmp_path):
    from hydragnn_tpu.utils.lsms import convert_raw_data_energy_to_gibbs

    # Two pure configs + one mixed 50/50 binary.
    d = tmp_path / "lsms"
    d.mkdir()

    def write(name, rows, energy):
        lines = [f"{energy}"]
        for r in rows:
            lines.append(" ".join(str(v) for v in r))
        (d / name).write_text("\n".join(lines) + "\n")

    # columns: type idx x y z ...
    write("pure0.txt", [[0, 0, 0, 0, 0], [0, 1, 0.5, 0.5, 0.5]], -2.0)
    write("pure1.txt", [[1, 0, 0, 0, 0], [1, 1, 0.5, 0.5, 0.5]], -4.0)
    write("mix.txt", [[0, 0, 0, 0, 0], [1, 1, 0.5, 0.5, 0.5]], -3.5)
    out = convert_raw_data_energy_to_gibbs(str(d), [0.0, 1.0])
    assert os.path.isdir(out)
    # mixed config: linear mixing = 0.5*(-1) + 0.5*(-2) per atom * 2
    # atoms = -3.0; enthalpy = -3.5 - (-3.0) = -0.5 (T=0 -> Gibbs).
    gibbs = float(open(os.path.join(out, "mix.txt")).readline().split()[0])
    np.testing.assert_allclose(gibbs, -0.5, atol=1e-10)
    # pure configs have zero formation enthalpy
    g0 = float(open(os.path.join(out, "pure0.txt")).readline().split()[0])
    np.testing.assert_allclose(g0, 0.0, atol=1e-10)


def test_per_test_limit_fails_the_test_by_name(tmp_path):
    """tests/conftest.py's limit, cut to 1 s in a pytest session of its
    own: a test that sleeps past it fails under its own name with every
    thread's stack on stderr, and the session goes on to the next test
    — it is not the outer timeout that ends it."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    (tmp_path / "conftest.py").write_text(
        "import tests.conftest\n"
        "tests.conftest.TEST_LIMIT_S = 1\n"
        "from tests.conftest import (  # noqa: F401\n"
        "    pytest_configure, pytest_runtest_call, pytest_runtest_setup,\n"
        ")\n"
    )
    (tmp_path / "test_nap.py").write_text(
        "import time\n\n\n"
        "def test_sleeps_past_the_limit():\n    time.sleep(600)\n\n\n"
        "def test_after_it():\n    pass\n"
    )
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", str(tmp_path)],
        capture_output=True, text=True, cwd=tmp_path, timeout=240,
        env=dict(os.environ, PYTHONPATH=repo),
    )
    assert r.returncode == 1, r.stdout + r.stderr
    assert "1 failed, 1 passed" in r.stdout, r.stdout
    assert (
        "test_nap.py::test_sleeps_past_the_limit ran past the 1 s limit"
        in r.stdout
    ), r.stdout
    # faulthandler's dump, taken while the test slept
    assert "Timeout (0:00:01)!" in r.stderr, r.stderr
    assert "in test_sleeps_past_the_limit" in r.stderr, r.stderr
