"""Example-driver smoke tests (reference tests/test_examples.py runs the
actual examples/ scripts): each driver must run end to end with tiny
settings.
"""

import os
import subprocess
import sys

import pytest

import tests._cpu  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=420):
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        }
    )
    return subprocess.run(
        [sys.executable, os.path.join(REPO, script), *args],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=REPO,
    )


def test_lennard_jones_example():
    r = _run(
        "examples/LennardJones/LennardJones.py",
        "--configs",
        "40",
        "--epochs",
        "4",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "force MAE" in r.stdout


def test_qm9_example_synthetic():
    r = _run(
        "examples/qm9/qm9.py",
        "--synthetic",
        "--mols",
        "60",
        "--epochs",
        "3",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Test MAE" in r.stdout


def test_multibranch_example():
    r = _run(
        "examples/multibranch/train.py",
        "--epochs",
        "2",
        "--sizes",
        "60",
        "30",
        "--hidden_dim",
        "8",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "devices per branch" in r.stdout
    assert "epoch   1" in r.stdout


def test_md17_example():
    r = _run(
        "examples/md17/md17.py", "--frames", "60", "--epochs", "3"
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "test force loss" in r.stdout


def test_zinc_example_gps():
    r = _run(
        "examples/zinc/zinc.py", "--mols", "80", "--epochs", "3"
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final:" in r.stdout


def test_oc20_example():
    r = _run(
        "examples/open_catalyst_2020/oc20.py",
        "--systems", "48", "--epochs", "2",
        timeout=540,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "test force loss" in r.stdout


def test_lsms_example_raw_ingest():
    """Drives the full Dataset.path raw-LSMS ingestion inside
    run_training (format detect -> read -> normalize -> split)."""
    r = _run("examples/lsms/lsms.py", "--configs", "60", "--epochs", "2")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final:" in r.stdout


def test_ising_example_multihead():
    r = _run(
        "examples/ising_model/ising.py", "--configs", "60", "--epochs", "2"
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "field" in r.stdout


def test_qm9_hpo_example():
    r = _run(
        "examples/qm9_hpo/qm9_hpo.py",
        "--trials", "2", "--epochs", "1", "--mols", "40",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "best:" in r.stdout


def test_giant_graph_example_ring_attention():
    """One sharded structure trained end-to-end over the 8-device mesh
    with ring attention (the long-context path as a user workflow)."""
    r = _run(
        "examples/giant_graph/giant.py",
        "--atoms", "125", "--configs", "8", "--epochs", "3",
        timeout=540,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "giant-graph training done" in r.stdout


def test_giant_graph_example_halo_mode():
    """The --halo path (ppermute boundary exchange, no full gather) as
    a user workflow, incl. the printed memory-model comparison."""
    r = _run(
        "examples/giant_graph/giant.py",
        "--atoms", "125", "--configs", "6", "--epochs", "2", "--halo",
        timeout=540,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "giant-graph training done" in r.stdout
    assert "memory model" in r.stdout


def test_uv_spectrum_example_multidim_head():
    """50-dim graph-output (full-spectrum) regression driver."""
    r = _run(
        "examples/dftb_uv_spectrum/uv_spectrum.py",
        "--mols", "80", "--epochs", "3",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "spectrum head" in r.stdout


def test_ani1x_example_mlip():
    r = _run(
        "examples/ani1_x/train.py", "--frames", "60", "--epochs", "2",
        "--mlip",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "test force loss" in r.stdout


def test_qm7x_train_then_inference():
    """train.py writes the checkpoint; inference.py reloads it through
    run_prediction (the reference qm7x_mlip_inference.py workflow)."""
    r = _run("examples/qm7x/train.py", "--frames", "60", "--epochs", "2")
    assert r.returncode == 0, r.stderr[-2000:]
    r = _run("examples/qm7x/inference.py", "--frames", "40", "--epochs", "2")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "inference error" in r.stdout


def test_transition1x_example():
    r = _run(
        "examples/transition1x/train.py",
        "--reactions", "8", "--epochs", "2",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final:" in r.stdout


def test_mptrj_example_periodic():
    r = _run(
        "examples/mptrj/train.py", "--structures", "60", "--epochs", "2"
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final:" in r.stdout


def test_alexandria_example_energy_baseline():
    """Exercises fit/subtract_energy_baseline in a user workflow."""
    r = _run(
        "examples/alexandria/train.py",
        "--structures", "60", "--epochs", "2",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "element coefficients fitted" in r.stdout


def test_eam_example_multitask():
    r = _run(
        "examples/eam/eam.py",
        "--structures", "60", "--epochs", "2", "--multitask",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "atomic_energy" in r.stdout


def test_ogb_example_smiles_edge_features():
    """ogb driver: SMILES ingestion (native parser) feeding an
    edge-featured PNA — one-hot bond classes on the edges."""
    r = _run("examples/ogb/train_gap.py", "--mols", "80", "--epochs", "2")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final:" in r.stdout


def test_open_catalyst_2025_mixed_pbc_example():
    """oc25 driver: periodic slabs + gas-phase frames in ONE MLIP run
    (mixed cell/edge_shifts presence through the field union)."""
    r = _run(
        "examples/open_catalyst_2025/train.py",
        "--systems", "40", "--epochs", "2",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final:" in r.stdout


def test_sc26_multi_model_hpo_example():
    """SC26 campaign: the HPO space includes mpnn_type itself."""
    r = _run(
        "examples/multidataset_hpo_sc26/train_hpo.py",
        "--trials", "2", "--epochs", "1", "--frames", "64",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "best: val" in r.stdout


def test_sc26_structure_optimization_example():
    """SC26 campaign: relaxation by gradient descent on positions with
    the trained MLIP's -grad(E, pos) forces must lower the energy."""
    r = _run(
        "examples/multidataset_hpo_sc26/structure_optimization.py",
        "--epochs", "2", "--frames", "64", "--blocks", "2",
        "--steps", "20",
        timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "relaxed: E" in r.stdout


def test_csce_example_smiles_ingestion():
    """csce driver end-to-end on synthetic SMILES strings through the
    rdkit-free parser (hydragnn_tpu/utils/smiles.py)."""
    r = _run("examples/csce/train_gap.py", "--mols", "80", "--epochs", "2")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final:" in r.stdout


def test_multibranch_hpo_example():
    """HPO x task parallelism: every random-search trial trains under
    the multibranch scheme through the public run_training API."""
    r = _run(
        "examples/multibranch_hpo/train.py",
        "--trials", "2", "--epochs", "2", "--sizes", "80", "40",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "best: val" in r.stdout


def test_multidataset_example_branch_routing():
    """One encoder, three per-family decoder branches routed by
    dataset_id inside a single-process run."""
    r = _run(
        "examples/multidataset/train.py",
        "--per_family", "40", "--epochs", "2",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "3 decoder branches" in r.stdout


def test_open_family_examples():
    """OC22 / OMat24 / OMol25 / nabla2DFT thin drivers."""
    for script, args in [
        ("examples/open_catalyst_2022/train.py", ["--systems", "40"]),
        ("examples/open_materials_2024/train.py", ["--structures", "50"]),
        ("examples/open_molecules_2025/train.py", ["--frames", "50"]),
        ("examples/nabla2_dft/train.py", ["--frames", "50"]),
    ]:
        r = _run(script, *args, "--epochs", "2", timeout=540)
        assert r.returncode == 0, f"{script}: {r.stderr[-2000:]}"
        assert "final:" in r.stdout, script


def test_qcml_example_mace():
    r = _run(
        "examples/qcml/train.py", "--frames", "48", "--epochs", "1",
        timeout=540,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final:" in r.stdout


def test_multidataset_hpo_example():
    """Random-search HPO over the two-family GFM setup."""
    r = _run(
        "examples/multidataset_hpo/train.py",
        "--per_family", "30", "--trials", "2", "--epochs", "1",
        timeout=540,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "best:" in r.stdout


def test_odac23_example_film_conditioning():
    """Graph-attr FiLM conditioning end-to-end (otherwise untested)."""
    r = _run(
        "examples/open_direct_air_capture_2023/train.py",
        "--systems", "48", "--epochs", "2",
        timeout=540,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "FiLM-conditioned" in r.stdout


def test_polymers_example_conv_node_head():
    """Long-chain graphs with a conv-type node decoder head."""
    r = _run(
        "examples/open_polymers_2026/train.py",
        "--chains", "60", "--epochs", "2",
        timeout=540,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "conv head" in r.stdout
