"""Example-driver smoke cases (reference tests/test_examples.py runs the
actual examples/ scripts): each driver must run end to end with tiny
settings, exit 0 and print what its workflow promises.

Every case is a subprocess, and ``--dist loadfile`` gives a whole file
to one worker, so the cases are one table here and a thin
``tests/test_examples_<family>.py`` per family: the families are shared
out by measured seconds (CHANGES.md, PR 31), none over four minutes in
the driver's command. A new case joins the lightest family that fits.
"""

import os
import subprocess
import sys

import pytest

import tests._cpu  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args):
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
        timeout=300,  # twice the slowest case under six workers' load
        env=env,
        cwd=REPO,
    )


# family -> (id, script, args, strings stdout must hold)
CASES = {
    # Each giant-graph run shares a file with lighter mesh drivers: xdist
    # hands files out by falling case count, so a file of one or two
    # cases starts last, and a two-minute case that starts last is the
    # whole run's tail.
    "giant_ring_multibranch": [
        # one sharded structure trained over the 8-device mesh with ring
        # attention (the long-context path as a user workflow)
        ("giant_graph_ring_attention", "examples/giant_graph/giant.py",
         "--atoms 125 --configs 8 --epochs 3", ["giant-graph training done"]),
        ("multibranch", "examples/multibranch/train.py",
         "--epochs 2 --sizes 60 30 --hidden_dim 8",
         ["devices per branch", "epoch   1"]),
        # HPO x task parallelism: every random-search trial trains under
        # the multibranch scheme through run_training
        ("multibranch_hpo", "examples/multibranch_hpo/train.py",
         "--trials 2 --epochs 2 --sizes 80 40", ["best: val"]),
    ],
    "giant_halo_multidataset": [
        # the --halo path (ppermute boundary exchange, no full gather),
        # incl. the printed memory-model comparison
        ("giant_graph_halo_mode", "examples/giant_graph/giant.py",
         "--atoms 125 --configs 6 --epochs 2 --halo",
         ["giant-graph training done", "memory model"]),
        # one encoder, three per-family decoder branches routed by
        # dataset_id inside a single-process run
        ("multidataset_branch_routing", "examples/multidataset/train.py",
         "--per_family 40 --epochs 2", ["3 decoder branches"]),
        # random-search HPO over the two-family GFM setup
        ("multidataset_hpo", "examples/multidataset_hpo/train.py",
         "--per_family 30 --trials 2 --epochs 1", ["best:"]),
    ],
    "forces": [
        ("lennard_jones", "examples/LennardJones/LennardJones.py",
         "--configs 40 --epochs 4", ["force MAE"]),
        ("md17", "examples/md17/md17.py",
         "--frames 60 --epochs 3", ["test force loss"]),
        ("ani1x_mlip", "examples/ani1_x/train.py",
         "--frames 60 --epochs 2 --mlip", ["test force loss"]),
    ],
    "qm": [  # and test_qm7x_train_then_inference, in the family's file
        ("qm9_synthetic", "examples/qm9/qm9.py",
         "--synthetic --mols 60 --epochs 3", ["Test MAE"]),
        ("qm9_hpo", "examples/qm9_hpo/qm9_hpo.py",
         "--trials 2 --epochs 1 --mols 40", ["best:"]),
        ("nabla2_dft", "examples/nabla2_dft/train.py",
         "--frames 50 --epochs 2", ["final:"]),
    ],
    "molecules": [
        ("zinc_gps", "examples/zinc/zinc.py",
         "--mols 80 --epochs 3", ["final:"]),
        # 50-dim graph-output (full-spectrum) regression driver
        ("uv_spectrum_multidim_head",
         "examples/dftb_uv_spectrum/uv_spectrum.py",
         "--mols 80 --epochs 3", ["spectrum head"]),
        # SMILES ingestion (native parser) feeding an edge-featured PNA:
        # one-hot bond classes on the edges
        ("ogb_smiles_edge_features", "examples/ogb/train_gap.py",
         "--mols 80 --epochs 2", ["final:"]),
        # synthetic SMILES strings through the rdkit-free parser
        # (hydragnn_tpu/utils/smiles.py)
        ("csce_smiles_ingestion", "examples/csce/train_gap.py",
         "--mols 80 --epochs 2", ["final:"]),
    ],
    "catalysis": [
        ("oc20", "examples/open_catalyst_2020/oc20.py",
         "--systems 48 --epochs 2", ["test force loss"]),
        ("oc22", "examples/open_catalyst_2022/train.py",
         "--systems 40 --epochs 2", ["final:"]),
        # periodic slabs + gas-phase frames in ONE MLIP run (mixed
        # cell/edge_shifts presence through the field union)
        ("oc25_mixed_pbc", "examples/open_catalyst_2025/train.py",
         "--systems 40 --epochs 2", ["final:"]),
        # graph-attr FiLM conditioning end-to-end (otherwise untested)
        ("odac23_film_conditioning",
         "examples/open_direct_air_capture_2023/train.py",
         "--systems 48 --epochs 2", ["FiLM-conditioned"]),
    ],
    "materials": [
        # the full Dataset.path raw-LSMS ingestion inside run_training
        # (format detect -> read -> normalize -> split)
        ("lsms_raw_ingest", "examples/lsms/lsms.py",
         "--configs 60 --epochs 2", ["final:"]),
        ("ising_multihead", "examples/ising_model/ising.py",
         "--configs 60 --epochs 2", ["field"]),
        ("eam_multitask", "examples/eam/eam.py",
         "--structures 60 --epochs 2 --multitask", ["atomic_energy"]),
        ("mptrj_periodic", "examples/mptrj/train.py",
         "--structures 60 --epochs 2", ["final:"]),
        # fit/subtract_energy_baseline in a user workflow
        ("alexandria_energy_baseline", "examples/alexandria/train.py",
         "--structures 60 --epochs 2", ["element coefficients fitted"]),
    ],
    "sc26": [
        # SC26 campaign: the HPO space includes mpnn_type itself
        ("sc26_multi_model_hpo",
         "examples/multidataset_hpo_sc26/train_hpo.py",
         "--trials 2 --epochs 1 --frames 64", ["best: val"]),
        # SC26 campaign: relaxation by gradient descent on positions with
        # the trained MLIP's -grad(E, pos) forces must lower the energy
        ("sc26_structure_optimization",
         "examples/multidataset_hpo_sc26/structure_optimization.py",
         "--epochs 2 --frames 64 --blocks 2 --steps 20", ["relaxed: E"]),
        ("qcml_mace", "examples/qcml/train.py",
         "--frames 48 --epochs 1", ["final:"]),
    ],
    "open": [
        ("omat24", "examples/open_materials_2024/train.py",
         "--structures 50 --epochs 2", ["final:"]),
        ("omol25", "examples/open_molecules_2025/train.py",
         "--frames 50 --epochs 2", ["final:"]),
        ("transition1x", "examples/transition1x/train.py",
         "--reactions 8 --epochs 2", ["final:"]),
        # long-chain graphs with a conv-type node decoder head
        ("polymers_conv_node_head", "examples/open_polymers_2026/train.py",
         "--chains 60 --epochs 2", ["conv head"]),
    ],
}


def family(name):
    """``parametrize`` mark over one family's share of the table."""
    return pytest.mark.parametrize(
        "script,args,expected",
        [pytest.param(s, a, e, id=i) for i, s, a, e in CASES[name]],
    )


def check_example(script, args, expected):
    r = _run(script, *args.split())
    assert r.returncode == 0, f"{script}: {r.stderr[-2000:]}"
    for text in expected:
        assert text in r.stdout, f"{script}: no {text!r} in stdout"
