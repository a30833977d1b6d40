"""The triplet readers (benchmarks/triplet_scopes.py, metrics/triplet_*) and
the DimeNet++ counts, against known answers.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

Not part of the repository's tier-1 tests. A new file beside test_scopes.py,
whose hand-made trace writer and fake run it borrows: every reader returns
None on the two traces recorded on the v5e (programs without the triplet
scopes) and on StepClock rows without triplet counts, and reads a
hand-made plane as worked out by hand below.
"""

from __future__ import annotations

import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, HERE]

from benchmarks import spec, triplet_scopes  # noqa: E402
from test_scopes import ROWS, FakeRun, close, step_row, write_xplane  # noqa: E402

FIXTURES = os.path.join(REPO, "benchmarks", "fixtures")
BODY = "jit(train_superstep)/while/body/closed_call/"
ENC = "Model.encode/stack.conv/"
READERS = ("triplet_time_share.train", "triplet_roofline_share.train",
           "triplet_pad_ratio.train")
ARCH = {"mpnn_type": "DimeNet", "num_spherical": 3, "num_radial": 1,
        "basis_emb_size": 2, "int_emb_size": 5, "num_conv_layers": 1}


def metric(name, run):
    return spec.load_module("metrics", name).compute(run)


def hand_made() -> dict:
    """One K=2 scan, one evaluation step, one single train step (us).

    ``jit_train_superstep`` [0, 1000]: a ``while`` around four body ops,
    [100, 300] the triplet reduce (``triplet/segment/sum``, forward), [300,
    600] the basis projection's transpose under ``triplet``, [600, 700]
    ``triplet_basis``, [700, 900] the optimizer; the while keeps 200.
    ``jit_eval_step`` [2000, 2400]: a ``triplet`` op of evaluation (not a
    train program: not counted). ``jit_train_step`` [3000, 3500]: [3000,
    3300] a ``triplet`` product of block 1, [3300, 3500] the loss's
    transpose. Train programs 1500 us; ``triplet`` 200 + 300 + 300 = 800,
    ``triplet_basis`` 100: a share of 900 / 1500."""
    ops = [
        ("%while.1 = (s32[], f32[8]) while((s32[], f32[8]) %t), body=%b",
         0, 1000, "jit(train_superstep)/while"),
        ("%fusion.1 = f32[4,5] fusion(s32[10] %i, f32[10,5] %m)", 100, 300,
         BODY + "jvp(Model)/" + ENC + "inter_0/triplet/segment/sum/scatter-add"),
        ("%fusion.2 = f32[3,2] fusion(f32[10,3] %s, f32[10,2] %g)", 300, 600,
         BODY + "transpose(jvp(Model))/" + ENC + "inter_0/triplet/lin_sbf1/dot_general"),
        ("%fusion.3 = f32[10,3] fusion(f32[10] %a)", 600, 700,
         BODY + "jvp(Model)/Model.encode/stack.embed/triplet_basis/cos"),
        ("%fusion.4 = f32[4,6] fusion(f32[4,6] %w, f32[4,6] %g)", 700, 900,
         BODY + "optimizer/mul"),
        ("%fusion.5 = f32[10,5] fusion(f32[10,5] %x, f32[10,5] %b)", 2000, 2400,
         "jit(eval_step)/Model.encode/stack.conv/inter_0/triplet/mul"),
        ("%fusion.6 = f32[10,5] fusion(f32[10,5] %x, f32[10,5] %b)", 3000, 3300,
         "jit(train_step)/jvp(Model)/" + ENC + "inter_1/triplet/mul"),
        ("%fusion.7 = f32[8,1] fusion(f32[8,1] %p, f32[8,1] %y)", 3300, 3500,
         "jit(train_step)/transpose(jvp(loss))/mul"),
    ]
    planes = {"/device:TPU:0": {
        "XLA Modules": [
            ("jit_train_superstep(11)", 0, 1000, ""),
            ("jit_eval_step(12)", 2000, 2400, ""),
            ("jit_train_step(13)", 3000, 3500, ""),
        ],
        "XLA Ops": ops,
    }}
    return {
        p: {l: [(n, a * 1000, b * 1000, t) for n, a, b, t in evs]
            for l, evs in lines.items()}
        for p, lines in planes.items()
    }


def triplet_row(epoch, step, k):
    return dict(step_row(epoch, step, k), triplets_pad=10, edges_pad=4,
                triplets=6 * k)


ROWS_T = [
    r if r.get("t") != "step" else triplet_row(r["epoch"], r["step"], r["k"])
    for r in ROWS
]


def dimenet_run(work, planes, rows):
    run = FakeRun(work, planes, rows)
    net = run.cell["config"]["hydragnn"]["NeuralNetwork"]
    net["Architecture"] = dict(ARCH)
    return run


def test_readers_on_the_hand_made_run(tmp_path):
    run = dimenet_run(str(tmp_path / "a"), hand_made(), ROWS_T)
    assert close(metric("triplet_time_share.train", run), 100 * 900 / 1500)
    s = run.facts["_triplet_scopes"]  # reduced once, kept
    assert close(s["triplet"], 800e-6) and close(s["triplet_basis"], 100e-6)
    # three steps of the traced epoch (k 2 and 1) at T=10, E=4, one block;
    # the hand-worked counts below, memory-bound both ways
    least = 3 * (424 + 568) / run.peaks["hbm_bytes_per_s"]
    got = metric("triplet_roofline_share.train", run)
    assert close(got, 100 * least / 800e-6) and got <= 100.0
    # the window's epochs 1 and 2: 2 x (2 + 1) steps of 10 slots over
    # 2 x (12 + 6) real triplets
    assert close(metric("triplet_pad_ratio.train", run), 60 / 36)


@pytest.mark.parametrize("fixture", ["small_trace", "scoped_trace"])
def test_readers_read_nothing_from_a_program_without_the_scopes(tmp_path, fixture):
    """The two recorded traces (no triplet scope; the small one no scope
    at all) and rows without triplet counts: every reader returns None."""
    run = dimenet_run(str(tmp_path / fixture), hand_made(), ROWS)
    shutil.rmtree(run.facts["trace_dir"])
    os.makedirs(run.facts["trace_dir"])
    shutil.copy(os.path.join(FIXTURES, fixture + ".xplane.pb"),
                run.facts["trace_dir"])
    for name in READERS:
        assert metric(name, run) is None, name


def test_readers_read_nothing_without_triplet_counts_or_a_trace(tmp_path):
    run = dimenet_run(str(tmp_path / "a"), hand_made(), ROWS)
    assert metric("triplet_pad_ratio.train", run) is None
    assert metric("triplet_roofline_share.train", run) is None
    run = dimenet_run(str(tmp_path / "b"), hand_made(), ROWS_T)
    run.facts["trace_dir"] = None
    for name in ("triplet_time_share.train", "triplet_roofline_share.train"):
        assert metric(name, run) is None, name
    # scopes.py's partial attribution (a trace without metadata) reads
    # nothing here either
    planes = hand_made()
    ops = planes["/device:TPU:0"]["XLA Ops"]
    planes["/device:TPU:0"]["XLA Ops"] = [e[:3] + ("",) for e in ops]
    run = dimenet_run(str(tmp_path / "c"), planes, ROWS_T)
    assert metric("triplet_time_share.train", run) is None


def test_seconds_of_the_hand_made_planes():
    got = triplet_scopes.seconds(hand_made())
    assert close(got["triplet"], 800e-6) and close(got["triplet_basis"], 100e-6)


def test_counts_of_the_triplet_block():
    counts = spec.load_module("counts", "dimenet_triplet")
    # the docstring's example, T=10, E=4, SR=3, B=2, I=5
    assert counts.forward(10, 4, 3, 2, 5) == {"bytes": 424, "flops": 420}
    assert counts.transpose(10, 4, 3, 2, 5) == {"bytes": 568, "flops": 1040}
    peaks = spec.peaks("TPU v5 lite")
    seconds, which = counts.step_least_seconds(ARCH, 10, 4, peaks)
    assert which == "memory"
    assert close(seconds, (424 + 568) / peaks["hbm_bytes_per_s"])


def test_counts_of_a_dimenet_step():
    counts = spec.load_module("counts", "dimenet")
    arch = {
        "hidden_dim": 4, "int_emb_size": 2, "basis_emb_size": 3,
        "out_emb_size": 5, "num_radial": 2, "num_spherical": 2,
        "num_conv_layers": 1, "input_dim": 1,
        "output_heads": {"graph": {"num_sharedlayers": 1, "dim_sharedlayers": 3,
                                   "num_headlayers": 1, "dim_headlayers": [2]}},
    }
    heads = [{"type": "graph", "dim": 1}]
    assert counts.forward_macs(arch, heads, 3, 4, 6) == 1407  # the docstring's
    # the triplet bound: a triangle, every pair of atoms joined both ways
    assert counts.triplets_per_graph(3, 6) == 6
    assert counts.train_flops_per_graph(arch, heads, 3, 6) == 6 * (
        counts.forward_macs(arch, heads, 3, 6, 6)
    )
