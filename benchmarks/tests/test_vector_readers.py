"""The vector-message readers (benchmarks/named_scopes.py,
metrics/vector_message_*) and the PaiNN counts, against known answers.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

Not part of the repository's tier-1 tests. A new file beside test_scopes.py,
whose hand-made trace writer and fake run it borrows: both readers return
None on a program without the scopes (the two traces recorded on the v5e,
a SchNet run, a PaiNN program from before the scopes), named_scopes.py
reads the triplet scopes as triplet_scopes.py does, and a hand-made plane
reads as worked out by hand below.
"""

from __future__ import annotations

import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, HERE]

from benchmarks import named_scopes, spec, triplet_scopes  # noqa: E402
from test_scopes import HEAD, ROWS, FakeRun, close  # noqa: E402
from test_scopes import hand_made as schnet_planes  # noqa: E402
from test_triplet_readers import hand_made as triplet_planes  # noqa: E402

FIXTURES = os.path.join(REPO, "benchmarks", "fixtures")
BODY = "jit(train_superstep)/while/body/closed_call/"
ENC = "Model.encode/stack.conv/"
READERS = ("vector_message_time_share.train",
           "vector_message_roofline_share.train")
ARCH = {"mpnn_type": "PAINN", "hidden_dim": 1, "num_radial": 2,
        "num_conv_layers": 2}


def metric(name, run):
    return spec.load_module("metrics", name).compute(run)


def hand_made(scoped: bool = True) -> dict:
    """One K=2 scan, one evaluation step, one single train step (us).

    ``jit_train_superstep`` [0, 1000]: a ``while`` around four body ops,
    [100, 300] the vector message's receiver sum (``vector_message/
    edge_aggregate/segment/sum``, forward), [300, 600] the sender gather's
    transpose under ``vector_message``, [600, 700] the update under
    ``vector_update``, [700, 900] the optimizer; the while keeps 200.
    ``jit_eval_step`` [2000, 2400]: a ``vector_message`` op of evaluation
    (not a train program: not counted). ``jit_train_step`` [3000, 3500]:
    [3000, 3300] a ``vector_message`` product of block 1, [3300, 3500] the
    loss's transpose. Train programs 1500 us; ``vector_message`` 200 + 300
    + 300 = 800, ``vector_update`` 100. Without ``scoped`` the paths lack
    both names, as in a program from before the scopes."""
    vm = "vector_message/" if scoped else ""
    vu = "vector_update/" if scoped else ""
    ops = [
        ("%while.1 = (s32[], f32[8]) while((s32[], f32[8]) %t), body=%b",
         0, 1000, "jit(train_superstep)/while"),
        ("%fusion.1 = f32[8,3] fusion(s32[24] %i, f32[24,3] %m)", 100, 300,
         BODY + "jvp(Model)/" + ENC + "message_0/" + vm
         + "edge_aggregate/segment/sum/scatter-add"),
        ("%fusion.2 = f32[8,3] fusion(f32[24,3] %g, s32[24] %i)", 300, 600,
         BODY + "transpose(jvp(Model))/" + ENC + "message_1/" + vm + "gather"),
        ("%fusion.3 = f32[8,3,1] fusion(f32[8,3,1] %v)", 600, 700,
         BODY + "jvp(Model)/" + ENC + "update_0/" + vu + "update_U/dot_general"),
        ("%fusion.4 = f32[4,6] fusion(f32[4,6] %w, f32[4,6] %g)", 700, 900,
         BODY + "optimizer/mul"),
        ("%fusion.5 = f32[24,3] fusion(f32[24,3] %x, f32[24,3] %b)", 2000, 2400,
         "jit(eval_step)/Model.encode/stack.conv/message_0/" + vm + "mul"),
        ("%fusion.6 = f32[24,3] fusion(f32[24,3] %x, f32[24,3] %b)", 3000, 3300,
         "jit(train_step)/jvp(Model)/" + ENC + "message_1/" + vm + "mul"),
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


def painn_run(work, planes, rows=ROWS):
    run = FakeRun(work, planes, rows)
    net = run.cell["config"]["hydragnn"]["NeuralNetwork"]
    net["Architecture"] = dict(ARCH)
    return run


def test_readers_on_the_hand_made_run(tmp_path):
    run = painn_run(str(tmp_path / "a"), hand_made())
    assert close(metric("vector_message_time_share.train", run), 100 * 800 / 1500)
    s = run.facts["_named_scopes"][("vector_message",)]  # reduced once, kept
    assert close(s["vector_message"], 800e-6) and close(s["total_s"], 1500e-6)
    # three steps of the traced epoch (k 2 and 1) at N=8, E=24, two blocks
    # (the first without v), memory-bound both ways
    counts = spec.load_module("counts", "painn_vector_message")
    per_step = sum(
        count(8, 24, 1, 2, first=first)["bytes"]
        for first in (True, False)
        for count in (counts.forward, counts.transpose)
    )
    least = 3 * per_step / run.peaks["hbm_bytes_per_s"]
    got = metric("vector_message_roofline_share.train", run)
    assert close(got, 100 * least / 800e-6) and got <= 100.0


def test_update_scope_is_read_apart():
    got = named_scopes.seconds(hand_made(), ("vector_message", "vector_update"))
    assert close(got["vector_message"], 800e-6)
    assert close(got["vector_update"], 100e-6)


@pytest.mark.parametrize("fixture", ["small_trace", "scoped_trace"])
def test_readers_read_nothing_from_the_recorded_traces(tmp_path, fixture):
    """The two traces recorded on the v5e (no vector scope; the small one
    no scope at all): both readers return None."""
    run = painn_run(str(tmp_path / fixture), hand_made())
    shutil.rmtree(run.facts["trace_dir"])
    os.makedirs(run.facts["trace_dir"])
    shutil.copy(os.path.join(FIXTURES, fixture + ".xplane.pb"),
                run.facts["trace_dir"])
    for name in READERS:
        assert metric(name, run) is None, name


@pytest.mark.parametrize("program", ["schnet", "painn_before_the_scopes"])
def test_readers_read_nothing_from_a_program_without_the_scopes(tmp_path, program):
    """A SchNet run, and PaiNN as the parent commit builds it (the same
    ops, no ``vector_message`` on their paths): both readers return None
    and neither raises."""
    if program == "schnet":
        run = FakeRun(str(tmp_path / program), schnet_planes(HEAD), ROWS)
    else:
        run = painn_run(str(tmp_path / program), hand_made(scoped=False))
    for name in READERS:
        assert metric(name, run) is None, name


def test_readers_read_nothing_without_a_trace_or_off_the_chip(tmp_path):
    run = painn_run(str(tmp_path / "a"), hand_made())
    run.facts["trace_dir"] = None
    for name in READERS:
        assert metric(name, run) is None, name
    cpu = painn_run(str(tmp_path / "b"), hand_made())
    cpu.peaks = None  # a rehearsal: no share of a roofline
    assert metric("vector_message_roofline_share.train", cpu) is None


def test_named_scopes_read_the_triplet_scopes_as_triplet_scopes_does():
    planes = triplet_planes()
    names = ("triplet", "triplet_basis")
    got = named_scopes.seconds(planes, names)
    want = triplet_scopes.seconds(planes)
    assert set(got) == set(want)
    for name in names:
        assert close(got[name], want[name]), name
    assert close(got["triplet"], 800e-6) and close(got["triplet_basis"], 100e-6)


def test_counts_of_the_vector_message_block():
    counts = spec.load_module("counts", "painn_vector_message")
    # the docstring's example, N=2, E=3, F=1, R=2
    assert counts.forward(2, 3, 1, 2) == {"bytes": 196, "flops": 110}
    assert counts.transpose(2, 3, 1, 2) == {"bytes": 272, "flops": 183}
    assert counts.forward(2, 3, 1, 2, first=True) == {"bytes": 152, "flops": 71}
    assert counts.transpose(2, 3, 1, 2, first=True) == {"bytes": 184, "flops": 105}
    peaks = spec.peaks("TPU v5 lite")
    seconds, which = counts.step_least_seconds(ARCH, 2, 3, peaks)
    assert which == "memory"
    assert close(seconds, (152 + 184 + 196 + 272) / peaks["hbm_bytes_per_s"])


def test_counts_of_a_painn_step():
    counts = spec.load_module("counts", "painn")
    arch = {
        "hidden_dim": 2, "num_radial": 3, "num_conv_layers": 2,
        "output_heads": {"graph": {"num_sharedlayers": 1, "dim_sharedlayers": 3,
                                   "num_headlayers": 1, "dim_headlayers": [2]}},
    }
    heads = [{"type": "graph", "dim": 1}]
    assert counts.forward_macs(arch, heads, 4, 6) == 2230  # the docstring's
    assert counts.train_flops_per_graph(arch, heads, 4, 6) == 6 * 2230
