"""benchmarks/scopes.py and the readers built on it, against known answers.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

Not part of the repository's tier-1 tests. The decoder is held to the two
traces recorded on the v5e under fixtures/ (the numbers a second method
gave when each was recorded) and to a hand-made ``.xplane.pb`` written
through the same schema, whose seconds are worked out by hand below.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import scopes, spec, trace  # noqa: E402

FIXTURES = os.path.join(REPO, "benchmarks", "fixtures")
BODY = "jit(train_superstep)/while/body/closed_call/"
CONV = "Model.encode/stack.conv/conv_0/"


def close(a, b, tol=1e-9):
    return abs(a - b) <= tol * max(abs(a), abs(b), 1e-30)


def hand_made(head_tf_op: str) -> dict:
    """One K=2 scan, one evaluation step, one single train step (us).

    ``jit_train_superstep`` [0, 1000]: a ``while`` around three body ops,
    [100, 400] the forward scatter under edge_aggregate/segment/sum,
    [400, 700] a transpose( path under edge_aggregate, [700, 900] the
    optimizer: the while keeps the 200 us they do not cover, and its own
    path holds no module, so they fall under ``other``.
    ``jit_eval_step`` [2000, 2400]: one matmul of conv_1's filter MLP.
    ``jit_train_step`` [3000, 3500]: [3000, 3300] an op whose path is
    ``head_tf_op`` (none: no tf_op), [3300, 3500] the loss's transpose.
    Train programs: 1500 us; edge_aggregate 600, backward 500, optimizer
    200; with no tf_op on the head op only 1200 of 1500 carry a path."""
    ops = [
        ("%while.1 = (s32[], f32[8]) while((s32[], f32[8]) %t), body=%b",
         0, 1000, "jit(train_superstep)/while"),
        ("%fusion.1 = f32[8,4] fusion(s32[24] %i, f32[24,4] %m)", 100, 400,
         BODY + "jvp(Model)/" + CONV + "edge_aggregate/segment/sum/scatter-add"),
        ("%fusion.2 = f32[24,4] fusion(f32[8,4] %g, s32[24] %i)", 400, 700,
         BODY + "transpose(jvp(Model))/" + CONV + "edge_aggregate/gather"),
        ("%fusion.3 = f32[4,6] fusion(f32[4,6] %w, f32[4,6] %g)", 700, 900,
         BODY + "optimizer/mul"),
        ("%fusion.4 = f32[24,4] fusion(f32[24,3] %r, f32[3,4] %w)", 2000, 2400,
         "jit(eval_step)/Model.encode/stack.conv/conv_1/filter_mlp/dense_0/dot_general"),
        ("%fusion.5 = f32[8,1] fusion(f32[8,6] %h, f32[6,1] %w)", 3000, 3300,
         head_tf_op),
        ("%fusion.6 = f32[8,1] fusion(f32[8,1] %p, f32[8,1] %y)", 3300, 3500,
         "jit(train_step)/transpose(jvp(loss))/mul"),
    ]
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [
                ("jit_train_superstep(11)", 0, 1000, ""),
                ("jit_eval_step(12)", 2000, 2400, ""),
                ("jit_train_step(13)", 3000, 3500, ""),
            ],
            "XLA Ops": ops,
        },
        "/host:CPU": {
            "main": [
                ("train_step", 0, 50, ""), ("train/feed_wait", 1000, 2000, ""),
                ("eval_step", 2000, 2050, ""), ("train_step", 2990, 3010, ""),
            ],
        },
    }
    return {  # microseconds above, the trace's nanoseconds out
        p: {l: [(n, a * 1000, b * 1000, t) for n, a, b, t in evs]
            for l, evs in lines.items()}
        for p, lines in planes.items()
    }


HEAD = "jit(train_step)/jvp(Model)/decoder/head0/dense_1/dot_general"


def write_xplane(path: str, planes: dict) -> None:
    """The hand-made planes as a real ``.xplane.pb``, through the schema
    of scopes.py: ``tf_op`` as a string on even metadata ids and as a
    reference to a stat metadata's name on odd ones (a trace holds both)."""
    space = scopes._schema()()
    for pname, lines in planes.items():
        plane = space.planes.add(name=pname)
        stat = plane.stat_metadata.add(key=1)
        stat.value.id, stat.value.name = 1, "tf_op"
        ids = {}
        for lname, events in lines.items():
            line = plane.lines.add(name=lname, timestamp_ns=7)
            for name, a, b, tf_op in events:
                if (name, tf_op) not in ids:
                    mid = ids[(name, tf_op)] = len(ids) + 1
                    meta = plane.event_metadata.add(key=mid)
                    meta.value.id, meta.value.name = mid, name
                    if tf_op and mid % 2:
                        ref = plane.stat_metadata.add(key=100 + mid)
                        ref.value.id, ref.value.name = 100 + mid, tf_op + ":"
                        meta.value.stats.add(metadata_id=1, ref_value=100 + mid)
                    elif tf_op:
                        meta.value.stats.add(metadata_id=1, str_value=tf_op + ":")
                line.events.add(
                    metadata_id=ids[(name, tf_op)],
                    offset_ps=(a - 7) * 1000 + 999,  # whole ns, as ProfileData
                    duration_ps=(b - a) * 1000,
                )
    with open(path, "wb") as fh:
        fh.write(space.SerializeToString())


def test_decoder_on_the_recorded_small_trace():
    path = os.path.join(FIXTURES, "small_trace.xplane.pb")
    with open(os.path.join(FIXTURES, "small_trace.expected.json")) as fh:
        want = json.load(fh)
    planes = scopes.decode(path)
    # the same intervals ProfileData gives, so both reductions agree
    theirs = trace.planes_of(trace.load(path))
    for pname, lines in theirs.items():
        for lname, events in lines.items():
            assert [e[:3] for e in planes[pname][lname]] == events
    ops = planes["/device:TPU:0"]["XLA Ops"]
    assert len(ops) == want["n_op_events"]
    fusions = [e for e in ops if e[0].startswith("%fusion.1 = ")]
    assert len(fusions) == 3
    assert all(e[3] == "jit(step)/dot_general" for e in fusions)
    assert all(e[3] == "" for e in ops if e not in fusions)
    busy = sum(b - a for a, b in trace.union((e[1], e[2]) for e in ops)) / 1e9
    assert close(busy, want["busy_s"]), (busy, want["busy_s"])
    reduced = scopes.reduce(planes)
    assert list(reduced["device_s"]) == ["jit_step"]
    assert close(reduced["device_s"]["jit_step"], want["busy_s"])
    # a program from before the scopes: nothing is read, with a reason
    table, why = scopes.train_table(reduced)
    assert table is None and "jit_step" in why


def test_decoder_on_the_recorded_scoped_trace():
    """Real ``transpose(`` paths, a real ``while`` around a scan's body,
    two programs told apart by name: the table of the fixture against the
    one a second method gave when it was recorded on the v5e."""
    path = os.path.join(FIXTURES, "scoped_trace.xplane.pb")
    with open(os.path.join(FIXTURES, "scoped_trace.expected.json")) as fh:
        want = json.load(fh)
    planes = scopes.decode(path)
    ops = planes["/device:TPU:0"]["XLA Ops"]
    assert len(ops) == want["n_op_events"] and want["n_while_events"] >= 1
    assert sorted({e[3] for e in ops if e[3]}) == want["tf_ops"]
    reduced = scopes.reduce(planes)
    assert [m[0] for m in reduced["modules"]] == want["modules"]
    assert set(want["modules"]) == {"jit_train_step", "jit_train_superstep"}
    for program, table in want["programs"].items():
        assert set(reduced["programs"][program]) == set(table)
        for scope, row in table.items():
            got = reduced["programs"][program][scope]
            assert close(got["fwd"], row["fwd"], 1e-6), (program, scope)
            assert close(got["bwd"], row["bwd"], 1e-6), (program, scope)
    total = sum(reduced["device_s"].values())
    assert close(total, want["busy_s"], 1e-6), (total, want["busy_s"])
    assert close(sum(reduced["with_tf_op_s"].values()), want["with_tf_op_s"], 1e-6)
    table, total = scopes.train_table(reduced)
    assert table is not None, total
    # both directions under the scope (the recorded step differentiates
    # every operand), nothing of the optimizer on a transpose( path, and
    # the scan's steps cost what the single steps cost
    agg = [row for name, row in table.items() if name.startswith("edge_aggregate")]
    assert sum(r["fwd"] for r in agg) > 0 and sum(r["bwd"] for r in agg) > 0
    assert table.get("optimizer", {"bwd": 0.0})["bwd"] == 0
    single = reduced["device_s"]["jit_train_step"] / 2
    scanned = reduced["device_s"]["jit_train_superstep"] / 2
    assert 0.5 < scanned / single < 2.0, (single, scanned)


def test_reduction_of_hand_made_events(tmp_path):
    path = str(tmp_path / "hand.xplane.pb")
    write_xplane(path, hand_made(HEAD))
    planes = scopes.decode(path)
    assert planes["/device:TPU:0"]["XLA Ops"][2][3].endswith("edge_aggregate/gather")
    r = scopes.reduce(planes)
    us = 1e-6
    sup = r["programs"]["jit_train_superstep"]
    assert close(sup["edge_aggregate/segment/sum"]["fwd"], 300 * us)
    assert close(sup["edge_aggregate"]["bwd"], 300 * us)
    assert sup["edge_aggregate"]["fwd"] == 0
    assert close(sup["optimizer"]["fwd"], 200 * us)
    assert close(sup["other"]["fwd"], 200 * us)  # the while's own 200 us
    assert r["programs"]["jit_eval_step"] == {
        "conv/filter_mlp": {"fwd": pytest.approx(400 * us), "bwd": 0.0}
    }
    one = r["programs"]["jit_train_step"]
    assert close(one["decoder/head0"]["fwd"], 300 * us)
    assert close(one["loss"]["bwd"], 200 * us)
    assert close(r["device_s"]["jit_train_superstep"], 1000 * us)
    assert [m[0] for m in r["modules"]] == [
        "jit_train_superstep", "jit_eval_step", "jit_train_step",
    ]
    table, total = scopes.train_table(r)
    assert close(total, 1500 * us)
    assert close(scopes.under(table, "edge_aggregate"), 600 * us)
    assert close(sum(row["bwd"] for row in table.values()), 500 * us)
    # every second of the train programs is in exactly one row
    assert close(sum(v["fwd"] + v["bwd"] for v in table.values()), total)
    assert any("edge_aggregate/segment/sum" in line for line in scopes.rows(table, total))


@pytest.mark.parametrize("path,scope,backward", [
    ("", "other", False),
    ("jit(train_step)/convert_element_type", "other", False),
    ("jit(f)/transpose(jvp(segment/sum))/scatter-add", "segment/sum", True),
    ("jit(s)/jvp(M)/M._pool/pool/segment/mean/segment/sum/add",
     "pool/segment/mean/segment/sum", False),
    ("jit(s)/jvp(M)/M.encode/stack.conv/conv_3/filter_mlp/jit(softplus)/exp",
     "conv/filter_mlp", False),
    ("jit(s)/jit(main)/while/body/edge_aggregate/edge_aggregate/mul",
     "edge_aggregate", False),
    ("jit(s)/forces/transpose(jvp(M))/conv_0/edge_geometry/sub",
     "forces/edge_geometry", True),
])
def test_scope_of_a_path(path, scope, backward):
    assert scopes.scope_of(path) == (scope, backward)


def test_partial_attribution_reads_nothing(tmp_path):
    # the head op without a tf_op: 1200 of 1500 us carry a path, under 90%
    r = scopes.reduce(hand_made(""))
    table, why = scopes.train_table(r)
    assert table is None and "80.0%" in why
    # paths everywhere, none of the vocabulary: executables that predate
    # the scopes (a stale compile cache)
    planes = hand_made(HEAD)
    ops = planes["/device:TPU:0"]["XLA Ops"]
    planes["/device:TPU:0"]["XLA Ops"] = [
        e[:3] + ("jit(train_step)/jvp(Model)/conv_0/lin1/dot_general",)
        for e in ops
    ]
    table, why = scopes.train_table(scopes.reduce(planes))
    assert table is None and "vocabulary" in why
    assert scopes.reduce({"/host:CPU": {"main": []}}) is None


def test_counts_of_the_edge_aggregate_block():
    counts = spec.load_module("counts", "schnet_edge_aggregate")
    # the docstring's example, N=8, E=24, F=4, F_out=6
    assert counts.forward(8, 24, 4, 6) == {"bytes": 992, "flops": 576}
    assert counts.transpose(8, 24, 4, 6) == {"bytes": 1600, "flops": 1248}
    assert counts.transpose(8, 24, 4, 6, h_grad=False)["bytes"] == 1472
    peaks = spec.peaks("TPU v5 lite")
    arch = {"num_filters": 4, "hidden_dim": 6, "num_conv_layers": 3}
    seconds, which = counts.step_least_seconds(arch, 8, 24, peaks)
    assert which == "memory"
    assert close(seconds, 3 * (992 + 1600) / peaks["hbm_bytes_per_s"])
    # the issue's reckoning at schnet_qm9's padded shapes: 452 + 877 MB
    fwd = counts.forward(39696, 790776, 128, 128)["bytes"]
    bwd = counts.transpose(39696, 790776, 128, 128)["bytes"]
    assert round(fwd / 1e6) == 452 and round(bwd / 1e6) == 877


class FakeRun:
    """What a metric reader is given, over the hand-made trace."""

    def __init__(self, work, planes, rows, peaks=True):
        os.makedirs(os.path.join(work, "trace"))
        write_xplane(os.path.join(work, "trace", "hand.xplane.pb"), planes)
        tel = os.path.join(work, "telemetry.jsonl")
        with open(tel, "w") as fh:
            for row in rows:
                fh.write(json.dumps(row) + "\n")
        self.facts = {
            "trace_dir": os.path.join(work, "trace"), "telemetry_path": tel,
            "epochs": 2,
        }
        self.driver = spec.load_module("drivers", "train")
        self.peaks = spec.peaks("TPU v5 lite") if peaks else None
        self.cell = {"config": {"hydragnn": {"NeuralNetwork": {
            "Architecture": {"mpnn_type": "SchNet", "num_filters": 4,
                             "hidden_dim": 6, "num_conv_layers": 1},
            "Variables_of_interest": {"input_node_features": [0]},
        }}}}
        self._planes = planes

    def trace(self):
        return trace.reduce({
            p: {l: [e[:3] for e in evs] for l, evs in lines.items()}
            for p, lines in self._planes.items()
        })


def step_row(epoch, step, k):
    return {"t": "step", "region": "train", "epoch": epoch, "step": step,
            "k": k, "nodes_pad": 8, "edges_pad": 24, "nodes": 7, "edges": 20,
            "wall_ms": 1.0, "input_wait_ms": 0.1}


ROWS = [
    {"t": "setup", "phase": "config", "ms": 5.0},
    {"t": "setup", "phase": "compile", "ms": 2500.0, "compile_count": 4,
     "cache_hits": 3, "cache_misses": 1},
    step_row(0, 2, 2), step_row(0, 3, 1),
    {"t": "profile", "event": "start", "epoch": 1, "trace_dir": "x"},
    step_row(1, 2, 2), step_row(1, 3, 1),
    {"t": "profile", "event": "stop", "epoch": 1},
    step_row(2, 2, 2), step_row(2, 3, 1),
]


def metric(name, run):
    return spec.load_module("metrics", name).compute(run)


def test_metric_readers_on_the_hand_made_run(tmp_path):
    run = FakeRun(str(tmp_path / "a"), hand_made(HEAD), ROWS)
    assert close(metric("segment_time_share.train", run), 100 * 600 / 1500)
    assert close(metric("backward_time_share.train", run), 100 * 500 / 1500)
    assert close(metric("optimizer_time_share.train", run), 100 * 200 / 1500)
    # the scan's 1000 us over K=2, then the single step's 500 us
    assert close(metric("device_step_ms.train", run), 0.5)
    # three steps of one layer at N=8, E=24, F=4, F_out=6 over 600 us
    least = 3 * (992 + 1600) / run.peaks["hbm_bytes_per_s"]
    assert close(metric("segment_roofline_share.train", run), 100 * least / 600e-6)
    assert metric("setup_compile_s", run) == 2.5
    # idle: [1000, 2000] under train/feed_wait, [2400, 3000] under nothing
    assert close(metric("idle_unattributed_share.train", run), 100 * 600 / 1600)
    assert "_scopes" in run.facts  # reduced once, kept


def test_metric_readers_read_nothing_from_a_program_without_scopes(tmp_path):
    """The parent commit: programs called jit_step, no vocabulary, no
    setup row. Every new reader returns None and none raises."""
    planes = hand_made(HEAD)
    dev = planes["/device:TPU:0"]
    dev["XLA Modules"] = [("jit_step(1)",) + m[1:] for m in dev["XLA Modules"]]
    rows = [r for r in ROWS if r["t"] != "setup"]
    run = FakeRun(str(tmp_path / "b"), planes, rows)
    for name in ("segment_time_share.train", "backward_time_share.train",
                 "optimizer_time_share.train", "device_step_ms.train",
                 "segment_roofline_share.train", "setup_compile_s"):
        assert metric(name, run) is None, name
    # and off the TPU (a rehearsal: no peaks) no share of a roofline
    cpu = FakeRun(str(tmp_path / "c"), hand_made(HEAD), ROWS, peaks=False)
    assert metric("segment_roofline_share.train", cpu) is None
    cpu.facts["trace_dir"] = None
    cpu.facts.pop("_scopes")
    assert metric("segment_time_share.train", cpu) is None
