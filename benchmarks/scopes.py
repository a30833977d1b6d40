#!/usr/bin/env python3
"""A device trace read by program, scope and direction.

    python3 benchmarks/scopes.py <trace dir or .xplane.pb>

``benchmarks/trace.py`` names an op by opcode and shapes, because that is
all ``jax.profiler.ProfileData`` exposes; the forward scatter of the
messages and the transpose of the sender gather then share one row. The
``.xplane.pb`` holds more: every op's *event metadata* carries ``tf_op``,
the HLO ``op_name``, which is the path of ``jax.named_scope``s, Flax module
names and JAX's own ``jvp(`` / ``transpose(`` wrappers under which the op
was traced (``jit(train_step)/transpose(jvp(Model))/conv_0/edge_aggregate/
segment/sum/scatter-add``). This file decodes it with ``google.protobuf``
and a descriptor of the xplane schema built here (the fields needed are
few; tensorflow's ``xplane_pb2`` costs 14 s to import), and reduces it:

* an op's time is its self time by ``trace.self_times``' rule (a ``while``
  is charged only what its body's ops do not cover);
* an op's program is the event of the ``XLA Modules`` line that encloses
  it, without the fingerprint: ``jit_train_superstep``;
* an op's scope is the chain of vocabulary names on its path
  (``edge_aggregate``, ``edge_aggregate/segment/sum``; the program's
  ``utils/tracer.SCOPES``); an op with none falls under its Flax modules
  (``conv/filter_mlp``, instance numbers folded) or, with no module or no
  ``tf_op`` at all, under ``other``;
* backward is a path through ``transpose(``; everything else is forward;
* a fusion is charged whole to the path of its root instruction, which is
  what XLA writes as the fusion's ``op_name``: a producer of another scope
  fused into it is charged to the root's scope.

``reduce`` gives ``{program: {scope: {"fwd": s, "bwd": s}}}`` and the
totals; the metric readers (``metrics/segment_*``, ``backward_time_share``,
``optimizer_time_share``, ``device_step_ms``) take theirs from ``of_run``,
which reduces a run's trace once and returns None, with a logged reason,
where under 90% of the train programs' device time carries a ``tf_op`` or
no event carries a vocabulary name (a stale compile cache, a trace without
metadata, a program that predates the scopes): never a number from a
partial attribution.
"""

from __future__ import annotations

import bisect
import os
import re
import sys

if __name__ == "__main__":  # run by hand from anywhere
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import trace as trace_mod

VOCABULARY = (
    "edge_geometry", "edge_aggregate", "segment", "pool", "loss", "forces",
    "optimizer", "guard",
)
MODULES_LINE = "XLA Modules"
TRAIN_PROGRAMS = ("jit_train_step", "jit_train_superstep")
EVAL_PROGRAMS = ("jit_eval_step", "jit_eval_superstep")
OTHER = "other"
MIN_ATTRIBUTED = 0.90
# path components that say how the op was traced, not where it belongs
_STRUCTURE = {
    "while", "body", "cond", "branch", "closed_call", "checkpoint", "remat",
    "custom_vjp_call", "custom_jvp_call", "custom_vjp_call_jaxpr", "pjit",
    "core_call", "shard_map", "vmap",
}
_WRAPPER = re.compile(r"^(?:[A-Za-z_]+\()+")
_INSTANCE = re.compile(r"_\d+$")


# -- the xplane schema, as far as it is read ----------------------------

_SCHEMA = None


def _schema():
    """The ``XSpace`` message class, from a descriptor built here. Maps are
    declared as the repeated key/value entries they are on the wire."""
    global _SCHEMA
    if _SCHEMA is not None:
        return _SCHEMA
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    INT64, UINT64, DOUBLE = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    STRING, BYTES, MESSAGE = F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE
    messages = {
        "XStat": [
            ("metadata_id", 1, INT64), ("double_value", 2, DOUBLE),
            ("uint64_value", 3, UINT64), ("int64_value", 4, INT64),
            ("str_value", 5, STRING), ("bytes_value", 6, BYTES),
            ("ref_value", 7, UINT64),
        ],
        "XEvent": [
            ("metadata_id", 1, INT64), ("offset_ps", 2, INT64),
            ("duration_ps", 3, INT64), ("stats", 4, "XStat*"),
            ("num_occurrences", 5, INT64),
        ],
        "XLine": [
            ("id", 1, INT64), ("name", 2, STRING), ("timestamp_ns", 3, INT64),
            ("events", 4, "XEvent*"), ("duration_ps", 9, INT64),
            ("display_name", 11, STRING),
        ],
        "XEventMetadata": [
            ("id", 1, INT64), ("name", 2, STRING), ("metadata", 3, BYTES),
            ("display_name", 4, STRING), ("stats", 5, "XStat*"),
        ],
        "XStatMetadata": [
            ("id", 1, INT64), ("name", 2, STRING), ("description", 3, STRING),
        ],
        "EventMetadataEntry": [("key", 1, INT64), ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, INT64), ("value", 2, "XStatMetadata")],
        "XPlane": [
            ("id", 1, INT64), ("name", 2, STRING), ("lines", 3, "XLine*"),
            ("event_metadata", 4, "EventMetadataEntry*"),
            ("stat_metadata", 5, "StatMetadataEntry*"), ("stats", 6, "XStat*"),
        ],
        "XSpace": [("planes", 1, "XPlane*")],
    }
    fd = descriptor_pb2.FileDescriptorProto(
        name="benchmarks_scopes_xplane.proto", package="benchmarks_scopes",
        syntax="proto3",
    )
    for mname, fields in messages.items():
        msg = fd.message_type.add(name=mname)
        for fname, number, kind in fields:
            field = msg.field.add(name=fname, number=number)
            field.label = F.LABEL_OPTIONAL
            if isinstance(kind, str):
                if kind.endswith("*"):
                    field.label = F.LABEL_REPEATED
                field.type = MESSAGE
                field.type_name = ".benchmarks_scopes." + kind.rstrip("*")
            else:
                field.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    _SCHEMA = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchmarks_scopes.XSpace")
    )
    return _SCHEMA


def decode(path: str) -> dict:
    """``{plane name: {line name: [(name, start_ns, end_ns, tf_op), ...]}}``
    of a ``.xplane.pb``: ``trace.planes_of``'s shape with the op's ``tf_op``
    ('' where it has none) as a fourth field. Times are the line's
    ``timestamp_ns`` plus the event's offset, in whole nanoseconds as
    ``ProfileData`` gives them, so both reductions see the same intervals.
    The trace writes ``tf_op`` as ``<op_name>:<op type>``, the type empty
    for a JAX program: the colon and what follows are dropped."""
    space = _schema()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out = {}
    for plane in space.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op_ids = {k for k, v in stat_names.items() if v == "tf_op"}
        meta = {}
        for entry in plane.event_metadata:
            tf_op = ""
            for stat in entry.value.stats:
                if stat.metadata_id in tf_op_ids:
                    # a string, or a reference to a stat metadata's name
                    tf_op = stat.str_value or stat_names.get(stat.ref_value, "")
            meta[entry.key] = (entry.value.name, tf_op.rpartition(":")[0] or tf_op)
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                name, tf_op = meta.get(ev.metadata_id, ("", ""))
                start = float(line.timestamp_ns + ev.offset_ps // 1000)
                end = start + ev.duration_ps // 1000
                events.append((name, start, end, tf_op))
    return out


# -- from a path to a scope ----------------------------------------------


def _components(tf_op: str) -> list:
    """The path's components without JAX's transform wrappers:
    ``transpose(jvp(conv_0))`` -> ``conv_0``; a component that is a whole
    ``jit(...)`` is marked by a leading ``jit:``."""
    out = []
    for raw in tf_op.split("/"):
        bare = _WRAPPER.sub("", raw).rstrip(")")
        if bare:
            out.append("jit:" + bare if raw.startswith("jit(") else bare)
    return out


def scope_of(tf_op: str) -> tuple:
    """``(scope, is_backward)`` of one op's ``tf_op``. See the module text."""
    if not tf_op:
        return OTHER, False
    backward = "transpose(" in tf_op
    parts = _components(tf_op)
    chain = []
    i = 0
    while i < len(parts):
        part = parts[i]
        if part in VOCABULARY:
            name = part
            if part == "segment" and i + 1 < len(parts) - 1:
                name, i = f"segment/{parts[i + 1]}", i + 1
            if not chain or chain[-1] != name:
                chain.append(name)
        i += 1
    if chain:
        return "/".join(chain), backward
    # no vocabulary name: the Flax modules on the path, without the
    # primitive at its end, method names (``Model.encode``), wrappers that
    # say how it was traced, and instance numbers (``conv_3`` -> ``conv``)
    modules = [
        _INSTANCE.sub("", p) for p in parts[:-1]
        if not p.startswith("jit:") and "." not in p and p not in _STRUCTURE
        and not p[:1].isupper()
    ]
    return ("/".join(modules[:2]) if modules else OTHER), backward


# -- the reduction ---------------------------------------------------------


def program_of(module_event_name: str) -> str:
    """``jit_train_step(5430423662477605327)`` -> ``jit_train_step``."""
    return module_event_name.split("(", 1)[0]


def reduce(planes: dict) -> dict:
    """The table and its totals, or None without a device plane with ops.

    ``programs``: ``{program: {scope: {"fwd": s, "bwd": s}}}``;
    ``device_s``: ``{program: s}``, the ops' self time inside the program's
    module events; ``with_tf_op_s``: the part of it that carries a path;
    ``modules``: ``[(program, start_ns, end_ns)]`` in time order;
    ``vocabulary_events``: how many op events carry a vocabulary name;
    ``by_op``: ``{trace.short_name: {(program, scope, "fwd"|"bwd"): s}}``,
    which splits a row of ``trace.reduce``'s ``device_ops`` by scope."""
    devices = {
        name: lines for name, lines in planes.items()
        if name.startswith(trace_mod.DEVICE_PREFIX)
        and lines.get(trace_mod.OPS_LINE)
    }
    if not devices:
        return None
    programs, device_s, with_tf_op_s, modules, by_op = {}, {}, {}, [], {}
    vocabulary_events = 0
    short = {}  # event name -> trace.short_name: names repeat every step
    n_dev = len(devices)
    for lines in devices.values():
        ops = lines[trace_mod.OPS_LINE]
        mods = sorted(
            (a, b, program_of(name))
            for name, a, b, *_ in lines.get(MODULES_LINE, [])
        )
        modules += [(p, a, b) for a, b, p in mods]
        starts = [m[0] for m in mods]
        # self time by event: trace.self_times folds by name, so each event
        # goes in under its own index
        self_ns = trace_mod.self_times(
            [(i, ev[1], ev[2]) for i, ev in enumerate(ops)]
        )
        labels = {}  # tf_op -> (scope, backward): paths repeat every step
        for i, ev in enumerate(ops):
            tf_op = ev[3] if len(ev) > 3 else ""
            seconds = self_ns.get(i, 0.0) / 1e9 / n_dev
            k = bisect.bisect_right(starts, ev[1]) - 1
            program = (
                mods[k][2] if k >= 0 and ev[1] < mods[k][1] else "no program"
            )
            if tf_op not in labels:
                labels[tf_op] = scope_of(tf_op)
            scope, backward = labels[tf_op]
            if scope.split("/", 1)[0] in VOCABULARY:
                vocabulary_events += 1
            row = programs.setdefault(program, {}).setdefault(
                scope, {"fwd": 0.0, "bwd": 0.0}
            )
            direction = "bwd" if backward else "fwd"
            row[direction] += seconds
            device_s[program] = device_s.get(program, 0.0) + seconds
            if ev[0] not in short:
                short[ev[0]] = trace_mod.short_name(ev[0])
            split = by_op.setdefault(short[ev[0]], {})
            key = (program, scope, direction)
            split[key] = split.get(key, 0.0) + seconds
            if tf_op:
                with_tf_op_s[program] = with_tf_op_s.get(program, 0.0) + seconds
    return {
        "programs": programs,
        "device_s": device_s,
        "with_tf_op_s": with_tf_op_s,
        "modules": sorted(modules, key=lambda m: m[1]),
        "vocabulary_events": vocabulary_events,
        "by_op": by_op,
    }


def train_table(reduced: dict) -> tuple:
    """``({scope: {"fwd", "bwd"}}, total seconds)`` of the train programs
    together, or ``(None, reason)`` where the attribution is partial."""
    total = sum(reduced["device_s"].get(p, 0.0) for p in TRAIN_PROGRAMS)
    if total <= 0:
        return None, (
            f"no device time under {TRAIN_PROGRAMS}: the trace's programs "
            f"are {sorted(reduced['device_s'])}"
        )
    named = sum(reduced["with_tf_op_s"].get(p, 0.0) for p in TRAIN_PROGRAMS)
    if named < MIN_ATTRIBUTED * total:
        return None, (
            f"only {100 * named / total:.1f}% of the train programs' device "
            "time carries a tf_op: a trace without metadata"
        )
    if not reduced["vocabulary_events"]:
        return None, (
            "no op carries a vocabulary name: a program without scopes, or "
            "executables from a compile cache that predates them"
        )
    table = {}
    for program in TRAIN_PROGRAMS:
        for scope, row in reduced["programs"].get(program, {}).items():
            into = table.setdefault(scope, {"fwd": 0.0, "bwd": 0.0})
            into["fwd"] += row["fwd"]
            into["bwd"] += row["bwd"]
    return table, total


def under(table: dict, scope: str) -> float:
    """Seconds, forward and backward, of ``scope`` and what nests in it."""
    return sum(
        row["fwd"] + row["bwd"] for name, row in table.items()
        if name == scope or name.startswith(scope + "/")
    )


def rows(table: dict, total: float, top: int = 20) -> list:
    """The table as lines: scope, forward s, backward s, share of total."""
    ranked = sorted(table.items(), key=lambda kv: -(kv[1]["fwd"] + kv[1]["bwd"]))
    out = [f"{'scope':44s} {'fwd s':>9s} {'bwd s':>9s} {'share %':>8s}"]
    for scope, row in ranked[:top]:
        out.append(
            f"{scope[:44]:44s} {row['fwd']:9.4f} {row['bwd']:9.4f} "
            f"{100 * (row['fwd'] + row['bwd']) / total:8.2f}"
        )
    rest = sum(r["fwd"] + r["bwd"] for _, r in ranked[top:])
    if rest:
        out.append(f"{'(the rest)':44s} {'':9s} {'':9s} {100 * rest / total:8.2f}")
    return out


def of_run(run) -> dict:
    """The reduction of a run's trace, made once and kept in ``run.facts``:
    ``{"table", "total_s", "reduced"}``, or None (no trace, no device plane,
    a partial attribution; the reason is logged once)."""
    facts = run.facts
    if "_scopes" in facts:
        return facts["_scopes"]
    from benchmarks import harness

    facts["_scopes"] = None
    tdir = facts.get("trace_dir")
    path = trace_mod.find_xplane(tdir) if tdir else None
    if not path:
        return None
    reduced = reduce(decode(path))
    if reduced is None:
        harness.log("scopes: no device plane with ops in the trace")
        return None
    table, total = train_table(reduced)
    if table is None:
        harness.log(f"scopes: nothing read: {total}")
        return None
    named = sum(reduced["with_tf_op_s"].get(p, 0.0) for p in TRAIN_PROGRAMS)
    harness.log(
        f"scopes: train programs {total:.3f}s on the device, "
        f"{100 * named / total:.1f}% with a tf_op; all programs "
        f"{ {p: round(s, 3) for p, s in sorted(reduced['device_s'].items())} }"
    )
    for line in rows(table, total):
        harness.log("scopes: " + line)
    facts["_scopes"] = {"table": table, "total_s": total, "reduced": reduced}
    return facts["_scopes"]


def traced_train_rows(run) -> list:
    """The program's StepClock rows of the traced epoch's train dispatches,
    in order: the epoch is the one the program's ``profile`` row names."""
    import json

    path = run.facts.get("telemetry_path")
    if not path or not os.path.isfile(path):
        return []
    if "_traced_epoch" not in run.facts:
        run.facts["_traced_epoch"] = None
        with open(path) as fh:
            for line in fh:
                if '"profile"' in line and '"start"' in line:
                    run.facts["_traced_epoch"] = json.loads(line).get("epoch")
                    break
    epoch = run.facts["_traced_epoch"]
    return [
        r for r in run.driver.step_rows(run.facts, "train")
        if r["epoch"] == epoch and "nodes_pad" in r
    ]


if __name__ == "__main__":
    import json

    target = sys.argv[1]
    found = target if target.endswith(".pb") else trace_mod.find_xplane(target)
    result = reduce(decode(found))
    if result is None:
        sys.exit("no device plane with ops")
    for prog, seconds in sorted(result["device_s"].items(), key=lambda kv: -kv[1]):
        print(f"== {prog}: {seconds:.6f}s on the device, "
              f"{100 * result['with_tf_op_s'].get(prog, 0.0) / seconds:.1f}% "
              "with a tf_op")
        for line in rows(result["programs"][prog], seconds):
            print(line)
    print("== the largest device ops (trace.py's rows) by program, scope, direction")
    ranked = sorted(result["by_op"].items(), key=lambda kv: -sum(kv[1].values()))
    for op, split in ranked[:int(sys.argv[2]) if len(sys.argv) > 2 else 6]:
        print(f"{sum(split.values()):10.4f}s {op}")
        for (prog, scope, direction), seconds in sorted(
            split.items(), key=lambda kv: -kv[1]
        ):
            print(f"{seconds:14.4f}s {prog} | {scope} | {direction}")
    table, total = train_table(result)
    print(json.dumps({"train_total_s": total if table else None,
                      "why_not": None if table else total}))
