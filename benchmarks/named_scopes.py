"""Device self time of named program scopes in a run's trace.

``benchmarks/scopes.py`` classifies an op by the first name of its closed
vocabulary on the op's path, so a scope outside that vocabulary (such as
``vector_message`` and ``vector_update``, put by models/equivariant.py)
falls there under the scopes and Flax modules around it. This file
classifies the ops itself, for the names it is given: an op of a train
program (``scopes.TRAIN_PROGRAMS``) whose ``tf_op`` path holds one of the
names as a component is charged to the first of them it holds; forward and
transpose alike, self time by ``trace.self_times``' rule, a fusion charged
whole to its root's path, as scopes.py does. The denominator of a share is
``scopes.of_run(run)["total_s"]``, the train programs' device time.
"""

from __future__ import annotations

import bisect

from benchmarks import scopes
from benchmarks import trace as trace_mod


def seconds(planes: dict, names: tuple) -> dict:
    """``{name: s}`` over the train programs for each of ``names``."""
    out = dict.fromkeys(names, 0.0)
    devices = [
        lines for name, lines in planes.items()
        if name.startswith(trace_mod.DEVICE_PREFIX)
        and lines.get(trace_mod.OPS_LINE)
    ]
    for lines in devices:
        ops = lines[trace_mod.OPS_LINE]
        mods = sorted(
            (a, b, scopes.program_of(name))
            for name, a, b, *_ in lines.get(scopes.MODULES_LINE, [])
        )
        starts = [m[0] for m in mods]
        self_ns = trace_mod.self_times(
            [(i, ev[1], ev[2]) for i, ev in enumerate(ops)]
        )
        labels = {}
        for i, ev in enumerate(ops):
            k = bisect.bisect_right(starts, ev[1]) - 1
            if k < 0 or ev[1] >= mods[k][1] or mods[k][2] not in scopes.TRAIN_PROGRAMS:
                continue
            tf_op = ev[3] if len(ev) > 3 else ""
            if tf_op not in labels:
                parts = scopes._components(tf_op)
                labels[tf_op] = next((n for n in names if n in parts), None)
            name = labels[tf_op]
            if name is not None:
                out[name] += self_ns.get(i, 0.0) / 1e9 / len(devices)
    return out


def of_run(run, names: tuple):
    """``{name: s, ..., "total_s": s}`` of a run's trace, made once for
    ``names`` and kept in ``run.facts``; None where scopes.py reads nothing
    (no trace, a partial attribution) or no op carries any of the names (a
    program without the scopes)."""
    cache = run.facts.setdefault("_named_scopes", {})
    if names in cache:
        return cache[names]
    cache[names] = None
    s = scopes.of_run(run)
    if s is None:
        return None
    found = seconds(
        scopes.decode(trace_mod.find_xplane(run.facts["trace_dir"])), names
    )
    if not any(found.values()):
        from benchmarks import harness

        harness.log("named scopes: no op of the train programs carries "
                    f"{' or '.join(names)}")
        return None
    cache[names] = dict(found, total_s=s["total_s"])
    return cache[names]
