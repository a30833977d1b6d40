"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but
``jax.profiler.ProfileData``.

* device planes are those whose name starts with ``/device:TPU:``;
* a device is busy while an event of its ``XLA Ops`` line runs; busy time
  is the union of those intervals, so nested or overlapping events are not
  counted twice;
* an op's time is its self time: an event that encloses others on the line
  (a ``while`` around its body's ops, as the K-step scan is) is charged only
  what its children do not cover. Ops are named ``<opcode> <result shape>
  <- <operand shapes>`` from the HLO text the trace gives, without layouts
  and instruction numbers, so one row holds an op of every layer;
* the traced window is the span from the first to the last device op or
  step annotation of the loop (``<region>_step`` on the host thread that
  carries them): the profiler's own start and stop are outside it;
* an idle gap is an interval of the window in which no op ran, named by the
  innermost host event of the annotated thread that covers its midpoint;
  gaps under 2 us are the device's own hand-over from one op to the next
  and are summed under one name without a look at the host.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "/host:CPU"
STEP_SUFFIX = "_step"
SHORT_GAP_NS = 2000.0
BETWEEN_OPS = "between two ops, under 2 us each"


def find_xplane(trace_dir: str):
    files = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )
    return files[-1] if files else None


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line):
    return [
        (ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
        for ev in line.events
    ]


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


_LAYOUT = re.compile(r"\{[^{}]*\}")


_SHAPE = re.compile(r"\b[a-z]+[0-9]*\[[0-9,]*\]")


def short_name(text: str) -> str:
    """What an op is, without its instruction number, so that the same op
    of every layer and every compiled program folds into one row:
    ``%fusion.9 = f32[8,128]{1,0:T(8,128)} fusion(s32[64]{0} %a, f32[64,128]
    %b), kind=...`` -> ``fusion f32[8,128] <- s32[64], f32[64,128]`` (the
    opcode, the result's shape, the first operands' shapes). A name that is
    no HLO text stays."""
    if " = " not in text:
        return text[:160]
    _, rest = text.split(" = ", 1)
    while _LAYOUT.search(rest):
        rest = _LAYOUT.sub("", rest)
    shape = ""
    if rest.startswith("("):  # a tuple-shaped result: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                shape, rest = "tuple", rest[i + 1:].lstrip()
                break
    head, _, args = rest.partition("(")
    if not shape:
        shape, _, head = head.strip().rpartition(" ")
    operands = _SHAPE.findall(args.split("), ")[0])[:3]
    name = f"{head.strip()} {shape}".strip()
    if operands:
        name += " <- " + ", ".join(operands)
    return name[:160]


def self_times(events) -> dict:
    """{name: seconds of self time} of one line's events (ns in)."""
    totals = {}
    stack = []  # [name, end, covered-by-children]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, start, covered = stack.pop()
            totals[name] = totals.get(name, 0.0) + (end - start - covered)
            if stack:
                stack[-1][3] += end - start

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        close(a)
        if stack and b > stack[-1][1]:
            b = stack[-1][1]  # overlap without nesting: clip to the parent
        stack.append([name, b, a, 0.0])
    close(float("inf"))
    return totals


def planes_of(data):
    """{plane name: {line name: [(name, start_ns, end_ns), ...]}}; lines of
    one name within a plane are joined."""
    out = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(_events(line))
    return out


def _annotated_thread(host_lines: dict):
    """The host thread that carries the loop's step annotations."""
    best, count = None, 0
    for name, events in host_lines.items():
        n = sum(1 for ev in events if ev[0].endswith(STEP_SUFFIX))
        if n > count:
            best, count = name, n
    return best


def reduce(planes: dict, top: int = 10) -> dict:
    """The numbers, or None where there is no device plane with ops."""
    devices = {
        name: lines for name, lines in planes.items()
        if name.startswith(DEVICE_PREFIX) and lines.get(OPS_LINE)
    }
    if not devices:
        return None
    host = {}
    for name, lines in planes.items():
        if name.startswith(HOST_PREFIX):
            host.update(lines)
    thread = _annotated_thread(host)
    host_events = sorted(host.get(thread, []), key=lambda e: (e[1], -e[2]))
    edges = [
        t for lines in devices.values() for _, a, b in lines[OPS_LINE]
        for t in (a, b)
    ] + [
        t for name, a, b in host_events if name.endswith(STEP_SUFFIX)
        for t in (a, b)
    ]
    start, end = min(edges), max(edges)
    busy, totals, gaps = [], {}, []
    for lines in devices.values():
        ops = lines[OPS_LINE]
        merged = union((a, b) for _, a, b in ops)
        busy.append(sum(b - a for a, b in merged))
        for name, ns in self_times(ops).items():
            name = short_name(name)
            totals[name] = totals.get(name, 0.0) + ns
        bounds = [[start, start]] + merged + [[end, end]]
        gaps += [
            (b0, a1) for (_, b0), (a1, _) in zip(bounds[:-1], bounds[1:])
            if a1 > b0
        ]
    n_dev = len(devices)
    by_host = {}
    starts = [ev[1] for ev in host_events]
    for a, b in gaps:
        if b - a < SHORT_GAP_NS:
            label = BETWEEN_OPS  # the device's own hand-over, not the host
        else:
            mid = 0.5 * (a + b)
            # host_events are sorted by start: only those that start before
            # the midpoint can cover it; the innermost is the shortest
            cover = [
                ev for ev in host_events[:bisect.bisect_right(starts, mid)]
                if ev[2] >= mid
            ]
            label = (
                min(cover, key=lambda ev: ev[2] - ev[1])[0]
                if cover else "no host span on the loop's thread"
            )
        by_host[label] = by_host.get(label, 0.0) + (b - a)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "devices": n_dev,
        "busy_s": sum(busy) / n_dev / 1e9,
        "window_s": (end - start) / 1e9,
        "device_ops": [[k, v / n_dev / 1e9] for k, v in rank(totals)],
        "idle_gaps": [[k, v / n_dev / 1e9] for k, v in rank(by_host)],
        "annotated_thread": thread,
    }


def describe(planes: dict, sample: int = 5) -> list:
    """A hand-readable listing: planes, lines, event counts, first names."""
    rows = []
    for pname, lines in planes.items():
        for lname, events in lines.items():
            names = []
            for ev in events:
                if ev[0] not in names:
                    names.append(ev[0])
                if len(names) >= sample:
                    break
            rows.append(f"{pname} | {lname} | {len(events)} events | {names}")
    return rows


if __name__ == "__main__":
    # python3 benchmarks/trace.py <trace dir or .xplane.pb>: the hand look
    import json
    import sys

    target = sys.argv[1]
    path = target if target.endswith(".pb") else find_xplane(target)
    planes = planes_of(load(path))
    for row in describe(planes):
        print(row)
    print(json.dumps(reduce(planes), indent=1))
