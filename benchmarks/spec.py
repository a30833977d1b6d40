"""Where the harness finds things: every cell, configuration, traffic mix,
driver, generator, metric, counts function and reference is a file of its
own, found by the name ``BENCHMARK.json`` (or the file that names it)
gives. Nothing here knows a cell's, a configuration's or a metric's name.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


def read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def benchmark() -> dict:
    return read_json(os.path.join(REPO, "BENCHMARK.json"))


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module. Names may hold dots
    (``pad_ratio.train``), so the file is loaded by path."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: {path}")
    mod_name = "benchmarks_" + kind + "_" + name.replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def _one(entries: list, name: str, what: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise KeyError(
            f"{what} {name!r}: {len(found)} entries in BENCHMARK.json "
            f"(have {[e['name'] for e in entries]})"
        )
    return found[0]


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict
        ) else v
    return out


def metrics_for(bench: dict, cell: str, section: str) -> list:
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell
    reports: those that list it under ``workloads``, or list none."""
    return [
        m for m in bench[section]
        if "workloads" not in m or cell in m["workloads"]
    ]


def cell(name: str, rehearse: bool = False) -> dict:
    """Everything one run of one cell needs, as data.

    ``workload``: the entry of BENCHMARK.json; ``config``: the
    configuration's file; ``traffic``: ``traffic/<traffic>.json``;
    ``extras``: ``workloads/<cell>.json`` (who, memory, limits,
    rehearsal). With ``rehearse`` the cell's own ``rehearsal`` block is
    merged over configuration and traffic, and its ``limits`` take the
    place of the cell's: a tiny copy for the CPU.
    """
    bench = benchmark()
    workload = _one(bench["workloads"], name, "workload")
    cfg_entry = _one(bench["configs"], workload["config"], "config")
    config = read_json(os.path.join(REPO, cfg_entry["file"]))
    traffic = read_json(
        os.path.join(HERE, "traffic", workload["traffic"] + ".json")
    )
    extras = read_json(os.path.join(HERE, "workloads", name + ".json"))
    if rehearse:
        small = extras.get("rehearsal", {})
        config = _merge(config, small.get("config", {}))
        traffic = _merge(traffic, small.get("traffic", {}))
        # a CPU computes float32 in float32, the chip's default matmul
        # rounds operands to bfloat16: the rehearsal has limits of its own
        extras = dict(extras, limits=small.get("limits", extras["limits"]))
    return {
        "name": name,
        "bench": bench,
        "workload": workload,
        "config": config,
        "traffic": traffic,
        "extras": extras,
        "chips": int(workload["chips"]),
    }


def peaks(device_kind: str) -> dict:
    """Peaks of one chip by ``device_kind``. An unknown kind is an error,
    never a default."""
    table = read_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["kinds"]:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.json "
            f"(have {sorted(table['kinds'])}): add it with its source"
        )
    return table["kinds"][device_kind]


def architecture(config: dict) -> dict:
    """The configuration's ``Architecture`` block with ``input_dim``, the
    number of input node features, beside it: what the counts function and
    the plain reference are given."""
    net = config["hydragnn"]["NeuralNetwork"]
    arch = dict(net["Architecture"])
    arch["input_dim"] = len(net["Variables_of_interest"]["input_node_features"])
    return arch
