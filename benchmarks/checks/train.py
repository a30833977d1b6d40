"""What decides ``correct`` for a cell of driver ``train``.

Two dispatches of the timed path's own jitted programs, taken in epoch 0
from the program's own loader (drivers/train.py), are compared with the
plain reference following the same steps from the same starting state on
the same graphs:

* ``scan``: the last dispatch of the program's K-step scan before the first
  single step (K is what ``superstep: auto`` chose; with 71 steps an epoch
  and K=8 it starts at Adam step 56): K optimizer steps in one call, from a
  warm state. The scan that starts from the fresh parameters is not
  compared: Adam's first updates are lr x sign(g), which turns round-off in
  an all but zero gradient into a whole update (PERF.md section 2).
* ``step``: the first single-step dispatch, from whatever state the scans
  before it left. Its state after the one step gives the gradient as the
  optimizer got it.

Parameters, Adam's moments and step count are handed to the reference as
they stood before each dispatch. The graphs are identified in the program's
batch by the position of their first atom and then taken from the
generator's records, so a fault in batch forming (a graph dropped, an edge
lost, a mask wrong) is not inherited by the reference.

Numbers compared, each ``<kind>.<name>`` against a limit from the cell's
workload file:

* ``loss``: the graph-weighted mean loss over the dispatch's steps, total
  and each task, as the program returns it; worst relative gap.
* ``grad``: the gradient the optimizer got, worked out from Adam's first
  moment before and after the dispatch, ``(mu' - b1^K mu) / (1 - b1)``: the
  gradient itself for a single step, the sum of the K gradients weighted
  b1^(K-1-i) for a scan. By the worst leaf: the gap between the program's
  norm and the reference's over the reference's norm of that leaf or of the
  median leaf, whichever is larger. ``grad_median`` is the median leaf's
  gap and ``grad_norm`` the gap of the whole gradient's norm, all leaves
  together, which is steady from seed to seed where the worst leaf swings
  (a bias whose gradient is a signed sum over half a million edges).
* ``update``: the parameters' change over the dispatch, by the worst leaf,
  the same measure; ``update_median``: its median leaf. Leaves whose
  reference gradient is under a thousandth of the median leaf's are left
  out of both (they move by round-off alone).

``compared(cell, facts)`` is what the harness calls; ``fault_numbers`` puts
the reference with a fault planted in the program's place (readings.py).
"""

from __future__ import annotations

import numpy as np

from benchmarks import spec


def _leaves(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], f"{prefix}/{k}" if prefix else k)
    else:
        yield prefix, np.asarray(tree, np.float64)


def norm_gaps(program, reference, keep=None):
    """{leaf: |‖p‖ - ‖r‖| / max(‖r‖, median ‖r‖)} over the kept leaves."""
    ref = {k: float(np.linalg.norm(v)) for k, v in _leaves(reference)}
    prog = {k: float(np.linalg.norm(v)) for k, v in _leaves(program)}
    if set(ref) != set(prog):
        raise ValueError(
            f"leaves differ: {sorted(set(ref) ^ set(prog))}"
        )
    median = float(np.median(list(ref.values())))
    return {
        k: abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30)
        for k in ref if keep is None or k in keep
    }


def whole_norm_gap(program, reference) -> float:
    """The gap between the norms of the whole trees, all leaves together,
    over the reference's."""
    ref = np.sqrt(sum(float(np.sum(v * v)) for _, v in _leaves(reference)))
    prog = np.sqrt(sum(float(np.sum(v * v)) for _, v in _leaves(program)))
    return float(abs(prog - ref) / max(ref, 1e-30))


def _diff(a, b):
    if isinstance(a, dict):
        return {k: _diff(a[k], b[k]) for k in a}
    return np.asarray(a, np.float64) - np.asarray(b, np.float64)


def identify(capture: dict, records: list) -> list:
    """The generator's records behind each step of the captured dispatch,
    found by the position of each graph's first atom."""
    by_first_atom = {}
    for r in records:
        key = r["pos"][0].astype(np.float32).tobytes()
        if key in by_first_atom:
            raise ValueError("two structures share a first atom position")
        by_first_atom[key] = r
    steps = []
    for k in range(capture["k"]):
        mask = capture["node_mask"][k].astype(bool)
        graph_of = capture["node_graph"][k]
        pos = capture["pos"][k]
        real = np.nonzero(capture["graph_mask"][k])[0]
        nodes = np.nonzero(mask)[0]
        first = {}
        for i in nodes[::-1]:
            first[int(graph_of[i])] = i
        chosen = []
        for g in real:
            rec = by_first_atom.get(pos[first[int(g)]].tobytes())
            if rec is None:
                raise ValueError(
                    f"step {k} graph {g}: no generated structure starts at "
                    f"{pos[first[int(g)]]}"
                )
            n_here = int(np.sum(mask & (graph_of == g)))
            if n_here != len(rec["z"]):
                raise ValueError(
                    f"step {k} graph {g}: {n_here} atoms in the batch, "
                    f"{len(rec['z'])} generated"
                )
            chosen.append(rec)
        steps.append(chosen)
    return steps


def reference_numbers(capture, records, reference, arch, heads, opt,
                      dtype=None, tamper_records=None, precision=None):
    """Run the reference over the captured dispatch. Returns its result."""
    steps = identify(capture, records)
    if tamper_records is not None:
        steps = [tamper_records(s) for s in steps]
    forces = any(h["type"] == "node" for h in heads)
    batches = [reference.collate(s, capture["padded"], forces) for s in steps]
    kw = {} if dtype is None else {"dtype": dtype}
    if precision is not None:
        kw["precision"] = precision
    return reference.follow(
        capture["params_in"], batches, arch, heads, opt,
        mu=capture["mu_in"], nu=capture["nu_in"], t0=capture["count_in"],
        **kw
    )


def compare_references(sound: dict, faulty: dict, capture: dict, opt: dict) -> dict:
    """The numbers for a reference put in the program's place: ``faulty``
    stands where the program's capture would, ``sound`` is the reference."""
    g = faulty["graphs"]
    stand_in = {
        "k": capture["k"],
        "params_in": capture["params_in"],
        "mu_in": capture["mu_in"],
        "params_out": faulty["params"],
        "mu": faulty["mu"],
        "acc": [
            np.sum(faulty["loss"] * g),
            np.sum(faulty["tasks"] * g[:, None], axis=0),
            np.sum(g),
        ],
    }
    return compare(stand_in, sound, opt)["numbers"]


def _dispatch_grad(mu_out, mu_in, b1: float, k: int):
    """The gradients of a K-step dispatch as the optimizer got them, from
    Adam's first moment: sum_i b1^(K-1-i) g_i (for K=1 the gradient)."""

    def walk(after, before):
        if isinstance(after, dict):
            return {key: walk(after[key], before[key]) for key in after}
        after = np.asarray(after, np.float64)
        return (after - b1 ** k * np.asarray(before, np.float64)) / (1.0 - b1)

    return walk(mu_out, mu_in)


def compare(capture: dict, ref: dict, opt: dict, leaves: bool = False) -> dict:
    """The numbers compared, program against reference."""
    g = ref["graphs"]
    ref_total = float(np.sum(ref["loss"] * g) / np.sum(g))
    ref_tasks = np.sum(ref["tasks"] * g[:, None], axis=0) / np.sum(g)
    loss_sum, tasks_sum, n_graphs = capture["acc"]
    prog_total = float(loss_sum / n_graphs)
    prog_tasks = np.asarray(tasks_sum, np.float64).reshape(-1) / n_graphs
    loss_gaps = [abs(prog_total - ref_total) / abs(ref_total)] + [
        abs(p - r) / abs(r) for p, r in zip(prog_tasks, ref_tasks)
    ]
    grad_ref = dict(_leaves(ref["grad_norm_sum"]))
    median = float(np.median([float(v) for v in grad_ref.values()]))
    moved = {k for k, v in grad_ref.items() if float(v) >= 1e-3 * median}
    k, b1 = capture["k"], float(opt["b1"])
    grad_trees = (
        _dispatch_grad(capture["mu"], capture["mu_in"], b1, k),
        # a single step: the reference's gradient itself
        ref["grad_first"] if k == 1
        else _dispatch_grad(ref["mu"], capture["mu_in"], b1, k),
    )
    grad = norm_gaps(*grad_trees)
    update = norm_gaps(
        _diff(capture["params_out"], capture["params_in"]),
        _diff(ref["params"], capture["params_in"]),
        keep=moved,
    )
    worst_grad = max(grad, key=grad.get)
    worst_update = max(update, key=update.get)
    return {
        "numbers": {
            "loss": float(max(loss_gaps)),
            "grad": float(grad[worst_grad]),
            "grad_median": float(np.median(list(grad.values()))),
            "grad_norm": whole_norm_gap(*grad_trees),
            "update": float(update[worst_update]),
            "update_median": float(np.median(list(update.values()))),
        },
        "detail": {
            "k": capture["k"],
            "graphs": float(n_graphs),
            "graphs_reference": float(np.sum(g)),
            "loss_program": prog_total,
            "loss_reference": ref_total,
            "tasks_program": [float(x) for x in prog_tasks],
            "tasks_reference": [float(x) for x in ref_tasks],
            "worst_grad_leaf": worst_grad,
            "worst_update_leaf": worst_update,
            "left_out": sorted(set(grad_ref) - moved),
            **({"grad_leaves": grad, "update_leaves": update,
                "grad_norm_sum": {k: float(v) for k, v in grad_ref.items()}}
               if leaves else {}),
        },
    }


def reference_result(cell, facts, capture, tamper_records=None, **kw):
    """The plain reference's result for one captured dispatch, at the
    matmul precision the configuration states."""
    config = cell["config"]
    kw.setdefault("precision", config.get("matmul_precision", "highest"))
    arch = spec.architecture(config)
    reference = spec.load_module("references", arch["mpnn_type"].lower())
    return reference_numbers(
        capture, facts["splits"]["train"], reference, arch,
        config["heads"], config["optimizer"], tamper_records=tamper_records,
        **kw,
    )


def compared(cell, facts, leaves=False, **kw):
    """({kind.name: number}, {kind: detail}): every captured dispatch
    against the reference."""
    numbers, detail = {}, {}
    for kind, capture in facts["captures"].items():
        result = compare(
            capture, reference_result(cell, facts, capture, **kw),
            cell["config"]["optimizer"], leaves=leaves,
        )
        numbers.update(
            {f"{kind}.{k}": v for k, v in result["numbers"].items()}
        )
        detail[kind] = result["detail"]
    return numbers, detail


def half_batch(records: list) -> list:
    """Half of a batch left out; the mean is then taken over the rest."""
    return records[: max(1, len(records) // 2)]


def reordered(records: list) -> list:
    """The same graphs in the opposite order: the same sums, rounded in
    another order. No fault: a look at how far round-off alone carries."""
    return records[::-1]


FAULTS = {"half_batch": half_batch}
LOOKS = {"reordered": reordered}

# the control: the program's own lower-precision path, the nearest below
# the float32 the configurations state, as keywords for the driver's run
CONTROL = {"training_overrides": {"precision": "bf16"}}


def fault_numbers(cell, facts, fault: str, **kw) -> dict:
    """The numbers of the reference with ``fault`` (or look) planted, put
    in the program's place and compared with the sound reference."""
    tamper = {**FAULTS, **LOOKS}[fault]
    numbers = {}
    for kind, capture in facts["captures"].items():
        sound = reference_result(cell, facts, capture, **kw)
        faulty = reference_result(cell, facts, capture, tamper, **kw)
        got = compare_references(
            sound, faulty, capture, cell["config"]["optimizer"]
        )
        numbers.update({f"{kind}.{k}": v for k, v in got.items()})
        steps = np.abs(faulty["loss"] - sound["loss"]) / np.abs(sound["loss"])
        numbers[f"{kind}.loss_by_step"] = [float(x) for x in steps]
    return numbers
