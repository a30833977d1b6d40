"""``correct`` has to come out false when it should: the control and the
faults, at the cell's rehearsal size on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

Not part of the repository's tier-1 tests (those are ``tests/``). Each case
skips the harness's look for a chip and drives the rest of a run through
``harness.execute`` with the cell's own limits:

* sound: the program as the configuration states it -> correct;
* control: the program's own lower-precision path switched on
  (``Training.precision: bf16``, the nearest precision below the fp32 the
  configurations state) -> not correct;
* stale_state: the train step returns its state unchanged -> not correct;
* half_batch: half of every batch masked out under the program's step, the
  mean taken over the rest -> not correct.
"""

from __future__ import annotations

import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import harness, spec  # noqa: E402

train_check = spec.load_module("checks", "train")
CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def stale_state(state_in, state_out):
    """A step that returns its state unchanged."""
    return state_in


def half_batch(batch):
    """Mask out the second half of the real graphs of every step, with
    their atoms and edges: the program's loss then averages over the rest."""
    import jax.numpy as jnp

    gmask = batch.graph_mask
    n_real = jnp.sum(gmask, axis=-1, keepdims=True)
    slot = jnp.arange(gmask.shape[-1])
    keep_g = gmask & (slot < (n_real + 1) // 2)
    keep_n = batch.node_mask & jnp.take_along_axis(
        keep_g, batch.node_graph_idx, axis=-1
    )
    keep_e = batch.edge_mask & jnp.take_along_axis(
        keep_n, batch.receivers, axis=-1
    )
    return batch.replace(graph_mask=keep_g, node_mask=keep_n, edge_mask=keep_e)


CASES = {
    "sound": ({}, True),
    "control": (train_check.CONTROL, False),
    "stale_state": ({"tamper": stale_state}, False),
    "half_batch": ({"tamper_batch": half_batch}, False),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("name", CELLS)
def test_correct_decides(name, case, capsys):
    driver_kw, want = CASES[case]
    cell = spec.cell(name, rehearse=True)
    result = harness.execute(
        cell, seed=20260930, seconds=1.0, trace=False, device=dict(DEVICE),
        t_start=time.perf_counter(), **driver_kw,
    )
    with capsys.disabled():
        print(f"\n{name} {case}: correct={result['correct']} "
              f"{result['compared']}")
    assert result["correct"] is want, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
