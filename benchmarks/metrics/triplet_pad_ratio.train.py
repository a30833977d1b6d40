"""Padded triplet slots over real triplets across the window's train steps,
from the program's StepClock rows (``triplets_pad`` a step, ``triplets``
summed over the dispatch's steps)."""


def compute(run):
    rows = [
        r for r in run.driver.step_rows(run.facts, "train") if "triplets" in r
    ]
    real = sum(r["triplets"] for r in rows)
    if not real:
        return None
    return sum(r["k"] * r["triplets_pad"] for r in rows) / real
