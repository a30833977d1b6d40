"""Padded node+edge slots over real ones across the window's train steps,
from the program's StepClock rows (their ``spec`` sizes and plan sizes)."""


def compute(run):
    rows = [r for r in run.driver.step_rows(run.facts, "train") if "nodes" in r]
    if not rows:
        return None
    padded = sum(r["k"] * (r["nodes_pad"] + r["edges_pad"]) for r in rows)
    real = sum(r["nodes"] + r["edges"] for r in rows)
    return padded / real if real else None
