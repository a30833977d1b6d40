"""Share of the train loop's host wall time spent waiting for the next
batch: sum of ``input_wait_ms`` over sum of ``wall_ms`` of the window's
train-region StepClock rows (host clock, the program's spans)."""


def compute(run):
    rows = run.driver.step_rows(run.facts, "train")
    wall = sum(r["wall_ms"] for r in rows)
    if not rows or wall <= 0:
        return None
    return 100.0 * sum(r["input_wait_ms"] for r in rows) / wall
