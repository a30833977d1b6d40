"""The verdict: every number a cell's checker compared, against the limit
the cell's workload file gives it. What is compared, and with which plain
reference, is the business of ``benchmarks/checks/<driver>.py``."""

from __future__ import annotations

import math


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, [[name, value, limit], ...]). A number whose limit is null
    is shown and not compared; a number that is not finite, or that has a
    limit and was not read, fails."""
    rows, ok = [], True
    for name, value in numbers.items():
        limit = limits.get(name)
        rows.append([name, value, limit])
        if limit is not None and not (math.isfinite(value) and value <= limit):
            ok = False
    for name, limit in limits.items():
        if limit is not None and name not in numbers:
            rows.append([name, None, limit])
            ok = False
    if not any(limit is not None for _, _, limit in rows):
        ok = False  # nothing compared is nothing proved
    return ok, rows
