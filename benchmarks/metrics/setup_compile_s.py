"""Seconds of XLA compilation and of retrieval from the persistent compile
cache before the window, as the program's compile observer counted them:
the ``setup`` row with phase ``compile`` that it writes to the telemetry
stream when the warm-up ends (utils/telemetry.py). A program without that
row reads nothing."""

import json
import os


def compute(run):
    path = run.facts.get("telemetry_path")
    if not path or not os.path.isfile(path):
        return None
    with open(path) as fh:
        for line in fh:
            if '"setup"' not in line:
                continue
            try:
                row = json.loads(line)
            except ValueError:
                continue  # a truncated tail line
            if row.get("t") == "setup" and row.get("phase") == "compile":
                return row["ms"] / 1e3
    return None
