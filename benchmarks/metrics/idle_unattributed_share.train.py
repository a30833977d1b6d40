"""Of the traced epoch's idle time that benchmarks/trace.py lists by host
span, the share that falls where the loop's thread carries no span at all
(its bucket ``no host span on the loop's thread``): what the program's
``tr.region`` sites (train/loop.py) leave unnamed."""

UNNAMED = "no host span on the loop's thread"


def compute(run):
    t = run.trace()
    if t is None or not t["idle_gaps"]:
        return None
    gaps = dict(map(tuple, t["idle_gaps"]))
    listed = sum(gaps.values())
    return 100.0 * gaps.get(UNNAMED, 0.0) / listed if listed > 0 else None
