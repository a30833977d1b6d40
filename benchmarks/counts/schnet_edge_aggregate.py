"""The least traffic and operations of one ``edge_aggregate`` block of
SchNet and of its transpose, from shapes alone, whatever implements it.

The block (the program's scope ``edge_aggregate``, models/schnet.py) is

    out[N, F_out] = segment_sum(h[senders] * filt, receivers) @ w

with ``h [N, F]`` the ``lin1`` output, ``filt [E, F]`` the filter values and
``w [F, F_out]`` the ``lin2`` weight. The least an implementation can do, in
float32 (4 bytes) with int32 indices:

* forward: read ``h`` once, ``filt`` once, the sender and the receiver index
  of every edge once, ``w``; write ``out`` once.
  bytes ``4 (N F + E F + N F_out + F F_out) + 8 E``;
  operations ``2 E F`` (product and sum) ``+ 2 N F F_out`` (the matmul).
* transpose: read the cotangent ``g [N, F_out]``, ``h``, ``filt``, both
  indices, ``w``; write the cotangents of ``h``, of ``filt`` and of ``w``.
  bytes ``4 (N F_out + N F + E F + F F_out) + 8 E + 4 (N F + E F + F F_out)``;
  operations ``4 N F F_out`` (``g w^T`` and ``agg^T g``) ``+ 5 E F`` (the
  aggregate is not read back, so it is made again: ``2 E F``; the cotangent
  of ``h``: ``2 E F``; of ``filt``: ``E F``).
  Where ``h`` carries no gradient its cotangent is not written
  (``h_grad=False``). SchNet's ``h`` is ``lin1(x)`` in every layer, and
  ``lin1``'s weight takes its gradient through it, so every layer writes it.

The shapes are the padded ones the step is given (``nodes_pad``,
``edges_pad`` of the StepClock rows): this is the roofline of the call the
kernel gets; ``pad_ratio.train`` carries what the padding costs.

By hand, N=8, E=24, F=4, F_out=6 (benchmarks/tests/test_scopes.py):
forward 4 (32 + 96 + 48 + 24) + 192 = 992 bytes, 192 + 384 = 576
operations; transpose 4 (48 + 32 + 96 + 24) + 192 + 4 (32 + 96 + 24) = 1600
bytes, 768 + 480 = 1248 operations; without the cotangent of ``h`` 1472.
"""

from __future__ import annotations


def forward(n: int, e: int, f: int, f_out: int) -> dict:
    return {
        "bytes": 4 * (n * f + e * f + n * f_out + f * f_out) + 8 * e,
        "flops": 2 * e * f + 2 * n * f * f_out,
    }


def transpose(n: int, e: int, f: int, f_out: int, h_grad: bool = True) -> dict:
    read = 4 * (n * f_out + n * f + e * f + f * f_out) + 8 * e
    written = 4 * (e * f + f * f_out + (n * f if h_grad else 0))
    return {
        "bytes": read + written,
        "flops": 4 * n * f * f_out + 5 * e * f,
    }


def least_seconds(counts: dict, peaks: dict) -> tuple:
    """``(seconds, which)``: the larger of bytes over the memory's peak and
    operations over the chip's peak, and which of the two it is."""
    memory = counts["bytes"] / peaks["hbm_bytes_per_s"]
    compute = counts["flops"] / peaks["flops_per_s"]
    return (memory, "memory") if memory >= compute else (compute, "compute")


def step_least_seconds(arch: dict, n: int, e: int, peaks: dict) -> tuple:
    """One train step's blocks, forward and transpose, over all layers:
    ``(seconds, which binds the most time)``."""
    f, f_out = int(arch["num_filters"]), int(arch["hidden_dim"])
    total, by = 0.0, {"memory": 0.0, "compute": 0.0}
    for counts in (forward(n, e, f, f_out), transpose(n, e, f, f_out)):
        seconds, which = least_seconds(counts, peaks)
        total += seconds
        by[which] += seconds
    layers = int(arch["num_conv_layers"])
    return layers * total, max(by, key=by.get)
