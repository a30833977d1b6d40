"""The least traffic and operations of one ``triplet`` block of DimeNet++
and of its transpose, from shapes alone, whatever implements it.

The block (the program's scope ``triplet``, models/dimenet.py) is

    out[E, I] = segment_sum(x[t_kj] * ((sbf @ w1) @ w2), t_ji)

with ``x [E, I]`` the down-projected messages, ``sbf [T, SR]`` the
spherical basis, ``w1 [SR, B]``, ``w2 [B, I]``, and ``t_kj``, ``t_ji`` [T]
edge indices. The least an implementation can do, in float32 (4 bytes) with
int32 indices:

* forward: read ``sbf`` once, ``x`` once, both indices, the weights; write
  ``out`` once. bytes ``4 (T SR + 2 E I + SR B + B I) + 8 T``; operations
  ``2 T (SR B + B I)`` (the projection) ``+ 2 T I`` (product and sum).
* transpose: read the cotangent ``g [E, I]``, ``x``, ``sbf``, both
  indices, the weights; write the cotangents of ``x``, ``w1`` and ``w2``
  (``sbf`` takes none: it is made from positions alone). bytes
  ``4 (3 E I + T SR + 2 (SR B + B I)) + 8 T``; operations ``2 T (SR B +
  B I)`` (the projection is not read back, so it is made again) ``+ 4 T I``
  (the cotangents of ``x`` and of the projection) ``+ 2 T (B I + SR B)``
  (of ``w2`` through the projection's cotangent, of ``w1``) ``+ 2 T I B``
  (the cotangent of ``sbf @ w1``).

The shapes are the padded ones the step is given (``triplets_pad``,
``edges_pad`` of the StepClock rows): the roofline of the call the block
gets; ``triplet_pad_ratio.train`` carries what the padding costs.

By hand, T=10, E=4, SR=3, B=2, I=5 (benchmarks/tests/test_triplet_readers.py):
forward 4 (30 + 40 + 6 + 10) + 80 = 424 bytes, 2*10*(6 + 10) + 2*10*5 =
420 operations; transpose 4 (60 + 30 + 32) + 80 = 568 bytes, 320 + 200 +
320 + 200 = 1040 operations.
"""

from __future__ import annotations


def forward(t: int, e: int, sr: int, b: int, i: int) -> dict:
    return {
        "bytes": 4 * (t * sr + 2 * e * i + sr * b + b * i) + 8 * t,
        "flops": 2 * t * (sr * b + b * i) + 2 * t * i,
    }


def transpose(t: int, e: int, sr: int, b: int, i: int) -> dict:
    return {
        "bytes": 4 * (3 * e * i + t * sr + 2 * (sr * b + b * i)) + 8 * t,
        "flops": 2 * t * (sr * b + b * i) + 4 * t * i
        + 2 * t * (b * i + sr * b) + 2 * t * i * b,
    }


def least_seconds(counts: dict, peaks: dict) -> tuple:
    """``(seconds, which)``: the larger of bytes over the memory's peak and
    operations over the chip's peak, and which of the two it is."""
    memory = counts["bytes"] / peaks["hbm_bytes_per_s"]
    compute = counts["flops"] / peaks["flops_per_s"]
    return (memory, "memory") if memory >= compute else (compute, "compute")


def step_least_seconds(arch: dict, t: int, e: int, peaks: dict) -> tuple:
    """One train step's blocks, forward and transpose, over all blocks:
    ``(seconds, which binds the most time)``."""
    sr = int(arch["num_spherical"]) * int(arch["num_radial"])
    b, i = int(arch["basis_emb_size"]), int(arch["int_emb_size"])
    total, by = 0.0, {"memory": 0.0, "compute": 0.0}
    for counts in (forward(t, e, sr, b, i), transpose(t, e, sr, b, i)):
        seconds, which = least_seconds(counts, peaks)
        total += seconds
        by[which] += seconds
    return int(arch["num_conv_layers"]) * total, max(by, key=by.get)
