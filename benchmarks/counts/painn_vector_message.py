"""The least traffic and operations of one ``vector_message`` block of
PaiNN and of its transpose, from shapes alone, whatever implements it.

The block (the program's scope ``vector_message``, models/equivariant.py
``PainnMessage``) is, with ``phi [N, 3F]`` the scalar MLP's output (made
outside the block), ``d [E]``, ``u [E, 3]`` the lengths and unit vectors,
``W_f [R, 3F]``, ``b_f [3F]``:

    W_ij          = (sinc(d) W_f + b_f) f_c(d)
    (g_v, g_e, m) = split(W_ij * phi[snd])
    s'            = s + segment_sum(m, rcv)
    v'            = v + segment_sum(v[snd] * g_v + g_e (x) u / d, rcv)

In the first block v = 0: the equations need no ``v``, no ``g_v`` and no
third of ``W_f`` or ``phi`` behind it. With c the thirds the block uses (3,
or 2 in the first block), vec 1 (0 in the first block), float32 (4 bytes)
and int32 indices, the least an implementation can do:

* forward: read ``d``, ``u``, both indices, the used columns of ``W_f``,
  ``b_f`` and ``phi``, ``s`` and ``v``; write ``s'`` and ``v'``. bytes
  ``4 (4 E + c F (R + 1) + c N F + 2 N F + 3 N F + vec 3 N F) + 8 E``;
  operations ``E F (2 R c + 3 c + 7 + 6 vec) + 4 N F`` (the filter's
  matmul 2 R c, its bias and cutoff 2 c, W * phi c, g_e (x) u / d 3, the
  two sums 4, v_j * g_v and its add 6 vec; the residual adds 4 N F).
* transpose: read the cotangents of ``s'`` and ``v'``, ``d``, ``u``, both
  indices, the weights, ``phi`` and ``v``; write the cotangents of ``phi``,
  ``v``, ``W_f`` and ``b_f`` (``d`` and ``u`` take none: positions are not
  trained). bytes ``4 (4 E + 2 c F (R + 1) + N F + 3 N F + 2 c N F + vec
  6 N F) + 8 E``; operations ``E F (4 R c + 7 c + 5 + 11 vec)``: the
  filter is not read back, so it is made again (2 R c + 2 c), the cutoff's
  cotangent, the bias's sum and the weight gradient (c + c + 2 R c), W *
  phi's two cotangents (2 c), g_e's cotangent through the product and the
  sum over the three components (5), v_j's and g_v's (8 vec), and the
  sender gathers' transposes, sums of the cotangents of ``phi`` and ``v``
  (c + 3 vec); the receiver sums' transposes are gathers.

The shapes are the padded ones the step is given (``nodes_pad``,
``edges_pad`` of the StepClock rows): the roofline of the call the block
gets; ``pad_ratio.train`` carries what the padding costs.

By hand, N=2, E=3, F=1, R=2 (benchmarks/tests/test_vector_readers.py): a
later block forward 4 (12 + 9 + 6 + 4 + 6 + 6) + 24 = 196 bytes, 3 (12 +
9 + 7 + 6) + 8 = 110 operations; transpose 4 (12 + 18 + 2 + 6 + 12 + 12)
+ 24 = 272 bytes, 3 (24 + 21 + 5 + 11) = 183 operations. The first block
forward 4 (12 + 6 + 4 + 4 + 6) + 24 = 152 bytes, 3 (8 + 6 + 7) + 8 = 71
operations; transpose 4 (12 + 12 + 2 + 6 + 8) + 24 = 184 bytes, 3 (16 +
14 + 5) = 105 operations.
"""

from __future__ import annotations


def forward(n: int, e: int, f: int, r: int, first: bool = False) -> dict:
    c, vec = (2, 0) if first else (3, 1)
    return {
        "bytes": 4 * (4 * e + c * f * (r + 1) + c * n * f + 2 * n * f
                      + 3 * n * f + vec * 3 * n * f) + 8 * e,
        "flops": e * f * (2 * r * c + 3 * c + 7 + 6 * vec) + 4 * n * f,
    }


def transpose(n: int, e: int, f: int, r: int, first: bool = False) -> dict:
    c, vec = (2, 0) if first else (3, 1)
    return {
        "bytes": 4 * (4 * e + 2 * c * f * (r + 1) + n * f + 3 * n * f
                      + 2 * c * n * f + vec * 6 * n * f) + 8 * e,
        "flops": e * f * (4 * r * c + 7 * c + 5 + 11 * vec),
    }


def least_seconds(counts: dict, peaks: dict) -> tuple:
    """``(seconds, which)``: the larger of bytes over the memory's peak and
    operations over the chip's peak, and which of the two it is."""
    memory = counts["bytes"] / peaks["hbm_bytes_per_s"]
    compute = counts["flops"] / peaks["flops_per_s"]
    return (memory, "memory") if memory >= compute else (compute, "compute")


def step_least_seconds(arch: dict, n: int, e: int, peaks: dict) -> tuple:
    """One train step's blocks, forward and transpose, over all blocks:
    ``(seconds, which binds the most time)``."""
    f, r = int(arch["hidden_dim"]), int(arch["num_radial"])
    total, by = 0.0, {"memory": 0.0, "compute": 0.0}
    for block in range(int(arch["num_conv_layers"])):
        for count in (forward, transpose):
            seconds, which = least_seconds(
                count(n, e, f, r, first=block == 0), peaks
            )
            total += seconds
            by[which] += seconds
    return total, max(by, key=by.get)
