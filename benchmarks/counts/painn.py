"""Operations of one PaiNN training step, from shapes alone.

The benchmark's own count: forward multiply-adds of the stack as the
program builds it (benchmarks/references/painn.py gives the equations) for
a graph of ``n`` atoms and ``e`` directed edges, times two (a multiply-add
is two operations), times three for forward plus backward. Padding and
recomputation are not counted, nor the radial basis's sines and cosines.
With F the width, R the radial functions and c = 3 (2 in the last block,
whose update has 2F outputs):

* embedding: ``n 118 F`` (the one-hot atomic number through a Dense);
* a block's message: ``n 4 F^2`` (phi, F -> F -> 3F) and ``e (3 R F + 16
  F)`` (the filter, then per edge the cutoff, W * phi, v_j * g_v, g_e r^/d
  and the two sums, 3 + 3 + 3 + 3 + 4 F);
* its update: ``n (6 F^2 + 2 F^2 + c F^2 + 6 F)`` (U and V, the update
  MLP 2F -> F -> cF, the norm and the inner product);
* the resize: ``n 2 F^2``, and ``n 3 F^2`` for the vector map after every
  block but the last;
* the decoder, once a graph.

By hand (benchmarks/tests/test_vector_readers.py): F=2, R=3, L=2, graph
head 3->2->1 (shared 1 layer of 3, a head layer of 2); n=4, e=6: embedding
944; block 0: 64 + 6 (18 + 32) + 4 (24 + 8 + 12 + 12) + 32 + 48 = 668;
block 1 (c = 2): 64 + 300 + 4 (24 + 8 + 8 + 12) + 32 = 604; decoder 6 +
6 + 2 = 14. Total 2,230.
"""

from __future__ import annotations

NUM_ELEMENTS = 118


def _mlp(dims: list) -> int:
    return sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def forward_macs(arch: dict, heads: list, n: float, e: float) -> float:
    F = int(arch["hidden_dim"])
    R = int(arch["num_radial"])
    blocks = int(arch["num_conv_layers"])
    macs = n * NUM_ELEMENTS * F
    for block in range(blocks):
        last = block == blocks - 1
        c = 2 if last else 3
        macs += n * 4 * F * F + e * (3 * R * F + 16 * F)
        macs += n * (6 * F * F + 2 * F * F + c * F * F + 6 * F)
        macs += n * 2 * F * F + (0 if last else n * 3 * F * F)
    g = arch["output_heads"]["graph"]
    shared = [F] + [int(g["dim_sharedlayers"])] * int(g["num_sharedlayers"])
    for head in heads:
        own = [shared[-1]] + [
            int(d) for d in g["dim_headlayers"][: int(g["num_headlayers"])]
        ] + [int(head["dim"])]
        macs += _mlp(own)
    return macs + _mlp(shared)


def train_flops_per_graph(arch: dict, heads: list, n: float, e: float) -> float:
    """Forward plus backward operations for one real graph."""
    return 3.0 * 2.0 * forward_macs(arch, heads, n, e)
