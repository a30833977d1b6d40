"""Operations of one DimeNet++ training step, from shapes alone.

The benchmark's own count: forward multiply-adds of the stack as the
program builds it (benchmarks/references/dimenet.py gives the equations)
for a graph of ``n`` atoms, ``e`` directed edges and ``t`` triplets, times
two (a multiply-add is two operations), times three for forward plus
backward. Padding and recomputation are not counted, nor the bases'
elementwise work (sines, the envelope, the spherical basis: no parameter
acts on them before the first matmul, which is counted).

``train_mfu`` gives mean atoms and edges alone. The triplets of a graph
whose edges all come in pairs i->j, j->i (a radius graph) are
``sum_j deg_j^2 - e``, at least ``e (e/n - 1)`` (Cauchy-Schwarz, over the
same ``n`` and ``e``); the count takes that bound, so it is a lower bound:
QM9-like molecules at 5 A, 19.06 atoms and 269.7 edges, give 3,547.6
against the 3,915.3 triplets counted in the training split, and the
triplet terms are 6% of the count.

By hand (benchmarks/tests/test_triplet_readers.py): H=4, I=2, B=3, O=5,
R=2, S=2, L=1, one input feature, graph head 4->3->1 shared 1 layer of 3
and a head layer of 2; n=3, e=4, t=6: lin 12; embedding 4 (2*4 + 12*4) =
224; interaction per edge 32 + (6 + 12 + 4) + (8 + 8) + 4 + 3*32 + 16 + 4
= 190, per triplet 4*3 + 3*2 + 2 + 2 = 22: 760 + 132; output 4 (8 + 4 + 4)
+ 3 (20 + 25 + 20) = 259; decoder 4*3 + 3*2 + 2 = 20. Total 1,407.
"""

from __future__ import annotations


def _mlp(dims: list) -> int:
    return sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def triplets_per_graph(n: float, e: float) -> float:
    """The least triplets ``n`` atoms and ``e`` paired edges can hold."""
    return max(e * (e / n - 1.0), 0.0)


def forward_macs(arch: dict, heads: list, n: float, e: float, t: float) -> float:
    H = int(arch["hidden_dim"])
    I = int(arch["int_emb_size"])
    B = int(arch["basis_emb_size"])
    O = int(arch["out_emb_size"])
    R = int(arch["num_radial"])
    SR = int(arch["num_spherical"]) * R
    blocks = int(arch["num_conv_layers"])
    macs = 0.0
    for block in range(blocks):
        h_in = int(arch.get("input_dim", 1)) if block == 0 else H
        macs += n * h_in * H  # the stack's lin
        macs += e * (R * H + 3 * H * H)  # embedding: lin_rbf, lin
        macs += e * (
            2 * H * H  # lin_ji, lin_kj
            + R * B + B * H + H  # rbf projection and its product
            + H * I + I * H  # lin_down, lin_up
            + H  # x_ji + x_kj
            + 3 * 2 * H * H + H * H + H  # residual layers, lin, skip
        )
        macs += t * (SR * B + B * I + I + I)  # basis projection, product, sum
        macs += e * (R * H + H + H)  # output: lin_rbf, product, sum
        macs += n * (H * O + O * O + O * H)  # lin_up, lin_0, lin_out
    g = arch["output_heads"]["graph"]
    shared = [H] + [int(g["dim_sharedlayers"])] * int(g["num_sharedlayers"])
    for head in heads:
        own = [shared[-1]] + [
            int(d) for d in g["dim_headlayers"][: int(g["num_headlayers"])]
        ] + [int(head["dim"])]
        macs += _mlp(own)
    return macs + _mlp(shared)


def train_flops_per_graph(arch: dict, heads: list, n: float, e: float) -> float:
    """Forward plus backward operations for one real graph."""
    t = triplets_per_graph(n, e)
    return 3.0 * 2.0 * forward_macs(arch, heads, n, e, t)
