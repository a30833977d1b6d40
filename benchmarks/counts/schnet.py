"""Operations of one SchNet training step, from shapes alone.

The benchmark's own count: forward multiply-adds of the published
architecture for a graph of ``n`` atoms and ``e`` directed edges, times two
(a multiply-add is two operations), times three for forward plus backward.
Padding and recomputation are not counted. ``lin1`` is ``H_in x F`` and
``lin2`` is ``F x H_out``: the program's ``utils/flops.schnet_flops`` counts
both as ``F x F``, which is wrong once ``hidden_dim != num_filters``.
"""

from __future__ import annotations


def _mlp(dims: list) -> int:
    """Multiply-adds of a dense chain over one row."""
    return sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def forward_macs(arch: dict, heads: list, n: float, e: float) -> float:
    """Forward multiply-adds for ``n`` atoms and ``e`` edges in one graph.

    ``arch`` is the configuration's ``Architecture`` block; ``heads`` is a
    list of ``{"type": "graph"|"node", "dim": d}``.
    """
    H = int(arch["hidden_dim"])
    F = int(arch["num_filters"])
    G = int(arch["num_gaussians"])
    L = int(arch["num_conv_layers"])
    macs = 0.0
    for layer in range(L):
        h_in = int(arch.get("input_dim", 1)) if layer == 0 else H
        macs += e * (G * F + F * F)  # filter MLP on the radial basis
        macs += e * F  # cutoff envelope on the filter
        macs += n * h_in * F  # lin1
        macs += e * F  # filter times gathered features
        macs += e * F  # segment sum (adds, counted like the program's)
        macs += n * F * H  # lin2
    out = arch["output_heads"]
    for head in heads:
        if head["type"] == "graph":
            g = out["graph"]
            shared = [H] + [int(g["dim_sharedlayers"])] * int(
                g["num_sharedlayers"]
            )
            own = [shared[-1]] + [
                int(d) for d in g["dim_headlayers"][: int(g["num_headlayers"])]
            ] + [int(head["dim"])]
            macs += _mlp(shared) + _mlp(own)  # once a graph
        else:
            nd = out["node"]
            own = [H] + [
                int(d) for d in nd["dim_headlayers"][: int(nd["num_headlayers"])]
            ] + [int(head["dim"])]
            macs += n * _mlp(own)
    return macs


def train_flops_per_graph(arch: dict, heads: list, n: float, e: float) -> float:
    """Forward plus backward operations for one real graph."""
    return 3.0 * 2.0 * forward_macs(arch, heads, n, e)
