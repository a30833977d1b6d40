"""Analytic model-FLOPs estimates + device peak tables + the shared
parsers for XLA's per-executable cost/memory accounting.

The flop arithmetic behind the run telemetry subsystem
(``utils/telemetry.py``'s live per-spec MFU rows,
docs/OBSERVABILITY.md), its one reader. Each estimator is a dense
multiply-add inventory (x2 = FLOPs) over MEAN REAL node/edge sizes —
no padding, no scatter lowering — i.e. the implementation-independent
figure a fair cross-framework comparison divides by. The benchmark
keeps counts of its own under ``benchmarks/counts/`` (``train_mfu``
reads those, not these).

The COUNTED side: ``compiled_cost_stats`` / ``compiled_memory_stats``
parse ``jax.stages.Compiled.cost_analysis()`` / ``memory_analysis()``
into plain dicts for the telemetry subsystem's per-executable
``executable`` rows. The analytic/counted PAIR is what roofline
attribution needs: counted/analytic is the padding+lowering waste
factor, and counted flops over counted bytes is the arithmetic
intensity the roofline ceiling ``min(peak_flops, intensity * peak_bw)``
turns into a memory-bound/compute-bound verdict (tools/graftboard.py
roofline).

Peak resolution (``resolve_peak_flops`` / ``resolve_peak_bandwidth``):
the running chip's ``device_kind`` when the tables know it. A chip the
tables do not know is an ERROR, never a default — its utilization
would be computed against some other chip's peak. Only a CPU run (or
one with no backend up yet) falls back to ``ANCHOR_DEVICE_KIND``, the
benchmark's chip, flagged ``roofline_anchor`` — a what-if "MFU this
run would achieve on the anchor TPU" that is never a device metric
(the CPU tests of the MFU and roofline arithmetic stand on it).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

# Peak bf16 FLOPs/sec by jax device_kind (public TPU/GPU specs).
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}

# Peak HBM bandwidth (bytes/sec) by device_kind — the other roofline
# axis (public specs: v4 1228 GB/s, v5e 819, v5p 2765, v6e 1640).
PEAK_HBM_BYTES_PER_SEC = {
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,
    "TPU v6e": 1640e9,
}

# The chip a CPU run's what-if peaks are read against: the device kind
# of the benchmark's chip (BENCHMARK.json's cells run on one of these).
ANCHOR_DEVICE_KIND = "TPU v5 lite"


def _resolve_peak(
    table: dict, table_name: str, what: str, device_kind: Optional[str]
) -> Tuple[float, str]:
    if device_kind in table:
        return table[device_kind], "device"
    # a real chip (not a CPU run, not a backend that is down: kind None)
    if device_kind is not None and device_kind.lower() != "cpu":
        raise ValueError(
            f"no peak {what} known for device kind {device_kind!r}: add "
            f"it to hydragnn_tpu.utils.flops.{table_name} with its source"
        )
    return table[ANCHOR_DEVICE_KIND], "roofline_anchor"


def resolve_peak_flops(
    device_kind: Optional[str] = None,
) -> Tuple[float, str]:
    """(peak bf16 FLOPs/sec, basis) for MFU denominators. Basis
    ``"device"`` = the running chip is in the peak table (a real MFU).
    A chip that is NOT in the table raises. On a CPU run the basis is
    ``"roofline_anchor"`` = ``ANCHOR_DEVICE_KIND``'s peak (a what-if
    utilization on the anchor chip, labelled as such)."""
    return _resolve_peak(PEAK_FLOPS, "PEAK_FLOPS", "FLOP/s", device_kind)


def resolve_peak_bandwidth(
    device_kind: Optional[str] = None,
) -> Tuple[float, str]:
    """(peak HBM bytes/sec, basis) — the bandwidth axis of the
    roofline. Basis semantics mirror ``resolve_peak_flops``."""
    return _resolve_peak(
        PEAK_HBM_BYTES_PER_SEC,
        "PEAK_HBM_BYTES_PER_SEC",
        "HBM bandwidth",
        device_kind,
    )


def compiled_cost_stats(compiled) -> dict:
    """Parse ``jax.stages.Compiled.cost_analysis()`` into a plain dict
    — counted HARDWARE flops (padding and scatter lowering included)
    and HBM bytes accessed for ONE dispatch of the executable. Keys
    (present only when XLA reports them): ``flops``,
    ``bytes_accessed``, ``transcendentals``, ``optimal_seconds``.
    Returns {} when the backend publishes no cost model (some PJRT
    plugins) — callers must treat absence as "unknown", never 0.
    The parse behind the telemetry ``executable`` rows
    (docs/OBSERVABILITY.md)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if ca is None:
        return {}
    out = {}
    for src, dst in (
        ("flops", "flops"),
        ("bytes accessed", "bytes_accessed"),
        ("transcendentals", "transcendentals"),
        ("optimal_seconds", "optimal_seconds"),
    ):
        try:
            v = ca.get(src)
        except Exception:
            return out
        if v is not None:
            try:
                out[dst] = float(v)
            except (TypeError, ValueError):
                pass
    return out


def compiled_memory_stats(compiled) -> dict:
    """Parse ``jax.stages.Compiled.memory_analysis()`` into a plain
    dict of the executable's HBM footprint in bytes:
    ``argument_bytes`` / ``output_bytes`` / ``temp_bytes`` (XLA's
    scratch) / ``alias_bytes`` (donated in-place reuse) /
    ``generated_code_bytes``. {} when the backend reports nothing."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    out = {}
    for src, dst in (
        ("argument_size_in_bytes", "argument_bytes"),
        ("output_size_in_bytes", "output_bytes"),
        ("temp_size_in_bytes", "temp_bytes"),
        ("alias_size_in_bytes", "alias_bytes"),
        ("generated_code_size_in_bytes", "generated_code_bytes"),
    ):
        v = getattr(ma, src, None)
        if v is not None:
            try:
                out[dst] = int(v)
            except (TypeError, ValueError):
                pass
    return out


# ----------------------------------------------------------------------
# Per-architecture inventories (docstrings document the op
# accounting). All take mean REAL sizes n (nodes/graph) and e
# (edges/graph).
# ----------------------------------------------------------------------


def schnet_flops(n, e, F, G, L, H):
    """SchNet forward multiply-adds (x2 = FLOPs) for n nodes / e edges:
    per conv layer the filter MLP on rbf (G->F->F per edge), cfconv
    in/out projections (``lin1`` H*F and ``lin2`` F*H per node),
    message multiply and segment add (F per edge each); then
    shared/head MLPs and the node embed. x3 for forward+backward of a
    train step."""
    fwd = L * (2 * e * (G * F + F * F) + 2 * n * (H * F + F * H) + 2 * e * F)
    fwd += 2 * n * H * H + 6 * H * H
    return 3.0 * fwd


def painn_flops(n, e, F, R, L, mlip_factor=9.0):
    """PaiNN training FLOPs per graph. Per layer (multiply-adds x2):
    message scalar MLP per node (F->F->3F), per-edge filter projection
    (R->3F) and gated scalar+vector message (~9F/edge: 3F gates over 1
    scalar + 3 vector components), update-block U/V vector projections
    (2 x 3 x F^2 per node) and update MLP (2F->F->3F). MLIP factor:
    the loss needs E AND forces = -dE/dpos (inner grad ~2x the energy
    forward -> x3), and the outer value_and_grad over params ~x3 that
    -> 9x the energy forward (the reference's create_graph=True double
    backward). The 9x is an UPPER bound — XLA shares subexpressions
    between the inner and outer transpose passes — so executed/model
    quotients can legitimately read below 1."""
    per_layer = (
        2 * n * (F * F + 3 * F * F)  # message scalar MLP
        + 2 * e * (R * 3 * F)  # filter projection
        + 2 * e * 9 * F  # gated message, 1 scalar + 3 vector comps
        + 2 * n * (2 * 3 * F * F)  # update U/V on vector channels
        + 2 * n * (2 * F * F + 3 * F * F)  # update MLP
    )
    fwd = L * per_layer + 2 * n * F
    return mlip_factor * fwd


def mace_flops(n, e, C, R, lmax, lhid, n_layers):
    """MACE training FLOPs per graph, from the op inventory of
    models/mace.py (docs/ROOFLINE.md): per layer the irreps linears
    (C^2 per l-block), the radial MLP (R+2C -> rd x3 -> P*C per edge),
    the channelwise TP path einsums
    (C x (2l1+1)(2l2+1)(2l3+1) per edge per path), the message scatter,
    and the symmetric contraction (~C x M_e^2 x M_hid per node at
    correlation 2). x3 for forward+backward."""
    from hydragnn_tpu.models.mace import tp_paths

    rd = float(max(1, math.ceil(C / 3.0)))
    M = lambda l: float((l + 1) ** 2)  # noqa: E731

    def layer(l_in, l_h):
        paths = tp_paths(l_in, lmax, lmax)
        P = float(len(paths))
        tp = 2 * e * C * sum(
            (2 * l1 + 1) * (2 * l2 + 1) * (2 * l3 + 1)
            for l1, l2, l3 in paths
        )
        radial = 2 * e * ((R + 2 * C) * rd + 2 * rd * rd + rd * P * C)
        # skip, up, down, post-msg, product, sizing irreps linears
        linears = 2 * n * C * C * (
            M(min(l_in, l_h)) + M(l_in) + 1 + M(lmax) + 2 * M(l_h)
        )
        scatter = 2 * e * C * M(lmax)
        sym = 2 * n * C * M(lmax) ** 2 * M(l_h)
        return tp + radial + linears + scatter + sym

    fwd = 2 * n * C  # element embedding
    for i in range(int(n_layers)):
        l_in = 0 if i == 0 else lhid
        l_h = 0 if i == int(n_layers) - 1 else lhid
        fwd += layer(l_in, l_h)
    return 3.0 * fwd


def pnaplus_flops(n, e, F, R, L, N=0.0):
    """PNAPlus(+GPS) training FLOPs per graph: per layer the PNA edge
    pipeline (rbf embed + pre_nn over 3F concat + rbf hadamard + 12
    aggregate/scale combos) and node post MLPs (13F->F, F->F), plus —
    when ``N`` (the static per-graph node bound) is nonzero — GPS
    global attention (qkv+out projections and dense masked scores over
    N). x3 for forward+backward."""
    pna = (
        2 * e * (R * F + 3 * F * F + R * F)  # rbf_emb, pre_nn, rbf_lin
        + 24 * e * F  # 4 aggregators x 3 scalers
        + 2 * n * (13 * F * F + F * F)  # post_nn on [x, scaled], lin
    )
    attn = (
        2 * n * (4 * F * F) + 2 * (2 * N * N * F) if N else 0.0
    )  # qkv/out + scores
    fwd = L * (pna + attn) + 2 * n * F * F + 6 * F * F
    return 3.0 * fwd


def model_flops_per_graph(cfg, mean_n: float, mean_e: float):
    """Dispatch ``cfg`` (models/spec.ModelConfig) to its analytic
    inventory at mean real sizes ``(mean_n, mean_e)``; None for
    architectures without one (no MFU row is emitted — never a
    fabricated estimate). MLIP training (``cfg.
    enable_interatomic_potential``) applies the 9x double-backward
    factor in place of the plain 3x fwd+bwd."""
    n, e = float(mean_n), float(mean_e)
    t = (cfg.mpnn_type or "").lower()
    mlip = 3.0 if cfg.enable_interatomic_potential else 1.0
    F = float(cfg.hidden_dim)
    L = float(cfg.num_conv_layers)
    if t == "schnet":
        return mlip * schnet_flops(
            n,
            e,
            float(cfg.num_filters or cfg.hidden_dim),
            float(cfg.num_gaussians or 50),
            L,
            F,
        )
    if t == "painn":
        R = float(cfg.num_radial or cfg.num_gaussians or 20)
        return painn_flops(n, e, F, R, L, mlip_factor=3.0 * mlip)
    if t == "mace":
        return mlip * mace_flops(
            n,
            e,
            F,
            float(cfg.num_radial or 8),
            int(cfg.max_ell or 1),
            int(cfg.node_max_ell or 1),
            int(cfg.num_conv_layers),
        )
    if t == "pnaplus":
        R = float(cfg.num_radial or 5)
        N = float(cfg.num_nodes or 0) if cfg.use_global_attn else 0.0
        return mlip * pnaplus_flops(n, e, F, R, L, N)
    return None
