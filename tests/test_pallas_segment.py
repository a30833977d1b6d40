"""Pallas sorted-segment-sum kernel: differential tests against
jax.ops.segment_sum (forward + gradient), plan construction edge cases.
Runs in interpret mode on the CPU mesh; the same code path compiles via
Mosaic on TPU (measured ~20% faster than XLA's scatter lowering at
E=32k/N=3k/F=128 — see module docstring).
"""

import numpy as np
import pytest

import tests._cpu  # noqa: F401

import jax
import jax.numpy as jnp

from hydragnn_tpu.ops.pallas_segment import (
    DEFAULT_BE,
    DEFAULT_BN,
    SortedSegmentPlan,
    plan_sorted_blocks,
    segment_sum_sorted,
)


def test_plan_covers_all_edges():
    rng = np.random.default_rng(0)
    seg = np.sort(rng.integers(0, 1000, 5000)).astype(np.int32)
    perm, seg_p, valid, window = plan_sorted_blocks(seg, 1000)
    assert len(perm) == len(seg_p) == len(valid)
    assert len(perm) % DEFAULT_BE == 0
    assert len(window) == len(perm) // DEFAULT_BE
    # every original edge appears exactly once among valid slots
    assert sorted(perm[valid]) == list(range(5000))
    # every valid slot's segment sits inside its block's window
    for b in range(len(window)):
        s = seg_p[b * DEFAULT_BE : (b + 1) * DEFAULT_BE]
        v = valid[b * DEFAULT_BE : (b + 1) * DEFAULT_BE]
        if v.any():
            assert np.all(s[v] // DEFAULT_BN == window[b])
    # windows non-decreasing (consecutive-revisit accumulation contract)
    assert np.all(np.diff(window) >= 0)


def test_plan_empty():
    perm, seg_p, valid, window = plan_sorted_blocks(
        np.zeros(0, np.int32), 16
    )
    assert not valid.any()
    assert len(window) == 1


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("shape", [(700, 128), (5000, 256)])
def test_forward_matches_xla(seed, shape):
    e, f = shape
    n = max(e // 10, 4)
    rng = np.random.default_rng(seed)
    seg = np.sort(rng.integers(0, n, e)).astype(np.int32)
    data = jnp.asarray(rng.normal(size=(e, f)), jnp.float32)
    ref = jax.ops.segment_sum(data, jnp.asarray(seg), num_segments=n)
    out = segment_sum_sorted(data, jnp.asarray(seg), n)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-4
    )


def test_gradient_matches_xla():
    rng = np.random.default_rng(3)
    e, n, f = 600, 64, 128
    seg = np.sort(rng.integers(0, n, e)).astype(np.int32)
    data = jnp.asarray(rng.normal(size=(e, f)), jnp.float32)

    def loss_pallas(d):
        return jnp.sum(segment_sum_sorted(d, jnp.asarray(seg), n) ** 2)

    def loss_xla(d):
        return jnp.sum(
            jax.ops.segment_sum(d, jnp.asarray(seg), num_segments=n) ** 2
        )

    g1 = jax.grad(loss_pallas)(data)
    g2 = jax.grad(loss_xla)(data)
    np.testing.assert_allclose(
        np.asarray(g1), np.asarray(g2), rtol=1e-4, atol=1e-3
    )


def test_plan_reuse_inside_jit():
    """A prebuilt plan is jittable (arrays become constants)."""
    rng = np.random.default_rng(5)
    e, n, f = 900, 100, 128
    seg = np.sort(rng.integers(0, n, e)).astype(np.int32)
    plan = SortedSegmentPlan(seg, n)
    data = jnp.asarray(rng.normal(size=(e, f)), jnp.float32)
    out = jax.jit(plan.__call__)(data)
    ref = jax.ops.segment_sum(data, jnp.asarray(seg), num_segments=n)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-4
    )


def test_empty_segments_are_zero():
    """Windows with no edges stay zero in the output."""
    e, n, f = 600, 1024, 128  # ids only in [0, 50): most windows empty
    rng = np.random.default_rng(7)
    seg = np.sort(rng.integers(0, 50, e)).astype(np.int32)
    data = jnp.asarray(rng.normal(size=(e, f)), jnp.float32)
    out = np.asarray(segment_sum_sorted(data, jnp.asarray(seg), n))
    assert np.all(out[50:] == 0.0)


def test_fused_product_matches_xla():
    """segment_sum_product_planned(a, b) == segment_sum(a * b): the
    fused kernel multiplies in VMEM instead of materializing the
    message intermediate."""
    from hydragnn_tpu.ops.pallas_segment import (
        plan_sorted_blocks,
        segment_sum_product_planned,
    )

    rng = np.random.default_rng(11)
    e, n, f = 900, 96, 128
    seg = np.sort(rng.integers(0, n, e)).astype(np.int32)
    a = jnp.asarray(rng.normal(size=(e, f)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(e, f)), jnp.float32)
    perm, seg_p, valid, window = plan_sorted_blocks(seg, n)
    out = segment_sum_product_planned(
        a, b, jnp.asarray(perm), jnp.asarray(seg_p),
        jnp.asarray(valid), jnp.asarray(window), n,
    )
    ref = jax.ops.segment_sum(a * b, jnp.asarray(seg), num_segments=n)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-4
    )


def test_fused_product_gradients_match_xla():
    """Both operands' gradients flow correctly through the fused VJP
    (d/da = b * g[seg], d/db = a * g[seg])."""
    from hydragnn_tpu.ops.pallas_segment import (
        plan_sorted_blocks,
        segment_sum_product_planned,
    )

    rng = np.random.default_rng(13)
    e, n, f = 500, 48, 64
    seg = np.sort(rng.integers(0, n, e)).astype(np.int32)
    a = jnp.asarray(rng.normal(size=(e, f)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(e, f)), jnp.float32)
    perm, seg_p, valid, window = plan_sorted_blocks(seg, n)
    args = (
        jnp.asarray(perm), jnp.asarray(seg_p),
        jnp.asarray(valid), jnp.asarray(window),
    )

    def loss_pallas(x, y):
        return jnp.sum(
            segment_sum_product_planned(x, y, *args, n) ** 2
        )

    def loss_xla(x, y):
        return jnp.sum(
            jax.ops.segment_sum(x * y, jnp.asarray(seg), num_segments=n)
            ** 2
        )

    ga1, gb1 = jax.grad(loss_pallas, argnums=(0, 1))(a, b)
    ga2, gb2 = jax.grad(loss_xla, argnums=(0, 1))(a, b)
    np.testing.assert_allclose(
        np.asarray(ga1), np.asarray(ga2), rtol=1e-4, atol=1e-3
    )
    np.testing.assert_allclose(
        np.asarray(gb1), np.asarray(gb2), rtol=1e-4, atol=1e-3
    )


def test_aggregate_receivers_product_dispatch():
    """The fused helper matches the XLA path on a planned batch (CPU
    forces use_plan explicitly; the batch carries plan fields from
    collate with_segment_plan). The in-kernel-multiply variant is
    opt-in via HYDRAGNN_TPU_SEGMENT_IMPL=pallas_fused."""
    import os

    prior = os.environ.get("HYDRAGNN_TPU_SEGMENT_IMPL")
    os.environ["HYDRAGNN_TPU_SEGMENT_IMPL"] = "pallas_fused"
    try:
        _run_dispatch_check()
    finally:
        if prior is None:
            os.environ.pop("HYDRAGNN_TPU_SEGMENT_IMPL", None)
        else:
            os.environ["HYDRAGNN_TPU_SEGMENT_IMPL"] = prior


def _run_dispatch_check():
    from hydragnn_tpu.data.graph import GraphSample, PadSpec, collate
    from hydragnn_tpu.ops.segment import aggregate_receivers_product

    rng = np.random.default_rng(17)
    samples = []
    for _ in range(4):
        nn_ = int(rng.integers(5, 9))
        ei = np.stack(
            [rng.integers(0, nn_, 24), rng.integers(0, nn_, 24)]
        )
        samples.append(
            GraphSample(
                x=rng.normal(size=(nn_, 3)).astype(np.float32),
                edge_index=ei,
            )
        )
    spec = PadSpec.for_samples(samples)
    batch = collate(samples, spec, with_segment_plan=True)
    assert batch.seg_window is not None
    e = batch.senders.shape[0]
    a = jnp.asarray(rng.normal(size=(e, 16)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(e, 16)), jnp.float32)
    fused = aggregate_receivers_product(a, b, batch, use_plan=True)
    plain = aggregate_receivers_product(a, b, batch, use_plan=False)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(plain), rtol=1e-5, atol=1e-4
    )


# ----------------------------------------------------------------------
# Shape-keyed crossover dispatch (ISSUE 3: never pick the planned
# kernel for oc20-class shapes where the crossover table's measured
# row has it slower than XLA).
# ----------------------------------------------------------------------


def test_planned_profitable_crossover_both_ways():
    """Pure table lookup (env/backend overrides live only in
    ops.segment.planned_path_wanted)."""
    from hydragnn_tpu.ops.pallas_segment import planned_profitable

    # the two measured anchor shapes
    assert planned_profitable(33792, 4224) is True  # qm9_b128
    assert planned_profitable(327680, 8192) is False  # oc20_b32
    # neighbors in log space land on the nearest verdict
    assert planned_profitable(20000, 3000) is True
    assert planned_profitable(8000, 1000) is True
    assert planned_profitable(500000, 16384) is False
    assert planned_profitable(250000, 8000) is False


def test_planned_path_wanted_env_force(monkeypatch):
    """The ONE env/backend override grammar, both directions."""
    from hydragnn_tpu.ops import segment

    monkeypatch.setattr(segment.jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("HYDRAGNN_TPU_SEGMENT_IMPL", "pallas")
    assert segment.planned_path_wanted(327680, 8192) is True
    monkeypatch.setenv("HYDRAGNN_TPU_SEGMENT_IMPL", "xla")
    assert segment.planned_path_wanted(33792, 4224) is False
    monkeypatch.delenv("HYDRAGNN_TPU_SEGMENT_IMPL", raising=False)
    assert segment.planned_path_wanted(33792, 4224) is True
    monkeypatch.setattr(segment.jax, "default_backend", lambda: "cpu")
    assert segment.planned_path_wanted(33792, 4224) is False


def test_aggregate_receivers_dispatch_decision(monkeypatch):
    """Unit-test of the dispatch decision itself (ops/segment.py
    _plan_dispatch) on a TPU-shaped backend, both ways: a qm9-class
    planned batch takes the kernel, an oc20-class one must fall back to
    the XLA scatter even though it carries a plan."""
    from hydragnn_tpu.ops import segment

    monkeypatch.delenv("HYDRAGNN_TPU_SEGMENT_IMPL", raising=False)

    class FakeBatch:
        def __init__(self, e, n, planned=True):
            self.seg_window = object() if planned else None
            self.num_edges = e
            self.num_nodes = n

    monkeypatch.setattr(segment.jax, "default_backend", lambda: "tpu")
    assert segment._plan_dispatch(FakeBatch(33792, 4224)) is True
    assert segment._plan_dispatch(FakeBatch(327680, 8192)) is False
    # no plan attached -> never the kernel, whatever the shape
    assert segment._plan_dispatch(FakeBatch(33792, 4224, False)) is False
    # forcing wins over the table
    monkeypatch.setenv("HYDRAGNN_TPU_SEGMENT_IMPL", "pallas")
    assert segment._plan_dispatch(FakeBatch(327680, 8192)) is True
    monkeypatch.setenv("HYDRAGNN_TPU_SEGMENT_IMPL", "xla")
    assert segment._plan_dispatch(FakeBatch(33792, 4224)) is False
    # off-TPU: scatter unless forced to interpret mode
    monkeypatch.delenv("HYDRAGNN_TPU_SEGMENT_IMPL", raising=False)
    monkeypatch.setattr(segment.jax, "default_backend", lambda: "cpu")
    assert segment._plan_dispatch(FakeBatch(33792, 4224)) is False


def test_loader_auto_segment_plan(monkeypatch):
    """with_segment_plan="auto": the host-side edge sort + block plan
    is only attached where the kernel would win AND be dispatched."""
    from hydragnn_tpu.data.graph import GraphSample, PadSpec
    from hydragnn_tpu.data.loader import GraphLoader

    rng = np.random.default_rng(0)
    samples = [
        GraphSample(
            x=rng.normal(size=(6, 1)).astype(np.float32),
            edge_index=np.stack(
                [rng.integers(0, 6, 12), rng.integers(0, 6, 12)]
            ),
        )
        for _ in range(8)
    ]
    ld = GraphLoader(samples, 4, with_segment_plan="auto")
    qm9ish = PadSpec(num_nodes=4224, num_edges=33792, num_graphs=129)
    oc20ish = PadSpec(num_nodes=8192, num_edges=327680, num_graphs=33)
    monkeypatch.delenv("HYDRAGNN_TPU_SEGMENT_IMPL", raising=False)
    # CPU backend: no plan (it would never be dispatched)
    assert ld.segment_plan_enabled(qm9ish) is False
    # forced interpret mode: follows the table per shape
    monkeypatch.setenv("HYDRAGNN_TPU_SEGMENT_IMPL", "pallas")
    assert ld.segment_plan_enabled(qm9ish) is True
    assert ld.segment_plan_enabled(oc20ish) is True  # force wins
    # explicit bool still wins over auto resolution
    ld_on = GraphLoader(samples, 4, with_segment_plan=True)
    monkeypatch.delenv("HYDRAGNN_TPU_SEGMENT_IMPL", raising=False)
    assert ld_on.segment_plan_enabled(oc20ish) is True
    batch = next(iter(ld_on))
    assert batch.seg_window is not None


# ----------------------------------------------------------------------
# Fused edge pipeline (ISSUE 9): gather -> filter multiply -> dense
# matmul -> segment reduce in ONE Pallas pass over aligned plan tiles.
# Ulp-tolerance CONTRACT (docs/ROOFLINE.md "Fused edge pipeline"):
# bitwise identity with the XLA scatter is explicitly NOT required —
# the block decomposition regroups the f32 accumulation. Gates:
#   f32:  rtol 1e-5 / atol 1e-4  (reduction regrouping only)
#   bf16: rtol 4e-2 / atol 2.5e-1 vs the SAME-dtype XLA reference
#         (a few bf16 ulps of the accumulated magnitude; the kernel
#         keeps f32 output tiles, the reference accumulates in bf16,
#         so the kernel is the more precise side)
# plus converged-loss parity in test_optimizer_precision_losses.py.
# ----------------------------------------------------------------------

F32_TOL = dict(rtol=1e-5, atol=1e-4)
BF16_TOL = dict(rtol=4e-2, atol=2.5e-1)


def _pipeline_case(seed=23, e=1300, n=160, f_in=64, f_out=32):
    rng = np.random.default_rng(seed)
    seg = np.sort(rng.integers(0, n, e)).astype(np.int32)
    a = rng.normal(size=(e, f_in)).astype(np.float32)
    b = rng.normal(size=(e, f_in)).astype(np.float32)
    w = rng.normal(size=(f_in, f_out)).astype(np.float32)
    plan = plan_sorted_blocks(seg, n)
    return seg, a, b, w, tuple(jnp.asarray(p) for p in plan)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("stages", ["a", "ab", "aw", "abw"])
def test_edge_pipeline_forward_matches_xla(dtype, stages):
    """Forward parity of every stage combination (reduce-only, +filter,
    +weight, full pipeline) against the XLA scatter reference in the
    SAME dtype, within the documented ulp tolerances."""
    from hydragnn_tpu.ops.pallas_segment import edge_pipeline_planned

    seg, a_np, b_np, w_np, plan = _pipeline_case()
    n = 160
    dt = jnp.dtype(dtype)
    a = jnp.asarray(a_np, dt)
    b = jnp.asarray(b_np, dt) if "b" in stages else None
    w = jnp.asarray(w_np) if "w" in stages else None  # f32 master weight
    out = edge_pipeline_planned(a, b, w, *plan, n)
    ref = a if b is None else a * b
    if w is not None:
        ref = ref @ w
    ref = jax.ops.segment_sum(ref, jnp.asarray(seg), num_segments=n)
    tol = F32_TOL if dtype == "float32" else BF16_TOL
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_edge_pipeline_vjp_matches_xla(dtype):
    """custom_vjp backward parity for ALL THREE operands (a, b, w):
    pull back the SAME cotangent through both implementations (fixing
    the cotangent isolates the backward rule from the forward's own
    ulp difference, which a loss-composed grad would amplify)."""
    from hydragnn_tpu.ops.pallas_segment import edge_pipeline_planned

    seg, a_np, b_np, w_np, plan = _pipeline_case(e=900, n=96)
    n = 96
    dt = jnp.dtype(dtype)
    a, b = jnp.asarray(a_np, dt), jnp.asarray(b_np, dt)
    w = jnp.asarray(w_np)
    out1, vjp1 = jax.vjp(
        lambda x, y, ww: edge_pipeline_planned(x, y, ww, *plan, n),
        a, b, w,
    )
    out2, vjp2 = jax.vjp(
        lambda x, y, ww: jax.ops.segment_sum(
            (x * y) @ ww, jnp.asarray(seg), num_segments=n
        ),
        a, b, w,
    )
    rng = np.random.default_rng(43)
    g = jnp.asarray(rng.normal(size=out1.shape), out1.dtype)
    tol = (
        dict(rtol=1e-4, atol=1e-3)
        if dtype == "float32"
        else BF16_TOL
    )
    for got, ref, name in zip(vjp1(g), vjp2(g), "abw"):
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(ref, np.float32),
            err_msg=f"d{name}",
            **tol,
        )


def test_edge_pipeline_masked_edges():
    """edge_valid folds the batch edge mask INTO the plan: masked
    (padding) edges contribute nothing to forward or backward, with no
    pre-masked operand copy."""
    from hydragnn_tpu.ops.pallas_segment import (
        edge_pipeline_planned,
        plan_blocks_static,
        static_block_bound,
    )

    rng = np.random.default_rng(29)
    e, n, f = 1100, 128, 32
    seg = np.sort(rng.integers(0, n, e)).astype(np.int32)
    # collate's mask: padding edges sort after every real one, so each
    # block's valid rows stay one contiguous run (the kernel contract)
    ev = np.arange(e) < 770
    a = jnp.asarray(rng.normal(size=(e, f)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(e, f)), jnp.float32)
    plan = plan_blocks_static(
        seg, n, static_block_bound(e, n), edge_valid=ev
    )
    plan = tuple(jnp.asarray(p) for p in plan)
    out = edge_pipeline_planned(a, b, None, *plan, n)
    ref = jax.ops.segment_sum(
        jnp.where(jnp.asarray(ev)[:, None], a * b, 0),
        jnp.asarray(seg),
        num_segments=n,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), **F32_TOL
    )
    # masked edges get ZERO gradient (the where-grad of the old
    # pre-mask, now via the plan's valid slots)
    g = jax.grad(
        lambda x: jnp.sum(edge_pipeline_planned(x, b, None, *plan, n) ** 2)
    )(a)
    assert np.all(np.asarray(g)[~ev] == 0.0)


def test_plan_rejects_edge_mask_with_holes():
    """The kernels rebuild a block's mask from the two end points of
    its valid run, so a mask that leaves holes inside a run cannot be
    planned — it raises at plan time instead of summing masked rows."""
    from hydragnn_tpu.ops.pallas_segment import plan_sorted_blocks

    seg = np.sort(np.random.default_rng(3).integers(0, 64, 900))
    ev = np.ones(900, bool)
    ev[100] = False
    with pytest.raises(ValueError, match="holes"):
        plan_sorted_blocks(seg.astype(np.int32), 64, edge_valid=ev)


def test_edge_pipeline_empty_windows_and_static_padding():
    """Empty node windows stay zero and plan_blocks_static padding
    blocks accumulate nothing — the all-invalid blocks read tile 0 and
    must not perturb the window they nominally target."""
    from hydragnn_tpu.ops.pallas_segment import (
        edge_pipeline_planned,
        plan_blocks_static,
        static_block_bound,
    )

    rng = np.random.default_rng(31)
    e, n, f = 700, 2048, 48  # ids only in [0, 40): most windows empty
    seg = np.sort(rng.integers(0, 40, e)).astype(np.int32)
    a = jnp.asarray(rng.normal(size=(e, f)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(e, f)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(f, 16)), jnp.float32)
    bound = static_block_bound(e, n)
    plan = plan_blocks_static(seg, n, bound)
    assert len(plan[3]) == bound  # padding blocks present
    plan = tuple(jnp.asarray(p) for p in plan)
    out = np.asarray(edge_pipeline_planned(a, b, w, *plan, n))
    ref = np.asarray(
        jax.ops.segment_sum(
            (a * b) @ w, jnp.asarray(seg), num_segments=n
        )
    )
    np.testing.assert_allclose(out, ref, **F32_TOL)
    assert np.all(out[40:] == 0.0)


def test_plan_aligned_tiles_invariant():
    """The fused kernel's gather contract: every block's slots are ONE
    be-aligned tile of the sorted edge array (perm[b*be] % be == 0 and
    slot i holds row perm[b*be] + i, clamped at the array end) — this
    is what lets a BlockSpec index_map stage the gather."""
    rng = np.random.default_rng(37)
    for e, n in ((5000, 1000), (700, 64), (90, 2000)):
        seg = np.sort(rng.integers(0, n, e)).astype(np.int32)
        perm, _, valid, _ = plan_sorted_blocks(seg, n)
        tiles = perm.reshape(-1, DEFAULT_BE)
        assert np.all(tiles[:, 0] % DEFAULT_BE == 0)
        expect = np.minimum(
            tiles[:, :1] + np.arange(DEFAULT_BE)[None, :], e - 1
        )
        assert np.all(tiles == expect)
        # every real edge still appears exactly once among valid slots
        assert sorted(perm[valid].tolist()) == list(range(e))


def test_crossover_table_what_if_rows_never_dispatch(tmp_path, monkeypatch):
    """The no-fabrication rule: rows whose verdict was not measured on
    a real device (*_measured=false) are invisible to dispatch; a
    measured fused win IS dispatched on."""
    import json

    from hydragnn_tpu.ops import pallas_segment as ps

    table = {
        "version": 1,
        "rows": [
            {
                "num_edges": 30000, "num_segments": 4000,
                "planned_wins": True, "planned_measured": True,
                "fused_wins": True, "fused_measured": False,  # WHAT-IF
            },
            {
                "num_edges": 300000, "num_segments": 8000,
                "planned_wins": False, "planned_measured": True,
                "fused_wins": True, "fused_measured": True,
            },
        ],
    }
    p = tmp_path / "table.json"
    p.write_text(json.dumps(table))
    monkeypatch.setenv(ps.CROSSOVER_TABLE_ENV, str(p))
    assert ps.planned_profitable(30000, 4000) is True
    assert ps.planned_profitable(300000, 8000) is False
    # the qm9-class WHAT-IF fused row must NOT dispatch; the measured
    # oc20-class one must
    assert ps.fused_profitable(30000, 4000) is True  # nearest MEASURED
    # row is the oc20 one — only measured rows exist in fused space
    assert ps.fused_profitable(300000, 8000) is True
    # empty/corrupt table -> no basis -> False everywhere
    p2 = tmp_path / "corrupt.json"
    p2.write_text("{not json")
    monkeypatch.setenv(ps.CROSSOVER_TABLE_ENV, str(p2))
    assert ps.planned_profitable(30000, 4000) is False
    assert ps.fused_profitable(30000, 4000) is False


def test_seed_table_fused_is_what_if():
    """The CHECKED-IN seed carries fused verdicts only as WHAT-IF
    (modeled traffic): until tools/roofline_segment.py --write-table
    runs on a real TPU, fused dispatch must stay off everywhere."""
    from hydragnn_tpu.ops.pallas_segment import (
        fused_profitable,
        load_crossover_table,
    )

    rows = load_crossover_table()
    assert rows, "seed table missing"
    assert all("fused_wins" in r for r in rows)  # verdict per row
    assert not any(r.get("fused_measured") for r in rows)
    assert fused_profitable(33792, 4224) is False
    assert fused_profitable(327680, 8192) is False


def test_fused_path_wanted_grammar(monkeypatch):
    """The ONE env grammar for the kernel-flavor policy: pallas_fused
    forces, xla forbids, pallas keeps the fused choice table-driven."""
    from hydragnn_tpu.ops import segment

    monkeypatch.setenv("HYDRAGNN_TPU_SEGMENT_IMPL", "pallas_fused")
    assert segment.fused_path_wanted(33792, 4224) is True
    monkeypatch.setenv("HYDRAGNN_TPU_SEGMENT_IMPL", "xla")
    assert segment.fused_path_wanted(33792, 4224) is False
    monkeypatch.setenv("HYDRAGNN_TPU_SEGMENT_IMPL", "pallas")
    # planned forced, fused still table-driven (seed: WHAT-IF only)
    assert segment.fused_path_wanted(33792, 4224) is False
    assert segment.planned_path_wanted(33792, 4224) is True


def test_aggregate_receivers_pipeline_matches_reference():
    """The dispatched full-pipeline helper: fused (forced) and unfused
    paths agree with the plain scatter+matmul reference on a planned
    batch, including the mean variant (degree division commutes with
    the matmul within tolerance)."""
    import os

    from hydragnn_tpu.data.graph import GraphSample, PadSpec, collate
    from hydragnn_tpu.ops.segment import aggregate_receivers_pipeline

    rng = np.random.default_rng(41)
    samples = []
    for _ in range(4):
        nn_ = int(rng.integers(5, 9))
        ei = np.stack(
            [rng.integers(0, nn_, 24), rng.integers(0, nn_, 24)]
        )
        samples.append(
            GraphSample(
                x=rng.normal(size=(nn_, 3)).astype(np.float32),
                edge_index=ei,
            )
        )
    spec = PadSpec.for_samples(samples)
    batch = collate(samples, spec, with_segment_plan=True)
    e = batch.senders.shape[0]
    a = jnp.asarray(rng.normal(size=(e, 16)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(e, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    ref = (
        jax.ops.segment_sum(
            jnp.where(batch.edge_mask[:, None], a * b, 0),
            batch.receivers,
            num_segments=batch.num_nodes,
        )
        @ w
    )
    prior = os.environ.get("HYDRAGNN_TPU_SEGMENT_IMPL")
    os.environ["HYDRAGNN_TPU_SEGMENT_IMPL"] = "pallas_fused"
    try:
        fused = aggregate_receivers_pipeline(
            a, b, batch, weight=w, use_plan=True
        )
        fused_mean = aggregate_receivers_pipeline(
            a, None, batch, weight=w, mean=True, use_plan=True
        )
    finally:
        if prior is None:
            os.environ.pop("HYDRAGNN_TPU_SEGMENT_IMPL", None)
        else:
            os.environ["HYDRAGNN_TPU_SEGMENT_IMPL"] = prior
    unfused = aggregate_receivers_pipeline(
        a, b, batch, weight=w, use_plan=False
    )
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref), **F32_TOL)
    np.testing.assert_allclose(np.asarray(unfused), np.asarray(ref), **F32_TOL)
    from hydragnn_tpu.ops.segment import degree

    cnt = jnp.maximum(
        degree(batch.receivers, batch.num_nodes, mask=batch.edge_mask), 1
    )
    ref_mean = (
        jax.ops.segment_sum(
            jnp.where(batch.edge_mask[:, None], a, 0),
            batch.receivers,
            num_segments=batch.num_nodes,
        )
        / cnt[:, None]
    ) @ w
    np.testing.assert_allclose(
        np.asarray(fused_mean), np.asarray(ref_mean), rtol=1e-4, atol=1e-4
    )


def test_reduce_only_sites_never_ride_a_fused_only_win(tmp_path, monkeypatch):
    """Dispatch layering (the acceptance rule's sharp edge): on a shape
    where the reduce-only planned kernel MEASURED a loss but the fused
    kernel a win, fused-capable call sites dispatch, plain-sum call
    sites must keep the XLA scatter (no fused variant exists for them),
    and the loader still attaches the plan (the fused path needs it)."""
    import json

    from hydragnn_tpu.ops import pallas_segment as ps
    from hydragnn_tpu.ops import segment

    table = {
        "rows": [
            {
                "num_edges": 327680, "num_segments": 8192,
                "planned_wins": False, "planned_measured": True,
                "fused_wins": True, "fused_measured": True,
            }
        ]
    }
    p = tmp_path / "fused_only.json"
    p.write_text(json.dumps(table))
    monkeypatch.setenv(ps.CROSSOVER_TABLE_ENV, str(p))
    monkeypatch.delenv("HYDRAGNN_TPU_SEGMENT_IMPL", raising=False)
    monkeypatch.setattr(segment.jax, "default_backend", lambda: "tpu")

    class FakeBatch:
        seg_window = object()
        num_edges = 327680
        num_nodes = 8192

    assert segment._plan_dispatch(FakeBatch()) is False
    assert segment._plan_dispatch(FakeBatch(), fused_capable=True) is True
    assert segment.fused_path_wanted(327680, 8192) is True
    assert segment.planned_path_wanted(327680, 8192) is True  # attach


def test_crossover_lookup_keys_on_feature_dim(tmp_path, monkeypatch):
    """A regenerated table carries one row per feature width at the
    same (E, N): the lookup must key on F when the call site provides
    it, and vote CONSERVATIVELY (all tied rows must win) when it
    cannot."""
    import json

    from hydragnn_tpu.ops import pallas_segment as ps

    table = {
        "rows": [
            {
                "num_edges": 33792, "num_segments": 4224,
                "feature_dim": 64,
                "fused_wins": False, "fused_measured": True,
            },
            {
                "num_edges": 33792, "num_segments": 4224,
                "feature_dim": 256,
                "fused_wins": True, "fused_measured": True,
            },
        ]
    }
    p = tmp_path / "fgrid.json"
    p.write_text(json.dumps(table))
    monkeypatch.setenv(ps.CROSSOVER_TABLE_ENV, str(p))
    assert ps.fused_profitable(33792, 4224, feature_dim=256) is True
    assert ps.fused_profitable(33792, 4224, feature_dim=64) is False
    # no F from the call site: equidistant rows disagree -> never take
    # the kernel on a possibly-losing shape
    assert ps.fused_profitable(33792, 4224) is False


def test_segment_impl_override_last_set_wins(monkeypatch):
    """Training.segment_impl plumbs through a last-set-wins override
    (cleared by an absent key), NOT an env setdefault — consecutive
    runs in one process must not inherit each other's flavor; the env
    var still outranks it."""
    from hydragnn_tpu.ops import segment

    monkeypatch.delenv("HYDRAGNN_TPU_SEGMENT_IMPL", raising=False)
    try:
        segment.set_segment_impl_override("pallas_fused")
        assert segment._segment_impl() == "pallas_fused"
        segment.set_segment_impl_override("xla")
        assert segment._segment_impl() == "xla"
        segment.set_segment_impl_override(None)  # absent config key
        assert segment._segment_impl() == ""
        segment.set_segment_impl_override("pallas_fused")
        monkeypatch.setenv("HYDRAGNN_TPU_SEGMENT_IMPL", "xla")
        assert segment._segment_impl() == "xla"  # env outranks config
    finally:
        segment.set_segment_impl_override(None)


def test_attach_policy_optimistic_on_feature_ties(tmp_path, monkeypatch):
    """An F-specific measured fused win (the 'flip the oc20 row'
    outcome) must stay REACHABLE: the loader's attach decision has no
    feature width, so it votes optimistically across the F grid —
    while dispatch without F stays conservative and dispatch WITH F
    picks the matching row."""
    import json

    from hydragnn_tpu.ops import pallas_segment as ps
    from hydragnn_tpu.ops import segment

    table = {
        "rows": [
            {
                "num_edges": 327680, "num_segments": 8192,
                "feature_dim": 128,
                "planned_wins": False, "planned_measured": True,
                "fused_wins": False, "fused_measured": True,
            },
            {
                "num_edges": 327680, "num_segments": 8192,
                "feature_dim": 256,
                "planned_wins": False, "planned_measured": True,
                "fused_wins": True, "fused_measured": True,
            },
        ]
    }
    p = tmp_path / "fgrid_oc20.json"
    p.write_text(json.dumps(table))
    monkeypatch.setenv(ps.CROSSOVER_TABLE_ENV, str(p))
    monkeypatch.delenv("HYDRAGNN_TPU_SEGMENT_IMPL", raising=False)
    monkeypatch.setattr(segment.jax, "default_backend", lambda: "tpu")
    # loader attach: optimistic — the F=256 fused win keeps plans on
    assert segment.planned_path_wanted(327680, 8192) is True
    # dispatch without F: conservative (tied rows disagree)
    assert ps.fused_profitable(327680, 8192) is False
    # dispatch with F: the matching row decides
    assert ps.fused_profitable(327680, 8192, feature_dim=256) is True
    assert ps.fused_profitable(327680, 8192, feature_dim=128) is False

    class FakeBatch:
        seg_window = object()
        num_edges = 327680
        num_nodes = 8192

    assert (
        segment._plan_dispatch(FakeBatch(), feature_dim=256, fused_capable=True)
        is True
    )
    assert (
        segment._plan_dispatch(FakeBatch(), feature_dim=128, fused_capable=True)
        is False
    )


# ----------------------------------------------------------------------
# Symmetric Pallas backward (ISSUE 18): grad-parity of the one-pass
# pullback vs the XLA reference, its dispatch gating, and the table
# cache reload.
# ----------------------------------------------------------------------

# Documented ulp tolerances for the fused VJP (looser than the forward
# F32_TOL: d_w accumulates E products per element and the block
# decomposition regroups the f32 adds).
VJP_F32_TOL = dict(rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("stages", ["a", "ab", "aw", "abw"])
def test_fused_bwd_matches_xla_pullback(dtype, stages, monkeypatch):
    """Grad parity of the symmetric Pallas backward for EVERY operand
    variant (b/w present and absent) in both precisions: the same
    cotangent pulled back through the fused kernel (forced via
    HYDRAGNN_TPU_SEGMENT_IMPL=pallas_fused) and through the XLA
    pullback must agree within the documented ulp tolerances."""
    from hydragnn_tpu.ops.pallas_segment import (
        _edge_pipeline_bwd_xla,
        edge_pipeline_planned,
    )

    monkeypatch.setenv("HYDRAGNN_TPU_SEGMENT_IMPL", "pallas_fused")
    seg, a_np, b_np, w_np, plan = _pipeline_case(e=900, n=96)
    n = 96
    dt = jnp.dtype(dtype)
    a = jnp.asarray(a_np, dt)
    b = jnp.asarray(b_np, dt) if "b" in stages else None
    w = jnp.asarray(w_np) if "w" in stages else None  # f32 master weight
    def run(*ops):
        it = iter(ops)
        return edge_pipeline_planned(
            next(it),
            next(it) if "b" in stages else None,
            next(it) if "w" in stages else None,
            *plan,
            n,
        )

    out, vjp = jax.vjp(run, *[t for t in (a, b, w) if t is not None])
    rng = np.random.default_rng(47)
    g = jnp.asarray(rng.normal(size=out.shape), out.dtype)
    got = vjp(g)
    ref = _edge_pipeline_bwd_xla(a, b, w, *plan[:3], g)
    tol = VJP_F32_TOL if dtype == "float32" else BF16_TOL
    names = "a" + ("b" if "b" in stages else "") + ("w" if "w" in stages else "")
    for got_t, ref_t, name in zip(got, [r for r in ref if r is not None], names):
        np.testing.assert_allclose(
            np.asarray(got_t, np.float32),
            np.asarray(ref_t, np.float32),
            err_msg=f"d{name} ({stages}, {dtype})",
            **tol,
        )


def test_fused_bwd_masked_edges_and_static_padding(monkeypatch):
    """The fused pullback under a STATIC-padded plan with masked edges:
    padding blocks (which read input tile 0) must not corrupt the
    gradients of tile 0's real edges — the cummax out-tile routing —
    and masked edges must get exactly zero gradient."""
    from hydragnn_tpu.ops.pallas_segment import (
        _edge_pipeline_bwd_xla,
        edge_pipeline_planned,
        plan_blocks_static,
        static_block_bound,
    )

    monkeypatch.setenv("HYDRAGNN_TPU_SEGMENT_IMPL", "pallas_fused")
    rng = np.random.default_rng(53)
    e, n, fi, fo = 1100, 2048, 32, 16  # ids in [0, 60): empty windows +
    seg = np.sort(rng.integers(0, 60, e)).astype(np.int32)  # padding
    ev = np.arange(e) < 770  # real edges first, as collate lays them
    a = jnp.asarray(rng.normal(size=(e, fi)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(e, fi)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(fi, fo)), jnp.float32)
    bound = static_block_bound(e, n)
    plan = plan_blocks_static(seg, n, bound, edge_valid=ev)
    assert len(plan[3]) == bound  # padding blocks present
    plan = tuple(jnp.asarray(p) for p in plan)
    out, vjp = jax.vjp(
        lambda x, y, ww: edge_pipeline_planned(x, y, ww, *plan, n), a, b, w
    )
    g = jnp.asarray(rng.normal(size=out.shape), out.dtype)
    got = vjp(g)
    ref = _edge_pipeline_bwd_xla(a, b, w, *plan[:3], g)
    for got_t, ref_t, name in zip(got, ref, "abw"):
        np.testing.assert_allclose(
            np.asarray(got_t),
            np.asarray(ref_t),
            err_msg=f"d{name}",
            **VJP_F32_TOL,
        )
    assert np.all(np.asarray(got[0])[~ev] == 0.0)
    assert np.all(np.asarray(got[1])[~ev] == 0.0)


def test_fused_bwd_single_block_and_empty_edges(monkeypatch):
    """Shape edges of the fused pullback: a sub-tile edge array (one
    block, E < be) round-trips, and E == 0 short-circuits to zero
    gradients without calling the kernel."""
    from hydragnn_tpu.ops.pallas_segment import (
        _edge_pipeline_bwd_xla,
        edge_pipeline_planned,
        plan_sorted_blocks,
    )

    monkeypatch.setenv("HYDRAGNN_TPU_SEGMENT_IMPL", "pallas_fused")
    rng = np.random.default_rng(59)
    e, n, f = 37, 12, 16
    seg = np.sort(rng.integers(0, n, e)).astype(np.int32)
    a = jnp.asarray(rng.normal(size=(e, f)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(e, f)), jnp.float32)
    plan = tuple(jnp.asarray(p) for p in plan_sorted_blocks(seg, n))
    assert plan[3].shape[0] == 1  # single block
    out, vjp = jax.vjp(
        lambda x, y: edge_pipeline_planned(x, y, None, *plan, n), a, b
    )
    g = jnp.asarray(rng.normal(size=out.shape), out.dtype)
    got = vjp(g)
    ref = _edge_pipeline_bwd_xla(a, b, None, *plan[:3], g)
    for got_t, ref_t in zip(got, ref[:2]):
        np.testing.assert_allclose(
            np.asarray(got_t), np.asarray(ref_t), **VJP_F32_TOL
        )
    # E == 0: zeros out, zero grads, no kernel call
    a0 = jnp.zeros((0, f), jnp.float32)
    plan0 = tuple(
        jnp.asarray(p) for p in plan_sorted_blocks(np.zeros(0, np.int32), n)
    )
    out0, vjp0 = jax.vjp(
        lambda x: edge_pipeline_planned(x, None, None, *plan0, n), a0
    )
    assert out0.shape == (n, f) and not np.asarray(out0).any()
    (g0,) = vjp0(jnp.ones((n, f), jnp.float32))
    assert g0.shape == (0, f)


def test_fused_bwd_wanted_grammar(tmp_path, monkeypatch):
    """The env/backend grammar of the BACKWARD flavor policy:
    pallas_fused forces the symmetric kernel, xla forbids it, and a
    non-TPU backend without the force stays on the XLA pullback even
    when the table claims a measured bwd win — CPU/CI never takes the
    kernel silently."""
    import json

    from hydragnn_tpu.ops import pallas_segment as ps
    from hydragnn_tpu.ops import segment

    monkeypatch.setenv("HYDRAGNN_TPU_SEGMENT_IMPL", "pallas_fused")
    assert segment.fused_bwd_wanted(33792, 4224) is True
    monkeypatch.setenv("HYDRAGNN_TPU_SEGMENT_IMPL", "xla")
    assert segment.fused_bwd_wanted(33792, 4224) is False
    # no force, CPU backend, measured win in the table -> still XLA
    table = {
        "rows": [
            {
                "num_edges": 33792, "num_segments": 4224,
                "bwd_wins": True, "bwd_measured": True,
            }
        ]
    }
    p = tmp_path / "bwd.json"
    p.write_text(json.dumps(table))
    monkeypatch.setenv(ps.CROSSOVER_TABLE_ENV, str(p))
    monkeypatch.delenv("HYDRAGNN_TPU_SEGMENT_IMPL", raising=False)
    assert segment.fused_bwd_wanted(33792, 4224) is False  # CPU
    # on TPU the measured row decides
    monkeypatch.setattr(segment.jax, "default_backend", lambda: "tpu")
    assert segment.fused_bwd_wanted(33792, 4224) is True
    assert ps.bwd_profitable(33792, 4224) is True


def test_seed_table_bwd_is_what_if():
    """The CHECKED-IN seed carries bwd verdicts only as WHAT-IF
    (modeled traffic, 1.4-1.8x): until --write-table runs on a real
    TPU, the symmetric backward must stay off everywhere — gradients
    get no fabrication exemption."""
    from hydragnn_tpu.ops.pallas_segment import (
        bwd_profitable,
        load_crossover_table,
    )

    rows = load_crossover_table()
    assert rows, "seed table missing"
    assert all("bwd_wins" in r for r in rows)  # verdict per row
    assert not any(r.get("bwd_measured") for r in rows)
    assert bwd_profitable(33792, 4224) is False
    assert bwd_profitable(327680, 8192) is False


def test_reload_crossover_table(tmp_path, monkeypatch):
    """The staleness fix: a table rewritten on disk is invisible to the
    per-path cache until reload_crossover_table() drops it — after the
    reload, dispatch sees the new verdicts (and path=None clears every
    cached path, for env-var swaps)."""
    import json

    from hydragnn_tpu.ops import pallas_segment as ps

    p = tmp_path / "t.json"
    row = {
        "num_edges": 1000, "num_segments": 100,
        "bwd_wins": False, "bwd_measured": True,
    }
    p.write_text(json.dumps({"rows": [row]}))
    monkeypatch.setenv(ps.CROSSOVER_TABLE_ENV, str(p))
    assert ps.bwd_profitable(1000, 100) is False
    row["bwd_wins"] = True
    p.write_text(json.dumps({"rows": [row]}))
    # stale cache: still the old verdict
    assert ps.bwd_profitable(1000, 100) is False
    ps.reload_crossover_table(str(p))
    assert ps.bwd_profitable(1000, 100) is True
    # path=None clears everything (env-var swap case)
    row["bwd_wins"] = False
    p.write_text(json.dumps({"rows": [row]}))
    ps.reload_crossover_table()
    assert ps.bwd_profitable(1000, 100) is False


def test_write_table_reloads_cache(tmp_path, monkeypatch):
    """roofline_segment.write_table must invalidate the in-process
    cache after writing, so measure -> write -> dispatch in one
    process sees the fresh verdicts."""
    import json
    import os
    import sys

    from hydragnn_tpu.ops import pallas_segment as ps

    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    try:
        import roofline_segment as rs
    finally:
        sys.path.pop(0)

    p = tmp_path / "w.json"
    p.write_text(json.dumps({"rows": []}))
    monkeypatch.setenv(ps.CROSSOVER_TABLE_ENV, str(p))
    assert ps.load_crossover_table(str(p)) == ()  # cache the empty table
    results = {
        ("tiny", "bfloat16"): {
            "xla_pipeline": (2.0, 0.0),
            "pallas_pipeline": (1.0, 0.0),
            "xla_pipeline_w": (2.0, 0.0),
            "pallas_pipeline_w": (2.0, 0.0),
            "pallas_fused_pipeline": (1.0, 0.0),
            "xla_bwd": (2.0, 0.0),
            "pallas_fused_bwd": (1.0, 0.0),
        }
    }
    monkeypatch.setitem(rs.SHAPES, "tiny", (100, 1000, 32))
    rs.write_table(results, str(p))
    rows = ps.load_crossover_table(str(p))  # must NOT be the stale ()
    assert len(rows) == 1
    r = rows[0]
    assert r["bwd_wins"] is True and "bwd_measured" in r
    assert r["fused_wins"] is True and r["planned_wins"] is True


# ----------------------------------------------------------------------
# Contracts of the fused pipeline that are counts, not timings: modeled
# traffic at the two classes of shape it was built for, and a train
# loop that compiles once under forced fused dispatch.
# ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "shape",
    [(33792, 4224, 128, 128), (327680, 8192, 256, 256)],
    ids=["qm9_b128", "oc20_b32"],
)
def test_modeled_traffic_fused_moves_fewer_bytes_per_flop(shape):
    """One pass over aligned tiles must move strictly fewer HBM bytes
    per model flop than reduce-then-matmul through the planned kernel:
    the arithmetic intensity ``graftboard roofline`` attributes, from
    sizes alone."""
    from hydragnn_tpu.ops.pallas_segment import modeled_pipeline_traffic

    e, n, fi, fo = shape
    fused = modeled_pipeline_traffic(e, n, fi, fo, fused=True)
    unfused = modeled_pipeline_traffic(e, n, fi, fo, fused=False)
    assert fused["model_flops"] == unfused["model_flops"]
    assert fused["bytes_per_flop"] < unfused["bytes_per_flop"]


def test_forced_fused_dispatch_train_loop_compiles_once(monkeypatch):
    """HYDRAGNN_TPU_SEGMENT_IMPL=pallas_fused sends the forward AND the
    pullback of a bf16 train step through the planned kernels. The plan
    arrays are batch data (in the vjp's residuals too): the warm epoch
    compiles one step per packed budget and three more epochs replay
    them. A compile after warm-up means a plan array was traced as a
    constant."""
    import hydragnn_tpu.ops.pallas_segment as ps
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data.graph import GraphSample
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.models.create import create_model_config, init_params
    from hydragnn_tpu.ops.neighbors import radius_graph
    from hydragnn_tpu.train.loop import _run_epoch, make_train_step
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.state import create_train_state
    from hydragnn_tpu.utils import telemetry

    rng = np.random.default_rng(0)
    samples = []
    for _ in range(64):
        n = int(rng.integers(9, 30))
        pos = rng.uniform(0, 2.2 * n ** (1 / 3), size=(n, 3))
        samples.append(
            GraphSample(
                x=rng.integers(0, 5, size=(n, 1)).astype(np.float32),
                pos=pos.astype(np.float32),
                edge_index=radius_graph(pos, 4.0, max_neighbours=32),
                y_graph=np.array([rng.normal()], np.float32),
            )
        )
    head = {
        "num_sharedlayers": 2,
        "dim_sharedlayers": 16,
        "num_headlayers": 2,
        "dim_headlayers": [16, 16],
    }
    config = {
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "SchNet",
                "radius": 4.0,
                "max_neighbours": 32,
                "num_gaussians": 8,
                "num_filters": 16,
                "hidden_dim": 16,
                "num_conv_layers": 2,
                "output_heads": {"graph": head},
                "task_weights": [1.0],
            },
            "Variables_of_interest": {
                "input_node_features": [0],
                "output_names": ["energy"],
                "output_index": [0],
                "type": ["graph"],
                "output_dim": [1],
            },
            "Training": {
                "batch_size": 8,
                "Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
            },
        }
    }
    cfgd = update_config(config, samples)
    monkeypatch.setenv("HYDRAGNN_TPU_SEGMENT_IMPL", "pallas_fused")
    calls = []
    for name in ("edge_pipeline_planned", "edge_pipeline_bwd_planned"):
        real = getattr(ps, name)

        def counting(*a, _real=real, _name=name, **kw):
            calls.append(_name)
            return _real(*a, **kw)

        monkeypatch.setattr(ps, name, counting)
    loader = GraphLoader(
        samples, 8, shuffle=True, seed=0, packing=True,
        with_segment_plan=True,
    )
    first = next(iter(loader))
    assert first.seg_window is not None, "loader attached no plan"
    model, cfg = create_model_config(cfgd)
    params, bs = init_params(model, first)
    tx = select_optimizer(cfgd["NeuralNetwork"]["Training"])
    step = make_train_step(
        model, tx, cfg, compute_dtype=jnp.bfloat16, donate=False
    )
    state = create_train_state(params, tx, bs)
    obs = telemetry.install_observer()
    try:
        for epoch in range(4):
            obs.set_phase(epoch)
            loader.set_epoch(epoch)
            state, _, _ = _run_epoch(step, state, loader, train=True)
        leaks = list(obs.post_warmup)
    finally:
        obs.close()
    assert {"edge_pipeline_planned", "edge_pipeline_bwd_planned"} <= set(calls)
    assert not leaks, leaks
