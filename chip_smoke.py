#!/usr/bin/env python3
"""The quickest proof that the program still starts on the chip.

    python chip_smoke.py                 # one TPU chip (what the driver runs)
    python chip_smoke.py --four-chips    # one host with four chips

One process drives the normal entry points end to end at the width of
the repo's headline configuration, on seeded synthetic data and random
weights, and checks what comes out. Every phase raises on failure; no
phase is wrapped in a handler that lets the run pass. The last line of
standard output is the result, and it is printed only when every phase
passed on a TPU:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Default phases (one chip): device, kernels, train, predict, serve,
rollout. ``--four-chips`` runs the multi-chip paths and what they are
compared with, and no other phase; its last line carries ``"count": 4``.

``--rehearse`` is the device-free walk-through of the same phases at a
tiny size (``on-chip-measurement`` guide, section 2): it skips the
"is this a TPU" check, never prints a result line and always exits
non-zero — a rehearsal is not a chip run. With ``--four-chips`` it needs
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# Sizes. "real" is what the driver runs; "tiny" is the CPU rehearsal.
REAL = dict(
    n_graphs=12288, hidden=128, layers=4, gaussians=50, batch=128,
    serve_requests=48, rollout_steps=64, dp_graphs=1024, dp_batch=32,
    kernel_shapes={
        # name: (num_edges, num_segments) — the crossover table's anchors
        "qm9_b128": (33792, 4224),
        "oc20_b32": (327680, 8192),
    },
    kernel_f=128,
)
TINY = dict(
    n_graphs=1400, hidden=16, layers=2, gaussians=8, batch=16,
    serve_requests=12, rollout_steps=32, dp_graphs=128, dp_batch=4,
    kernel_shapes={"tiny_a": (1300, 160), "tiny_b": (2100, 600)},
    kernel_f=32,
)

# Kernel-vs-XLA tolerances: |got - ref| <= rtol*|ref| + atol + scale*rms(ref).
# rtol and atol are the documented contract of tests/test_pallas_segment.py:
# f32 differs only by how the block decomposition regroups the f32 adds
# (d_w sums E products per element, hence the looser of the two f32 gates);
# bf16 is held to a few bf16 ulps against the SAME-dtype XLA reference. The
# rms term exists because of what the chip does and interpret mode does
# not: on the MXU the kernel's DEFAULT-precision reduce rounds the dense
# product h to bf16 before summing (2^-8 relative per term), the XLA path
# does not, so the sum's error scales with the ACCUMULATED magnitude
# (~rms of the output), not with each element's own — at F=128 that is
# rms 30-110, where the suite's atol was set at CPU magnitudes of ~20.
KERNEL_TOL = {
    "float32": dict(rtol=1e-4, atol=1e-3, scale=1e-5),
    "bfloat16": dict(rtol=4e-2, atol=2.5e-1, scale=2e-2),
}


def log(msg: str) -> None:
    print(msg, flush=True)


class Phase:
    """Prints a phase's start and its wall time; never swallows."""

    def __init__(self, name: str, times: dict):
        self.name, self.times = name, times

    def __enter__(self):
        log(f"== {self.name}")
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self.t0
        if exc_type is None:
            self.times[self.name] = round(dt, 1)
            log(f"== {self.name} ok in {dt:.1f}s")
        else:
            log(f"== {self.name} FAILED after {dt:.1f}s")
        return False


# ----------------------------------------------------------------------
# device
# ----------------------------------------------------------------------


def phase_device(want_count: int, rehearse: bool) -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    from hydragnn_tpu import native
    from hydragnn_tpu.utils.runtime import maybe_enable_compilation_cache

    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    log(f"device: {json.dumps(device)}")
    log(f"versions: jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu}, python {sys.version.split()[0]}")
    if not rehearse and device["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: platform is {device['platform']!r}, not a TPU"
        )
    if device["count"] != want_count:
        raise SystemExit(
            f"chip_smoke: this run needs {want_count} device(s), "
            f"jax sees {device['count']}"
        )
    log(f"compile cache: {maybe_enable_compilation_cache() or 'off'}")
    # a failed build must fail here, not fall back to numpy neighbours
    if not native.available():
        raise SystemExit("chip_smoke: the native library did not build")
    log("native library: built")
    return device


# ----------------------------------------------------------------------
# kernels
# ----------------------------------------------------------------------


def phase_kernels(size: dict, seed: int, on_tpu: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hydragnn_tpu.ops import pallas_segment as ps
    from hydragnn_tpu.ops.segment import segment_sum

    log(f"tolerance vs the XLA path, |got-ref| <= rtol*|ref| + atol + "
        f"scale*rms(ref): {KERNEL_TOL} — f32: the block decomposition "
        "regroups f32 adds; bf16: a few ulps against the same-dtype "
        "reference, and the MXU rounds the dense product to bf16 before "
        "the reduce, so the error scales with the accumulated magnitude")

    @jax.jit
    def compare(got, ref, rtol, atol, scale):
        got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
        rms = jnp.sqrt(jnp.mean(ref * ref))
        diff = jnp.abs(got - ref)
        bad = diff > rtol * jnp.abs(ref) + atol + scale * rms
        return (
            jnp.sum(bad), jnp.max(diff), rms, jnp.all(jnp.isfinite(got))
        )

    failures = []
    f = size["kernel_f"]
    rng = np.random.default_rng(seed)
    for shape_name, (e, n) in size["kernel_shapes"].items():
        seg = np.sort(rng.integers(0, n, e)).astype(np.int32)
        plan_np = ps.plan_blocks_static(seg, n, ps.static_block_bound(e, n))
        plan = tuple(jnp.asarray(p) for p in plan_np)
        seg_d = jnp.asarray(seg)
        a32 = rng.normal(size=(e, f)).astype(np.float32)
        b32 = rng.normal(size=(e, f)).astype(np.float32)
        w = jnp.asarray(rng.normal(size=(f, f)).astype(np.float32))
        g32 = rng.normal(size=(n, f)).astype(np.float32)
        for dtype in ("float32", "bfloat16"):
            tol = KERNEL_TOL[dtype]
            a = jnp.asarray(a32, dtype)
            b = jnp.asarray(b32, dtype)
            for variant in ("reduce", "product", "fused"):
                bb = b if variant != "reduce" else None
                ww = w if variant == "fused" else None  # f32 master weight

                def fwd_kernel(x, y, z):
                    return ps.edge_pipeline_planned(x, y, z, *plan, n)

                def fwd_xla(x, y, z):
                    msg = x if y is None else x * y
                    if z is not None:
                        msg = msg @ z
                    return segment_sum(msg, seg_d, n)

                def bwd_kernel(gg, x, y, z):
                    return ps.edge_pipeline_bwd_planned(
                        gg, x, y, z, *plan, n
                    )

                def bwd_xla(gg, x, y, z):
                    return ps._edge_pipeline_bwd_xla(
                        x, y, z, *plan[:3], gg
                    )

                g = jnp.asarray(g32, jax.eval_shape(fwd_xla, a, bb, ww).dtype)
                for direction, kern, ref_fn, args in (
                    ("fwd", fwd_kernel, fwd_xla, (a, bb, ww)),
                    ("bwd", bwd_kernel, bwd_xla, (g, a, bb, ww)),
                ):
                    lowered = jax.jit(kern).lower(*args)
                    has_call = "tpu_custom_call" in lowered.as_text()
                    if on_tpu and not has_call:
                        raise RuntimeError(
                            f"{shape_name}/{variant}/{direction}/{dtype}: "
                            "no tpu_custom_call in the lowered text — "
                            "the kernel is not going through Mosaic"
                        )
                    got = lowered.compile()(*args)
                    # the XLA side at full f32 matmul precision: on a TPU
                    # its default would round f32 operands to bf16, and
                    # the kernel (HIGHEST for f32 data) would be compared
                    # with the less exact of the two
                    with jax.default_matmul_precision("highest"):
                        ref = jax.jit(ref_fn)(*args)
                    # absent operands come back as None: not leaves
                    got_l = jax.tree_util.tree_leaves(got)
                    ref_l = jax.tree_util.tree_leaves(ref)
                    label = f"{shape_name}/{variant}/{direction}/{dtype}"
                    worst, worst_rms, n_bad = 0.0, 0.0, 0
                    for gt, rf in zip(got_l, ref_l):
                        bad, mx, rms, finite = compare(
                            gt, rf, tol["rtol"], tol["atol"], tol["scale"]
                        )
                        if not bool(finite):
                            failures.append(f"{label}: non-finite output")
                        n_bad += int(bad)
                        if float(mx) > worst:
                            worst, worst_rms = float(mx), float(rms)
                    if n_bad:
                        failures.append(
                            f"{label}: {n_bad} elements out of tolerance, "
                            f"max|diff|={worst:.3e} at rms {worst_rms:.3e}"
                        )
                    log(f"kernel {shape_name} E={e} N={n} F={f} {dtype:8s} "
                        f"{variant:7s} {direction}: custom_call={has_call} "
                        f"max|diff|={worst:.3e} (rms {worst_rms:.3e}) "
                        f"{'ok' if not n_bad else 'OUT OF TOLERANCE'}")
    if failures:
        raise RuntimeError(
            "kernels disagree with the XLA path:\n  " + "\n  ".join(failures)
        )


# ----------------------------------------------------------------------
# data and configuration
# ----------------------------------------------------------------------


def make_molecules(n_graphs: int, seed: int):
    """Seeded synthetic QM9-sized molecules: 9-29 atoms of 5 species at
    random positions in a box of molecular density, radius graph at 4 A
    with at most 32 neighbours. The label is a smooth function of
    species and geometry, standardized — learnable, so a falling loss
    means the optimizer works."""
    import numpy as np

    from hydragnn_tpu.data.graph import GraphSample
    from hydragnn_tpu.ops.neighbors import radius_graph

    rng = np.random.default_rng(seed)
    species_energy = rng.normal(size=5)
    samples, labels = [], []
    for _ in range(n_graphs):
        n = int(rng.integers(9, 30))
        pos = rng.uniform(0, 2.2 * n ** (1 / 3), size=(n, 3))
        z = rng.integers(0, 5, size=n)
        ei = radius_graph(pos, 4.0, max_neighbours=32)
        d = np.linalg.norm(pos[ei[0]] - pos[ei[1]], axis=1)
        labels.append(
            species_energy[z].mean() + 0.3 * np.exp(-d).sum() / n
        )
        samples.append(
            GraphSample(
                x=z.astype(np.float32)[:, None],
                pos=pos.astype(np.float32),
                edge_index=ei,
            )
        )
    labels = np.asarray(labels)
    labels = (labels - labels.mean()) / labels.std()
    for s, y in zip(samples, labels):
        s.y_graph = np.array([y], np.float32)
    return samples


def schnet_config(size: dict, name: str, epochs: int = 2) -> dict:
    """A SchNet at the PyG QM9 widths with every Training option at
    its default: fp32, pipeline feed, packing auto, superstep
    auto, use_segment_plan auto."""
    h = size["hidden"]
    return {
        "Verbosity": {"level": 1},
        "Dataset": {"name": name},
        "NeuralNetwork": {
            "Architecture": {
                "mpnn_type": "SchNet",
                "radius": 4.0,
                "max_neighbours": 32,
                "num_gaussians": size["gaussians"],
                "num_filters": h,
                "hidden_dim": h,
                "num_conv_layers": size["layers"],
                "output_heads": {
                    "graph": {
                        "num_sharedlayers": 2,
                        "dim_sharedlayers": h,
                        "num_headlayers": 2,
                        "dim_headlayers": [h, h],
                    }
                },
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
                "num_epoch": epochs,
                "batch_size": size["batch"],
                "Checkpoint": True,
                "Optimizer": {"type": "AdamW", "learning_rate": 1e-3},
            },
        },
    }


# ----------------------------------------------------------------------
# train
# ----------------------------------------------------------------------

_SCATTER_SIG = re.compile(
    r"\}\) : \(tensor<(\d+)x(\d+)xf32>, tensor<(\d+)x1xi32>, "
    r"tensor<(\d+)x(\d+)xf32>\) -> tensor"
)
_CALL_SIG = re.compile(
    r"custom_call @tpu_custom_call\(.*?\) \{.*?\} : \((.*?)\) -> "
    r"tensor<(\d+)x(\d+)xf32>",
    re.S,
)


def _check_dispatch_in_dump(dump_dir: str, width: int) -> dict:
    """Read the train-step programs jax lowered during training and hold
    each to the crossover table: a program whose padded (E, N) the
    table's verdict sends to the planned kernel must contain the Pallas
    custom call, and a program it keeps on the XLA scatter must not."""
    from hydragnn_tpu.ops.segment import planned_path_wanted

    seen = {}
    for path in sorted(glob.glob(os.path.join(dump_dir, "*.mlir"))):
        with open(path) as fh:
            text = fh.read()
        # the train step and its K-scan twin are the programs that both
        # aggregate edges at the model's width and update the optimizer
        base = os.path.basename(path)
        if not re.search(r"_jit_(step|superstep)_", base):
            continue
        calls = _CALL_SIG.findall(text)
        if calls:
            # operands: scalar-prefetch ints, seg tiles, then a [E, F]
            operands, n_pad, _ = calls[0]
            e = max(
                int(m.group(1))
                for m in re.finditer(
                    rf"tensor<(\d+)x{width}xf32>", operands
                )
            )
            n, has_call = int(n_pad), True
        else:
            sigs = [
                (int(m.group(1)), int(m.group(4)))
                for m in _SCATTER_SIG.finditer(text)
                if int(m.group(2)) == width and int(m.group(5)) == width
            ]
            if not sigs:
                continue  # an eval step of another width, or no model
            n, e = max(sigs, key=lambda s: s[1])
            has_call = False
        wanted = planned_path_wanted(e, n)
        seen[base] = (e, n, has_call, wanted)
        if has_call != wanted:
            raise RuntimeError(
                f"{base}: padded E={e} N={n}: the crossover table's "
                f"verdict is planned={wanted}, the lowered step has "
                f"custom_call={has_call}"
            )
    if not seen:
        raise RuntimeError(
            f"no train-step program found among the dumps in {dump_dir}"
        )
    for base, (e, n, has_call, wanted) in seen.items():
        log(f"dispatch {base}: E={e} N={n} verdict planned={wanted} "
            f"custom_call={has_call}")
    return seen


def _train_once(config, splits, seed, dump_dir=None):
    """One ``run_training`` under a compile observer. Returns
    (state, model, cfg, history, full_config, observer)."""
    import jax

    import hydragnn_tpu
    from hydragnn_tpu.utils import telemetry

    obs = telemetry.install_observer()
    if dump_dir is not None:
        jax.config.update("jax_dump_ir_to", dump_dir)
    try:
        out = hydragnn_tpu.run_training(config, datasets=splits, seed=seed)
    finally:
        obs.close()
        if dump_dir is not None:
            jax.config.update("jax_dump_ir_to", None)
    return (*out, obs)


def phase_train(size: dict, seed: int, work: str):
    import numpy as np

    from hydragnn_tpu.data.loader import split_dataset

    t0 = time.perf_counter()
    samples = make_molecules(size["n_graphs"], seed)
    splits = split_dataset(samples, 0.8)
    log(f"data: {len(samples)} seeded synthetic QM9-sized graphs "
        f"({sum(s.num_nodes for s in samples) / len(samples):.1f} atoms, "
        f"{sum(s.edge_index.shape[1] for s in samples) / len(samples):.1f} "
        f"edges mean) in {time.perf_counter() - t0:.1f}s; "
        f"split {[len(s) for s in splits]}")
    log(f"model: SchNet hidden {size['hidden']}, {size['hidden']} filters, "
        f"{size['gaussians']} Gaussians, {size['layers']} interaction "
        f"layers, batch {size['batch']}, fp32. "
        "Hidden/filters/Gaussians are the "
        "PyG SchNet defaults HydraGNN wraps for QM9 (128/128/50, from "
        "memory: no network here); the published depth is 6 interaction "
        "blocks at a 10 A cutoff, cut here to 4 layers at 4 A / 32 "
        "neighbours: a smoke run, not the benchmark's schnet_qm9.")

    dump = os.path.join(work, "ir")
    config = schnet_config(size, "chip_smoke")
    state, model, cfg, hist, full, obs = _train_once(
        config, splits, seed, dump_dir=dump
    )
    losses = [float(x) for x in hist.train_loss]
    log(f"train loss by epoch {losses}; val {list(map(float, hist.val_loss))}")
    log(f"compilations {obs.compile_count} ({obs.compile_ms / 1e3:.1f}s), "
        f"persistent-cache hits {obs.cache_hits} misses {obs.cache_misses}")
    if not np.all(np.isfinite(losses + list(hist.val_loss))):
        raise RuntimeError(f"non-finite loss: {losses} {hist.val_loss}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"train loss did not fall: {losses}")
    if obs.post_warmup:
        raise RuntimeError(
            f"{len(obs.post_warmup)} compilation(s) after epoch 1: "
            f"{obs.post_warmup}"
        )
    log("zero compilations in epoch 2")
    _check_dispatch_in_dump(dump, size["hidden"])

    # The same run on the single-thread feed. The pipeline recycles its
    # packed host buffers on non-CPU backends on the belief that H2D
    # always copies; an async device_put that still read a recycled
    # buffer would show here as a different loss history.
    config0 = schnet_config(size, "chip_smoke_w0")
    config0["NeuralNetwork"]["Training"]["Parallelism"] = {
        "pipeline": {"workers": 0}
    }
    *_, hist0, _, obs0 = _train_once(config0, splits, seed)
    losses0 = [float(x) for x in hist0.train_loss]
    log(f"workers=0 train loss by epoch {losses0}; persistent-cache hits "
        f"{obs0.cache_hits} misses {obs0.cache_misses}")
    if losses0 != losses or list(hist0.val_loss) != list(hist.val_loss):
        raise RuntimeError(
            "loss history differs between the pipeline feed and "
            f"workers=0: {losses} / {losses0}; val {hist.val_loss} / "
            f"{hist0.val_loss}"
        )
    log("loss history identical between the pipeline feed and workers=0")
    return state, model, cfg, hist, full, splits


# ----------------------------------------------------------------------
# predict, serve, rollout
# ----------------------------------------------------------------------


def phase_predict(full: dict, hist, splits):
    """``run_prediction`` from the checkpoint on disk. The validation
    split stands in as the test split first: the error must then be the
    run's last validation loss but for batch shapes (the eval feed packs,
    prediction does not), which proves the checkpoint round trip."""
    import numpy as np

    import hydragnn_tpu

    tr, va, te = splits
    err_val, *_ = hydragnn_tpu.run_prediction(full, datasets=(tr, va, va))
    val = float(hist.val_loss[-1])
    log(f"prediction on the validation split from disk: {float(err_val):.6f}"
        f"; last validation loss of the run: {val:.6f}")
    if abs(float(err_val) - val) > 1e-3 * max(abs(val), 1e-6):
        raise RuntimeError(
            f"checkpoint on disk gives {err_val} on the validation "
            f"split, the run ended at {val} (tolerance 1e-3 relative: "
            "same weights, other batch shapes)"
        )
    err, tasks, trues, preds = hydragnn_tpu.run_prediction(
        full, datasets=splits
    )
    log(f"prediction on the test split: {float(err):.6f}")
    if not np.isfinite(err) or abs(float(err) - val) > 0.5 * max(err, val):
        raise RuntimeError(
            f"test error {err} is not close to the validation loss {val}"
        )
    if np.asarray(preds[0]).shape != (len(te), 1):
        raise RuntimeError(f"prediction shape {np.asarray(preds[0]).shape}")
    return np.asarray(preds[0])


def phase_serve(size, state, model, cfg, splits, preds) -> None:
    """A ``ServingEngine`` on the trained state answers requests of mixed
    sizes through two pack budgets; per-graph outputs must match
    ``run_prediction`` for the same graphs."""
    import numpy as np

    from hydragnn_tpu.data.graph import PackSpec
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.serve import DynamicBatcher, ServingEngine
    from hydragnn_tpu.serve.engine import ServingSettings

    te = splits[2]
    n_req = min(size["serve_requests"], len(te))
    fspec = GraphLoader(te, size["batch"])._fixed_batch_spec()
    big = PackSpec(
        num_nodes=fspec.num_nodes,
        num_edges=fspec.num_edges,
        num_graphs=fspec.num_graphs,
    )
    small = PackSpec(
        num_nodes=max(big.num_nodes // 8, 40) // 8 * 8,
        num_edges=max(big.num_edges // 8, 1024) // 8 * 8,
        num_graphs=max(big.num_graphs // 8, 4),
    )
    budgets = [small, big]
    engine = ServingEngine(
        model, cfg, state, budgets, example=te[0],
        settings=ServingSettings(enabled=True),
    )
    batcher = DynamicBatcher(budgets, deadline_ms=1e3, max_open_bins=2)
    try:
        reqs = [batcher.submit(s) for s in te[:n_req]]
        batcher.close()
        engine.process(batcher, timeout=0.05)
        served = np.stack([np.asarray(r.result[0]) for r in reqs])
    finally:
        batcher.close()
        engine.close()
    ref = preds[:n_req]
    sizes = sorted({int(s.num_nodes) for s in te[:n_req]})
    diff = float(np.max(np.abs(served.reshape(ref.shape) - ref)))
    log(f"served {n_req} requests of {len(sizes)} sizes "
        f"({sizes[0]}-{sizes[-1]} atoms) in {engine.dispatches} dispatches "
        f"over budgets {[(b.num_nodes, b.num_edges) for b in budgets]}; "
        f"max|served - run_prediction| = {diff:.3e} (tolerance 1e-4: "
        "same weights, other padded shapes)")
    if not np.all(np.isfinite(served)) or diff > 1e-4:
        raise RuntimeError(
            f"served outputs differ from run_prediction by {diff}"
        )


def phase_rollout(size) -> None:
    """``RolloutEngine`` over the MD drill potential of
    ``__graft_entry__`` for a few macro steps."""
    import numpy as np

    import __graft_entry__ as entry

    steps = size["rollout_steps"]
    eng = entry._md_engine(
        entry._md_potential(), steps=steps, superstep_k=16
    )
    res = eng.run(eng.init_state())
    total = res.energies + res.kinetic
    log(f"rollout: {res.stats['steps']} steps in {res.stats['macros']} "
        f"macro dispatches, {res.stats['rebuilds']} rebuilds, events "
        f"{res.stats['events']}, capacity growths "
        f"{res.stats['capacity_growths']}, total energy "
        f"{float(total[0]):.6f} -> {float(total[-1]):.6f}")
    if res.stats["steps"] != steps or not np.all(np.isfinite(total)):
        raise RuntimeError(f"rollout degraded: {res.stats}")
    if res.stats["events"] or res.stats["capacity_growths"]:
        raise RuntimeError(
            f"rollout raised the overflow/non-finite flag: {res.stats}"
        )


# ----------------------------------------------------------------------
# four chips
# ----------------------------------------------------------------------


def _distinct_devices(x) -> int:
    return len({s.device for s in x.addressable_shards})


def phase_four_chips(size: dict, seed: int) -> None:
    """``run_training`` on ``data=4`` and on ``data=2,fsdp=2`` against a
    one-device run of the same steps (same seed, same global batch, no
    packing so every optimizer step sees the same graphs), then one step
    each of multibranch and of graph-sharded ring attention."""
    import jax
    import numpy as np

    import __graft_entry__ as entry
    from hydragnn_tpu.data.loader import GraphLoader, split_dataset
    from hydragnn_tpu.parallel import runtime
    from hydragnn_tpu.parallel.dp import DPLoader

    samples = make_molecules(size["dp_graphs"], seed)
    splits = split_dataset(samples, 0.8)
    per_dev = size["dp_batch"]
    tol = 2e-3
    log(f"loss tolerance {tol} relative: the same graphs enter every "
        "optimizer step, but the mean over the global batch is taken as "
        "a weighted all-reduce of four per-device means, and two epochs "
        "of AdamW carry that reassociation forward")

    def run(name, parallelism, batch):
        cfg = schnet_config(size, name)
        tr = cfg["NeuralNetwork"]["Training"]
        tr["batch_size"] = batch
        tr["Checkpoint"] = False
        tr["Parallelism"] = dict(parallelism, packing={"enabled": False})
        state, _, _, hist, full, obs = _train_once(cfg, splits, seed)
        losses = [float(x) for x in hist.train_loss]
        log(f"{name}: train loss {losses} val "
            f"{list(map(float, hist.val_loss))}; compilations "
            f"{obs.compile_count}, after epoch 1: {len(obs.post_warmup)}")
        if not np.all(np.isfinite(losses)):
            raise RuntimeError(f"{name}: non-finite loss {losses}")
        return state, losses, full

    _, ref, _ = run("smoke_one_device", {"scheme": "single"}, 4 * per_dev)
    for name, par in (
        ("smoke_dp4", {"scheme": "dp", "data": 4}),
        ("smoke_dp2_fsdp2", {"scheme": "dp", "data": 2, "fsdp": 2}),
    ):
        batch = 4 * per_dev // par["data"]
        state, losses, full = run(name, par, batch)
        rel = max(abs(a - b) / abs(b) for a, b in zip(losses, ref))
        log(f"{name}: max relative loss difference to one device {rel:.2e}")
        if rel > tol:
            raise RuntimeError(
                f"{name}: losses {losses} differ from the one-device "
                f"run's {ref} by {rel:.2e} > {tol}"
            )
        plan = runtime.plan_from_config(full)
        stacked = next(iter(DPLoader(
            GraphLoader(splits[0], batch, fixed_pad=True), plan.mesh
        )))
        n_batch = _distinct_devices(stacked.x)
        leaves = jax.tree_util.tree_leaves(state.params)
        n_param = max(_distinct_devices(p) for p in leaves)
        log(f"{name}: mesh {dict(plan.mesh.shape)}; batch.x "
            f"{stacked.x.shape} on {n_batch} devices "
            f"(shard {stacked.x.addressable_shards[0].data.shape}); "
            f"params on {n_param} devices")
        if n_batch != 4 or n_param != 4:
            raise RuntimeError(
                f"{name}: batch on {n_batch} and params on {n_param} "
                "distinct devices, expected 4 and 4"
            )
        if "fsdp" in par:
            sharded = [
                p for p in leaves
                if p.addressable_shards[0].data.shape != p.shape
            ]
            log(f"{name}: {len(sharded)}/{len(leaves)} parameter leaves "
                "are fsdp-sharded (shard smaller than the leaf)")
            if not sharded:
                raise RuntimeError(
                    f"{name}: no parameter leaf is actually sharded"
                )
    entry._dryrun_multibranch(4)
    entry._dryrun_graphshard(4)


# ----------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run the four-chip paths and what they are compared with, "
        "and no other phase",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="device-free walk-through at a tiny size; never prints a "
        "result, always exits non-zero",
    )
    args = ap.parse_args(argv)
    size = TINY if args.rehearse else REAL
    t_start = time.perf_counter()
    times: dict = {}

    # Scratch for this run inside the checkout: run logs, checkpoints
    # and the lowered-IR dump. Relative run paths (logs/<name>/...)
    # resolve here.
    work = os.path.join(REPO, ".chip_smoke_work")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    os.chdir(work)

    with Phase("device", times):
        device = phase_device(4 if args.four_chips else 1, args.rehearse)
    on_tpu = device["platform"] == "tpu"
    if args.four_chips:
        with Phase("four-chips", times):
            phase_four_chips(size, args.seed)
    else:
        with Phase("kernels", times):
            phase_kernels(size, args.seed, on_tpu)
        with Phase("train", times):
            state, model, cfg, hist, full, splits = phase_train(
                size, args.seed, work
            )
        with Phase("predict", times):
            preds = phase_predict(full, hist, splits)
        with Phase("serve", times):
            phase_serve(size, state, model, cfg, splits, preds)
        with Phase("rollout", times):
            phase_rollout(size)
    log(f"phase seconds: {json.dumps(times)}; total "
        f"{time.perf_counter() - t_start:.1f}s")
    if args.rehearse:
        log("rehearsal walked every phase; not a chip run, no result")
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
