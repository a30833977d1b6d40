#!/usr/bin/env python3
"""Compile a cell's train step for a described v5e chip, with no chip, and
read ``memory_analysis()``: how a batch size is sized before chip time is
spent (on-chip-measurement guide, section 2.3).

    JAX_PLATFORMS=cpu python3 benchmarks/aot_memory.py --workload <cell> [--batch N]

Builds the cell's data and loader as ``run_training`` does, takes the first
padded batch, and lowers the program's single step and its K-step scan for
``v5e:2x2`` device 0. Prints argument, output, temporary and total bytes,
and whether the lowered step holds a ``tpu_custom_call``. Nothing runs on
a device: this is a count, never a measurement.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--n-graphs", type=int, default=None,
                    help="generate fewer graphs (the fit wants the whole set)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import spec

    cell = spec.cell(args.workload)
    for key, value in cell["extras"].get("env", {}).items():
        os.environ[key] = str(value)
    if args.batch:
        cell["traffic"]["batch_size"] = args.batch
    if args.n_graphs:
        cell["traffic"]["params"]["n_graphs"] = args.n_graphs
    driver = spec.load_module("drivers", cell["traffic"]["driver"])
    gen = spec.load_module("generators", cell["traffic"]["generator"])
    splits = gen.make(0, **cell["traffic"]["params"])
    train = driver.to_samples(splits["train"])

    from hydragnn_tpu import runner
    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data.graph import stack_batches
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.models.create import create_model_config, init_params
    from hydragnn_tpu.parallel import runtime
    from hydragnn_tpu.train.loop import (
        make_superstep_fn, make_train_step, superstep_task_count,
    )
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.state import create_train_state

    config = driver.build_config(cell, "/tmp/unused", False)
    config = update_config(config, train)
    plan = runtime.plan_from_config(config)
    batch_size = int(cell["traffic"]["batch_size"])
    on, budgets, slack = runner._resolve_packing(
        plan, False, batch_size, train, 0, fixed_pad="auto", seed=0
    )
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"  # the loader's and the dispatch's vote
    try:
        loader = GraphLoader(
            train, batch_size, shuffle=True, seed=0,
            fixed_pad=runner._resolve_fixed_pad(plan.scheme, 0),
            with_segment_plan="auto", packing=on, pack_budgets=budgets,
            pack_max_budgets=plan.packing_max_budgets, pack_slack=slack,
            pack_max_graphs=plan.packing_max_graphs,
        )
        batch = next(iter(loader))
        jax.default_backend = real_backend
        model, cfg = create_model_config(config)
        params, bs = init_params(model, batch)
        tx = select_optimizer(config["NeuralNetwork"]["Training"])
        state = create_train_state(params, tx, bs)
        jax.default_backend = lambda: "tpu"
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        chip = SingleDeviceSharding(topo.devices[0])

        def described(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    np.shape(x), np.asarray(x).dtype, sharding=chip
                ),
                tree,
            )

        k = plan.superstep_steps if isinstance(plan.superstep_steps, int) else 8
        print(f"padded batch: N={batch.node_mask.shape[-1]} "
              f"E={batch.edge_mask.shape[-1]} G={batch.graph_mask.shape[-1]}; "
              f"segment plan attached: {batch.seg_window is not None}; K={k}")
        acc = (
            jnp.zeros((), jnp.float32),
            jnp.zeros((superstep_task_count(cfg),), jnp.float32),
            jnp.zeros((), jnp.float32),
        )
        macro = stack_batches([batch] * k)
        for name, lowered in (
            ("step", make_train_step(model, tx, cfg).lower(
                described(state), described(batch))),
            ("superstep", make_superstep_fn(model, tx, cfg, train=True).lower(
                described(state), described(acc), described(macro.batch))),
        ):
            compiled = lowered.compile()
            m = compiled.memory_analysis()
            total = (m.argument_size_in_bytes + m.output_size_in_bytes
                     + m.temp_size_in_bytes - m.alias_size_in_bytes)
            print(f"{name}: arguments {m.argument_size_in_bytes} outputs "
                  f"{m.output_size_in_bytes} temporaries {m.temp_size_in_bytes} "
                  f"aliased {m.alias_size_in_bytes} total {total} bytes "
                  f"({total / 2**30:.2f} GiB, {100 * total / 16 / 2**30:.1f}% of "
                  f"16 GiB); tpu_custom_call in the step: "
                  f"{'tpu_custom_call' in compiled.as_text()}")
    finally:
        jax.default_backend = real_backend
    return 0


if __name__ == "__main__":
    sys.exit(main())
