"""Device-free AOT compiles for a described TPU v5e: the kernels of the
main path at real widths, and the SchNet train step at chip_smoke's
shapes. The TPU's compiler is installed here and compiles for a chip
that is described, not attached — what it refuses here it refuses on
the chip, where the refusal would cost chip time (these kernels passed
every interpret-mode test while Mosaic refused all of them).

Nothing runs: a compile that passes is not a chip run. ``python
chip_smoke.py`` executes the same programs on the chip.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU's library, and
every xdist worker imports every test file. All of these tests stay in
this one file so the worker that is handed it is the one that loads the
library.
"""

import os

import numpy as np
import pytest

import tests._cpu  # noqa: F401

import jax
import jax.numpy as jnp

from hydragnn_tpu.ops import pallas_segment as ps

# (num_edges, num_segments): the crossover table's QM9-class and
# OC20-class anchors — the shapes the dispatch votes between.
SHAPES = {"qm9": (33792, 4224), "oc20": (327680, 8192)}
F = 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip (the next one warns
    and compiles again): keep the cache off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_on_tpu(monkeypatch, no_persistent_cache):
    """Steer the code that asks which backend it runs on: with a
    described device ``jax.default_backend()`` still says "cpu", which
    would put the kernels in interpret mode and the dispatch on the XLA
    scatter. The switch is steered here, in the test — the program has
    no option for it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ps._interpret() is False


def _shapes(one_chip, shape, dtype, variant):
    e, n = SHAPES[shape]
    blocks = ps.static_block_bound(e, n)

    def sds(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    a = sds((e, F), dtype)
    b = sds((e, F), dtype) if variant != "reduce" else None
    w = sds((F, F), jnp.float32) if variant == "fused" else None
    plan = (
        sds((blocks * ps.DEFAULT_BE,), jnp.int32),  # perm
        sds((blocks * ps.DEFAULT_BE,), jnp.int32),  # seg_padded
        sds((blocks * ps.DEFAULT_BE,), jnp.bool_),  # valid
        sds((blocks,), jnp.int32),  # window_id
    )
    return e, n, a, b, w, plan


def _compile_with_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("variant", ["reduce", "product", "fused"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_forward_kernel_compiles_for_v5e(
    one_chip, as_on_tpu, shape, variant, dtype
):
    e, n, a, b, w, plan = _shapes(one_chip, shape, dtype, variant)
    _compile_with_kernel(
        lambda a, b, w, *plan: ps.edge_pipeline_planned(a, b, w, *plan, n),
        a, b, w, *plan,
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("variant", ["reduce", "product", "fused"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_backward_kernel_compiles_for_v5e(
    one_chip, as_on_tpu, shape, variant, dtype
):
    e, n, a, b, w, plan = _shapes(one_chip, shape, dtype, variant)
    g_dtype = jnp.float32 if variant == "fused" else dtype
    g = jax.ShapeDtypeStruct((n, F), g_dtype, sharding=one_chip)
    _compile_with_kernel(
        lambda g, a, b, w, *plan: ps.edge_pipeline_bwd_planned(
            g, a, b, w, *plan, n
        ),
        g, a, b, w, *plan,
    )


@pytest.mark.parametrize("feed", ["step", "superstep"])
def test_schnet_train_step_compiles_for_v5e(
    one_chip, no_persistent_cache, monkeypatch, feed
):
    """The whole SchNet train step at chip_smoke's width, batch and
    packed shapes — per-step and as the K-scan the default feed
    dispatches — with the dispatch taking the table's verdict as it
    does on a TPU: the planned kernel must be inside the compiled
    step."""
    import chip_smoke

    from hydragnn_tpu.config import update_config
    from hydragnn_tpu.data.graph import stack_batches
    from hydragnn_tpu.data.loader import GraphLoader
    from hydragnn_tpu.models.create import create_model_config, init_params
    from hydragnn_tpu.train.loop import (
        make_superstep_fn,
        make_train_step,
        superstep_task_count,
    )
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.state import create_train_state

    size = chip_smoke.REAL
    samples = chip_smoke.make_molecules(8 * size["batch"], seed=0)
    config = update_config(chip_smoke.schnet_config(size, "aot"), samples)
    model, cfg = create_model_config(config)
    loader = GraphLoader(
        samples, size["batch"], shuffle=True, seed=0, packing=True,
        with_segment_plan="auto",
    )
    with monkeypatch.context() as on_tpu:
        # the loader's attach vote asks for the backend too
        on_tpu.setattr(jax, "default_backend", lambda: "tpu")
        batch = next(iter(loader))
    assert batch.seg_window is not None, (
        "the table's verdict attaches no plan at the smoke's shape "
        f"E={batch.num_edges} N={batch.num_nodes}"
    )
    params, bs = init_params(model, batch)
    tx = select_optimizer(config["NeuralNetwork"]["Training"])
    state = create_train_state(params, tx, bs)

    # the model was initialized on the CPU, as the CPU; from here on the
    # program is lowered for the described chip, as on a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                np.shape(x), np.asarray(x).dtype, sharding=one_chip
            ),
            tree,
        )

    if feed == "step":
        lowered = make_train_step(model, tx, cfg).lower(
            described(state), described(batch)
        )
    else:
        macro = stack_batches([batch] * 8)
        acc = (
            jnp.zeros((), jnp.float32),
            jnp.zeros((superstep_task_count(cfg),), jnp.float32),
            jnp.zeros((), jnp.float32),
        )
        lowered = make_superstep_fn(model, tx, cfg, train=True).lower(
            described(state), described(acc), described(macro.batch)
        )
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
