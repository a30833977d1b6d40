"""Train state and precision policy.

Precision mirrors the reference's PRECISION_MAP
(hydragnn/train/train_validate_test.py:43-109): bf16 = fp32 master params
with bf16 compute (the natural JAX policy), fp32, fp64 (enables x64).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import optax
from flax import struct
from flax.core import FrozenDict

from hydragnn_tpu.utils import tracer as tr


@struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any
    batch_stats: Any

    def apply_gradients(self, grads, tx: optax.GradientTransformation):
        with tr.scope("optimizer"):
            updates, new_opt_state = tx.update(
                grads, self.opt_state, self.params
            )
            new_params = optax.apply_updates(self.params, updates)
        return self.replace(
            step=self.step + 1, params=new_params, opt_state=new_opt_state
        )


def create_train_state(
    params, tx: optax.GradientTransformation, batch_stats=None
) -> TrainState:
    return TrainState(
        step=jnp.asarray(0, jnp.int32),
        params=params,
        opt_state=tx.init(params),
        batch_stats=batch_stats if batch_stats is not None else FrozenDict({}),
    )


PRECISIONS = ("bf16", "fp32", "fp64")


def resolve_precision(precision: str):
    """Returns (param_dtype, compute_dtype) (reference
    train_validate_test.py:52-71 resolve_precision)."""
    if precision not in PRECISIONS:
        raise ValueError(
            f"Unsupported precision {precision!r}; pick one of {PRECISIONS}"
        )
    if precision == "bf16":
        return jnp.float32, jnp.bfloat16
    if precision == "fp64":
        jax.config.update("jax_enable_x64", True)
        return jnp.float64, jnp.float64
    return jnp.float32, jnp.float32


def cast_batch(batch, compute_dtype):
    """Cast floating INPUT leaves of a GraphBatch to the compute dtype
    (reference move_batch_to_device, train_validate_test.py:74-84).

    Target fields (y_graph/y_node/energy/forces) keep full precision so
    the loss is computed against unrounded labels; under bf16 compute
    the prediction is upcast by the subtraction instead.
    """
    keep = {"y_graph", "y_node", "energy", "forces"}

    def _cast(path, x):
        if any(getattr(p, "name", None) in keep for p in path):
            return x
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(compute_dtype)
        return x

    return jax.tree_util.tree_map_with_path(_cast, batch)
