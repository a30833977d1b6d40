"""Loss functions and multihead task-weighted loss.

Registry mirrors the reference's ``loss_function_selection``
(hydragnn/utils/model/model.py:30-43): mse / mae / smooth_l1 / rmse /
GaussianNLLLoss. The multihead combination reimplements
``Base.loss_hpweighted`` (hydragnn/models/Base.py:879-906): per-task
losses weighted by |w|-normalized task weights, computed over masked
(real) graphs/nodes only.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from hydragnn_tpu.data.graph import GraphBatch
from hydragnn_tpu.models.spec import ModelConfig
from hydragnn_tpu.utils import tracer as tr


def masked_mean(err: jax.Array, mask: jax.Array) -> jax.Array:
    m = mask.astype(err.dtype)
    if err.ndim > 1:
        m = m.reshape(m.shape + (1,) * (err.ndim - 1))
    denom = jnp.maximum(jnp.sum(m) * (err.size / mask.size), 1.0)
    return jnp.sum(err * m) / denom


def elementwise_loss(kind: str, pred: jax.Array, target: jax.Array) -> jax.Array:
    if kind == "mse":
        return (pred - target) ** 2
    if kind == "mae":
        return jnp.abs(pred - target)
    if kind == "smooth_l1":
        d = jnp.abs(pred - target)
        return jnp.where(d < 1.0, 0.5 * d * d, d - 0.5)
    raise ValueError(f"Unknown loss function: {kind}")


def head_loss(
    kind: str,
    pred: jax.Array,
    target: jax.Array,
    mask: jax.Array,
    var: Optional[jax.Array] = None,
) -> jax.Array:
    if kind == "rmse":
        return jnp.sqrt(masked_mean(elementwise_loss("mse", pred, target), mask))
    if kind == "GaussianNLLLoss":
        v = jnp.maximum(var, 1e-6)
        nll = 0.5 * (jnp.log(v) + (pred - target) ** 2 / v)
        return masked_mean(nll, mask)
    return masked_mean(elementwise_loss(kind, pred, target), mask)


@tr.scoped("loss")
def multihead_loss(
    outputs: List[jax.Array], batch: GraphBatch, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array]:
    """Task-weighted total loss + per-task losses.

    ``outputs[h]`` is [K, dim*(1+var_output)]; targets come from
    ``batch.y_graph`` / ``batch.y_node`` sliced by the static head offsets.
    Returns (total, per_task [num_heads]).
    """
    tot = jnp.asarray(0.0, jnp.float32)
    tasks = []
    for hi, (level, start, end) in enumerate(cfg.head_offsets()):
        head = cfg.heads[hi]
        out = outputs[hi]
        pred = out[:, : head.dim]
        var = out[:, head.dim :] ** 2 if cfg.var_output else None
        if level == "graph":
            target = batch.y_graph[:, start:end]
            mask = batch.graph_mask
        else:
            target = batch.y_node[:, start:end]
            mask = batch.node_mask
        task = head_loss(cfg.loss_function_type, pred, target, mask, var)
        tasks.append(task)
        tot = tot + cfg.task_weights[hi] * task
    return tot, jnp.stack(tasks)
