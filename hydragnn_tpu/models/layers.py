"""Shared neural building blocks: activations, MLPs, masked norms.

Activation registry mirrors the reference's
``activation_function_selection`` (hydragnn/utils/model/model.py:44-61);
MaskedBatchNorm is the padded-batch equivalent of torch BatchNorm1d with
optional cross-replica stats (SyncBatchNorm, reference distributed.py:416).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn


def activation(name: str) -> Callable[[jax.Array], jax.Array]:
    table = {
        "relu": jax.nn.relu,
        "selu": jax.nn.selu,
        "prelu": lambda x: jax.nn.leaky_relu(x, 0.25),
        "elu": jax.nn.elu,
        "lrelu_01": lambda x: jax.nn.leaky_relu(x, 0.1),
        "lrelu_025": lambda x: jax.nn.leaky_relu(x, 0.25),
        "lrelu_05": lambda x: jax.nn.leaky_relu(x, 0.5),
        "sigmoid": jax.nn.sigmoid,
        "softplus": jax.nn.softplus,
        "shifted_softplus": shifted_softplus,
        "silu": jax.nn.silu,
        "tanh": jnp.tanh,
        "gelu": jax.nn.gelu,
    }
    if name not in table:
        raise ValueError(f"Unknown activation function: {name}")
    return table[name]


NUM_ELEMENTS = 118  # the periodic table


def species_onehot(x: jax.Array, node_mask: jax.Array) -> jax.Array:
    """One-hot atomic number over the periodic table [N, 118]: the first
    input column is Z, rounded and clamped into 1..118; padding nodes get
    zero rows (reference MACEStack.process_node_attributes, :510-541)."""
    z = jnp.clip(jnp.round(x[:, 0]), 1, NUM_ELEMENTS).astype(jnp.int32)
    oh = jax.nn.one_hot(z - 1, NUM_ELEMENTS, dtype=x.dtype)
    return oh * node_mask[:, None].astype(x.dtype)


def shifted_softplus(x: jax.Array) -> jax.Array:
    """softplus(x) - log(2): SchNet's activation (zero at zero)."""
    return jax.nn.softplus(x) - jnp.log(2.0).astype(x.dtype)


class DenseParams(nn.Module):
    """Parameter-only twin of ``nn.Dense``: declares the SAME param
    tree (``<name>/kernel``, ``<name>/bias`` with Dense's default
    initializers, so the RNG folding and checkpoint layout are
    identical to an ``nn.Dense`` of the same name) but RETURNS the raw
    arrays instead of applying the matmul — for call sites that fuse
    the matmul into a kernel (ops/segment.aggregate_receivers_pipeline)
    while staying restore-compatible with checkpoints written when the
    layer was a plain Dense."""

    features: int
    use_bias: bool = True

    @nn.compact
    def __call__(self, in_dim: int):
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (in_dim, self.features),
        )
        bias = (
            self.param("bias", nn.initializers.zeros, (self.features,))
            if self.use_bias
            else None
        )
        return kernel, bias


class MLP(nn.Module):
    """Plain MLP: Dense(+act) per hidden layer, optional final activation."""

    features: Sequence[int]
    act: str = "relu"
    final_activation: bool = False
    use_bias: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        fn = activation(self.act)
        for i, f in enumerate(self.features):
            x = nn.Dense(f, use_bias=self.use_bias, name=f"dense_{i}")(x)
            if i < len(self.features) - 1 or self.final_activation:
                x = fn(x)
        return x


class MaskedBatchNorm(nn.Module):
    """BatchNorm over the unmasked rows of a padded [N, F] array.

    Running statistics live in the ``batch_stats`` collection; when
    ``axis_name`` is set, batch statistics are averaged across that mesh
    axis (SyncBatchNorm semantics).
    """

    momentum: float = 0.9
    epsilon: float = 1e-5
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(
        self, x: jax.Array, mask: jax.Array, *, train: bool
    ) -> jax.Array:
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros(x.shape[-1], jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones(x.shape[-1], jnp.float32)
        )
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))

        if train:
            m = mask.astype(x.dtype)[:, None]
            count = jnp.maximum(jnp.sum(m), 1.0)
            mean = jnp.sum(x * m, axis=0) / count
            var = jnp.sum(((x - mean) ** 2) * m, axis=0) / count
            if self.axis_name is not None:
                mean = jax.lax.pmean(mean, self.axis_name)
                var = jax.lax.pmean(var, self.axis_name)
            if not self.is_initializing():
                ra_mean.value = (
                    self.momentum * ra_mean.value
                    + (1.0 - self.momentum) * mean.astype(jnp.float32)
                )
                ra_var.value = (
                    self.momentum * ra_var.value
                    + (1.0 - self.momentum) * var.astype(jnp.float32)
                )
        else:
            mean = ra_mean.value.astype(x.dtype)
            var = ra_var.value.astype(x.dtype)

        inv = jax.lax.rsqrt(var.astype(x.dtype) + self.epsilon)
        return (x - mean.astype(x.dtype)) * inv * scale + bias
