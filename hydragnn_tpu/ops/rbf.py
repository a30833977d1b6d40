"""Radial basis functions, cutoffs, and distance transforms.

Functional JAX equivalents of the reference's radial machinery:
Gaussian smearing (hydragnn/models/SCFStack.py GaussianSmearing via PyG),
Bessel basis (hydragnn/models/PNAPlusStack.py:40-143, DIMEStack),
sinc basis + cosine cutoff (hydragnn/models/PAINNStack.py:331-352),
Bessel/Chebyshev/Gaussian bases + PolynomialCutoff + Agnesi/Soft transforms
(hydragnn/utils/model/mace_utils/modules/radial.py:23-248).

All are pure elementwise functions of edge length [E] -> [E, num_basis];
XLA fuses them into the surrounding edge MLPs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.utils import tracer as tr


@tr.scoped("edge_geometry")
def gaussian_smearing(
    dist: jax.Array, start: float, stop: float, num_gaussians: int
) -> jax.Array:
    """exp(-gamma (d - mu_k)^2) on an even grid of centers."""
    offset = jnp.linspace(start, stop, num_gaussians, dtype=dist.dtype)
    coeff = -0.5 / (offset[1] - offset[0]) ** 2
    diff = dist[..., None] - offset
    return jnp.exp(coeff * diff**2)


@tr.scoped("edge_geometry")
def bessel_basis(dist: jax.Array, cutoff: float, num_radial: int) -> jax.Array:
    """sqrt(2/c) * sin(n pi d / c) / d — spherical Bessel j0 basis."""
    freq = jnp.arange(1, num_radial + 1, dtype=dist.dtype) * jnp.pi
    d = dist[..., None] / cutoff
    d_safe = jnp.where(d < 1e-8, 1e-8, d)
    prefactor = jnp.asarray(np.sqrt(2.0 / cutoff), dist.dtype)
    return prefactor * jnp.sin(freq * d_safe) / (d_safe * cutoff)


@tr.scoped("edge_geometry")
def sinc_basis(dist: jax.Array, cutoff: float, num_basis: int) -> jax.Array:
    """sinc-like expansion sin(n pi d/c)/d used by PaiNN
    (reference: hydragnn/models/PAINNStack.py:331-341)."""
    n = jnp.arange(1, num_basis + 1, dtype=dist.dtype)
    d_safe = jnp.where(dist < 1e-8, 1e-8, dist)[..., None]
    return jnp.sin(n * jnp.pi * d_safe / cutoff) / d_safe


@tr.scoped("edge_geometry")
def chebyshev_basis(dist: jax.Array, cutoff: float, num_basis: int) -> jax.Array:
    """Chebyshev polynomials of scaled distance on [-1, 1]
    (reference: mace_utils/modules/radial.py ChebychevBasis)."""
    x = jnp.clip(2.0 * dist / cutoff - 1.0, -1.0, 1.0)[..., None]
    n = jnp.arange(1, num_basis + 1, dtype=dist.dtype)
    return jnp.cos(n * jnp.arccos(x))


@tr.scoped("edge_geometry")
def cosine_cutoff(dist: jax.Array, cutoff: float) -> jax.Array:
    """0.5 (cos(pi d/c) + 1) for d < c else 0 (SchNet/PaiNN cutoff)."""
    out = 0.5 * (jnp.cos(jnp.pi * dist / cutoff) + 1.0)
    return jnp.where(dist < cutoff, out, 0.0)


@tr.scoped("edge_geometry")
def polynomial_cutoff(dist: jax.Array, cutoff: float, p: int = 6) -> jax.Array:
    """MACE polynomial envelope, C^p smooth at the cutoff
    (reference: mace_utils/modules/radial.py PolynomialCutoff)."""
    d = dist / cutoff
    pf = float(p)
    out = (
        1.0
        - (pf + 1.0) * (pf + 2.0) / 2.0 * d**p
        + pf * (pf + 2.0) * d ** (p + 1)
        - pf * (pf + 1.0) / 2.0 * d ** (p + 2)
    )
    return jnp.where(d < 1.0, out, 0.0)


def envelope(dist_scaled: jax.Array, exponent: int = 5) -> jax.Array:
    """DimeNet smooth envelope u(d) with u(1)=u'(1)=u''(1)=0
    (reference: hydragnn/models/PNAPlusStack.py Envelope / DimeNet)."""
    p = exponent + 1
    a = -(p + 1) * (p + 2) / 2.0
    b = p * (p + 2.0)
    c = -p * (p + 1) / 2.0
    x = dist_scaled
    x_safe = jnp.where(x < 1e-8, 1e-8, x)
    out = 1.0 / x_safe + a * x ** (p - 1) + b * x**p + c * x ** (p + 1)
    return jnp.where(x < 1.0, out, 0.0)


def agnesi_transform(
    dist: jax.Array,
    r_0: jax.Array,
    a: float = 1.0805,
    q: float = 0.9183,
    p: float = 4.5791,
) -> jax.Array:
    """Agnesi distance transform (mace_utils/modules/radial.py:151-196):
    (1 + a (d/r_0)^q / (1 + (d/r_0)^(q-p)))^-1, decreasing 1 -> 0. The
    transformed value REPLACES the distance fed to the radial basis;
    ``r_0`` is the per-edge mean covalent radius of the endpoints."""
    x = jnp.maximum(dist / r_0, 1e-12)
    return 1.0 / (1.0 + a * x**q / (1.0 + x ** (q - p)))


def soft_transform(
    dist: jax.Array, r_0: jax.Array, a: float = 0.2, b: float = 3.0
) -> jax.Array:
    """Soft distance transform (mace_utils/modules/radial.py:204-248):
    d + 0.5 tanh(-(d/r_0) - a (d/r_0)^b) + 0.5, with ``r_0`` the per-edge
    quarter-sum of the endpoint covalent radii."""
    x = dist / r_0
    return dist + 0.5 * jnp.tanh(-x - a * x**b) + 0.5


@tr.scoped("edge_geometry")
def edge_vectors_and_lengths(
    pos: jax.Array,
    senders: jax.Array,
    receivers: jax.Array,
    shifts: jax.Array | None = None,
    *,
    normalize: bool = False,
    eps: float = 1e-9,
) -> tuple[jax.Array, jax.Array]:
    """PBC-aware displacement primitive: vec = pos[s] - pos[r] + shift.

    The single geometric primitive all geometric stacks share (reference:
    hydragnn/utils/model/operations.py:21 get_edge_vectors_and_lengths).
    Returns (vectors [E,3], lengths [E]).
    """
    vec = pos[senders] - pos[receivers]
    if shifts is not None:
        vec = vec + shifts
    length = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + eps)
    if normalize:
        vec = vec / length[..., None]
    return vec, length
