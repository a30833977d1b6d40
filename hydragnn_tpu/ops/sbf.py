"""DimeNet spherical basis: spherical Bessel x Legendre angular functions.

Functional JAX equivalent of the reference's SphericalBasisLayer /
BesselBasisLayer (imported from PyG in hydragnn/models/DIMEStack.py:22-27
and used via the DIMEStack rbf/sbf members). The reference relies on
sympy-generated closed forms, which are numerically unstable in bf16/f32;
here each radial basis function norm_ln * j_l(z_ln * d), d in [0,1], is
fitted once on the host with float64 Chebyshev interpolation and evaluated
on device as a single cos(k*arccos(t)) @ coeffs matmul — stable, exact to
~1e-6, and MXU-shaped.

Shapes: dist [E] -> rbf [E, num_radial]; (dist, angle, idx_kj) ->
sbf [T, num_spherical * num_radial].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.ops.rbf import envelope


# ----------------------------------------------------------------------
# Host-side float64: j_l evaluation, roots, Chebyshev interpolation.
# ----------------------------------------------------------------------

def _jl_host(l: int, x: np.ndarray) -> np.ndarray:
    """Spherical Bessel j_l on the host in float64.

    Uses scipy when present; otherwise a series/recurrence hybrid that is
    accurate to ~1e-9 absolute for l <= 8 (enough for the Chebyshev fit).
    """
    x = np.asarray(x, dtype=np.float64)
    try:
        from scipy.special import spherical_jn

        return spherical_jn(l, x)
    except ImportError:
        pass
    x_safe = np.where(np.abs(x) < 1e-12, 1e-12, x)
    out_rec = np.sin(x_safe) / x_safe
    if l >= 1:
        jm, jc = out_rec, np.sin(x_safe) / x_safe**2 - np.cos(x_safe) / x_safe
        for n in range(1, l):
            jm, jc = jc, (2 * n + 1) / x_safe * jc - jm
        out_rec = jc
    # Series near zero (float64: accurate for x < 0.5).
    t = 0.5 * x * x
    dfact = 1.0
    for k in range(l + 1):
        dfact *= 2 * k + 1
    ser = (
        x**l
        / dfact
        * (1.0 - t / (2 * l + 3) + t * t / (2.0 * (2 * l + 3) * (2 * l + 5)))
    )
    return np.where(np.abs(x) < 0.5, ser, out_rec)


@functools.lru_cache(maxsize=None)
def spherical_bessel_roots(num_spherical: int, num_radial: int) -> np.ndarray:
    """First ``num_radial`` positive roots of j_l, l = 0..num_spherical-1.

    Roots of j_l interlace those of j_{l-1}; each is found by bisection
    inside the interlacing bracket (j_0 roots are n*pi exactly).
    """
    n_extra = num_radial + num_spherical
    roots = np.zeros((num_spherical, n_extra))
    roots[0] = np.arange(1, n_extra + 1) * np.pi
    for l in range(1, num_spherical):
        for k in range(n_extra - l):
            lo, hi = roots[l - 1, k], roots[l - 1, k + 1]
            flo = float(_jl_host(l, np.array([lo + 1e-9]))[0])
            for _ in range(80):
                mid = 0.5 * (lo + hi)
                fm = float(_jl_host(l, np.array([mid]))[0])
                if fm == 0.0:
                    lo = hi = mid
                    break
                if (fm > 0) == (flo > 0):
                    lo, flo = mid, fm
                else:
                    hi = mid
            roots[l, k] = 0.5 * (lo + hi)
    return roots[:, :num_radial].copy()


@functools.lru_cache(maxsize=None)
def _radial_cheb_coeffs(
    num_spherical: int, num_radial: int, degree: int = 64
) -> np.ndarray:
    """Chebyshev coefficients [degree, S*R] of the normalized radial
    functions f_ln(d) = sqrt(2 / j_{l+1}(z_ln)^2) * j_l(z_ln * d), d in
    [0,1] mapped to t = 2d-1 in [-1,1]."""
    z = spherical_bessel_roots(num_spherical, num_radial)  # [S, R]
    norm = np.zeros_like(z)
    for l in range(num_spherical):
        norm[l] = np.sqrt(2.0 / _jl_host(l + 1, z[l]) ** 2)

    K = degree
    theta = (np.arange(K) + 0.5) * np.pi / K
    t_nodes = np.cos(theta)  # Chebyshev nodes in [-1,1]
    d_nodes = 0.5 * (t_nodes + 1.0)  # map to [0,1]

    # Sample all (l, n) functions at the nodes: [K, S, R]
    f = np.zeros((K, num_spherical, num_radial))
    for l in range(num_spherical):
        for n in range(num_radial):
            f[:, l, n] = norm[l, n] * _jl_host(l, z[l, n] * d_nodes)

    # DCT-based Chebyshev coefficients: c_k = (2-delta_k0)/K sum f cos(k theta)
    kth = np.outer(np.arange(K), theta)  # [K, K]
    weights = np.cos(kth)  # [k, node]
    c = 2.0 / K * weights @ f.reshape(K, -1)  # [K, S*R]
    c[0] *= 0.5
    return c  # [degree, S*R]


# ----------------------------------------------------------------------
# Device-side evaluation.
# ----------------------------------------------------------------------

def chebyshev_eval(t: jax.Array, coeffs: jax.Array) -> jax.Array:
    """Evaluate Chebyshev series sum_k c_k T_k(t) for a coefficient matrix
    [K, F]: one cos(k*arccos t) feature map and a matmul.

    The matmul is pinned to ``Precision.HIGHEST``: at the default
    precision a TPU rounds both operands to bfloat16, and the basis then
    lies further from its float64 values than its float32 evaluation
    does (PERF.md, dimenet_pp_qm9). It is ``[E, K] x [K, F]``, a few
    MFLOP a step."""
    K = coeffs.shape[0]
    tc = jnp.clip(t, -1.0, 1.0)
    theta = jnp.arccos(tc)
    feats = jnp.cos(theta[..., None] * jnp.arange(K, dtype=t.dtype))
    return jnp.matmul(feats, coeffs, precision=jax.lax.Precision.HIGHEST)


def legendre_pl(c: jax.Array, l_max: int) -> jax.Array:
    """Legendre P_l(c) for l = 0..l_max via the stable upward recurrence."""
    p0 = jnp.ones_like(c)
    outs = [p0]
    if l_max >= 1:
        outs.append(c)
        pm, pc = p0, c
        for l in range(1, l_max):
            pm, pc = pc, ((2 * l + 1) * c * pc - l * pm) / (l + 1)
            outs.append(pc)
    return jnp.stack(outs, axis=-1)


def bessel_basis_envelope(
    dist: jax.Array, cutoff: float, num_radial: int, exponent: int = 5
) -> jax.Array:
    """DimeNet radial basis: u(d/c) * sqrt(2/c) * sin(n pi d/c)
    (reference BesselBasisLayer behavior, DIMEStack.py:70)."""
    d = dist / cutoff
    d_safe = jnp.where(d < 1e-8, 1e-8, d)
    freq = jnp.arange(1, num_radial + 1, dtype=dist.dtype) * jnp.pi
    env = envelope(d_safe, exponent)
    return env[..., None] * jnp.asarray(
        np.sqrt(2.0 / cutoff), dist.dtype
    ) * jnp.sin(freq * d_safe[..., None])


def radial_bessel_jl(
    dist_scaled: jax.Array, num_spherical: int, num_radial: int
) -> jax.Array:
    """Normalized j_l(z_ln * d) for d in [0,1] -> [..., S, R] via the
    precomputed Chebyshev table."""
    coeffs = jnp.asarray(
        _radial_cheb_coeffs(num_spherical, num_radial), dist_scaled.dtype
    )
    t = 2.0 * dist_scaled - 1.0
    flat = chebyshev_eval(t, coeffs)
    return flat.reshape(dist_scaled.shape + (num_spherical, num_radial))


def spherical_basis(
    dist: jax.Array,
    angle: jax.Array,
    idx_kj: jax.Array,
    *,
    cutoff: float,
    num_spherical: int,
    num_radial: int,
    envelope_exponent: int = 5,
) -> jax.Array:
    """2-D spherical basis a_SBF(d_kj, angle) of DimeNet.

    ``dist`` is per-edge [E]; ``angle`` per-triplet [T]; ``idx_kj`` maps
    each triplet to its k->j edge. Returns [T, num_spherical*num_radial].
    """
    d = jnp.clip(dist / cutoff, 0.0, 1.0)
    radial = radial_bessel_jl(d, num_spherical, num_radial)  # [E, S, R]
    env = envelope(jnp.where(d < 1e-8, 1e-8, d), envelope_exponent)
    radial = radial * env[:, None, None]

    # Angular: Y_l^0(angle) = sqrt((2l+1)/4pi) P_l(cos angle).
    ls = jnp.arange(num_spherical, dtype=dist.dtype)
    pl = legendre_pl(jnp.cos(angle), num_spherical - 1)  # [T, S]
    cbf = pl * jnp.sqrt((2.0 * ls + 1.0) / (4.0 * jnp.pi))

    out = radial[idx_kj] * cbf[:, :, None]  # [T, S, R]
    return out.reshape(out.shape[0], num_spherical * num_radial)
