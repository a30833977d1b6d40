"""E(3) math core: real spherical harmonics + real Clebsch-Gordan tensors.

From-scratch JAX replacement for the e3nn machinery the reference imports
for MACE (hydragnn/utils/model/mace_utils/tools/cg.py:22-136,
o3.SphericalHarmonics / o3.TensorProduct usage in
hydragnn/utils/model/mace_utils/modules/blocks.py).

Design: every convention (basis ordering, phases, normalization) is
fixed ONCE, numerically, at import time on the host:

1. Real spherical harmonics are defined analytically (associated
   Legendre × cos/sin) and then *fitted* to homogeneous Cartesian
   polynomial coefficient tensors. Runtime evaluation is a single
   monomials @ coeffs matmul — no trig, traceable, MXU-friendly.
2. Complex Wigner 3j symbols come from the Racah closed form (exact in
   float64 for the small l used here); the real-basis 3j tensor is
   obtained by numerically fitting the real↔complex change of basis to
   the SAME real harmonics as (1), so self-consistency holds by
   construction. Each generated tensor is verified to be rotation
   invariant under Wigner D matrices derived from the harmonics
   themselves; generation fails loudly otherwise.

Component normalization (e3nn "component"): E[|Y_lm|^2] = 1 over the
sphere, i.e. ||Y_l||^2 = 2l+1 for a unit vector.
"""

from __future__ import annotations

import math
from functools import lru_cache
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "sh_dim",
    "sh_basis",
    "real_wigner_3j",
    "wigner_d_from_sh",
    "monomial_powers",
    "sh_coeff_matrix",
]


def sh_dim(lmax: int) -> int:
    """Total dimension of l = 0..lmax concatenated: (lmax+1)^2."""
    return (lmax + 1) ** 2


# ----------------------------------------------------------------------
# Host-side analytic real spherical harmonics (definition of record)
# ----------------------------------------------------------------------


def _assoc_legendre(l: int, m: int, x: np.ndarray) -> np.ndarray:
    """P_l^m(x) WITHOUT the Condon-Shortley phase (plain convention)."""
    pmm = np.ones_like(x)
    if m > 0:
        somx2 = np.sqrt(np.maximum(1.0 - x * x, 0.0))
        fact = 1.0
        for _ in range(m):
            pmm = pmm * fact * somx2
            fact += 2.0
    if l == m:
        return pmm
    pmmp1 = x * (2 * m + 1) * pmm
    if l == m + 1:
        return pmmp1
    pll = np.zeros_like(x)
    for ll in range(m + 2, l + 1):
        pll = ((2 * ll - 1) * x * pmmp1 - (ll + m - 1) * pmm) / (ll - m)
        pmm = pmmp1
        pmmp1 = pll
    return pll


def _real_sh_reference(l: int, vecs: np.ndarray) -> np.ndarray:
    """[K, 2l+1] real SH at unit vectors, component normalization.

    Component order m = -l..l: negative m are sin(|m| phi) terms,
    m = 0 the zonal term, positive m the cos(m phi) terms.
    """
    x, y, z = vecs[:, 0], vecs[:, 1], vecs[:, 2]
    r = np.sqrt(x * x + y * y + z * z)
    ct = np.clip(z / r, -1.0, 1.0)
    phi = np.arctan2(y, x)
    out = np.zeros((vecs.shape[0], 2 * l + 1))
    for m in range(0, l + 1):
        nrm = math.sqrt(
            (2 * l + 1) * math.factorial(l - m) / math.factorial(l + m)
        )
        plm = _assoc_legendre(l, m, ct)
        if m == 0:
            out[:, l] = nrm * plm
        else:
            out[:, l + m] = math.sqrt(2.0) * nrm * plm * np.cos(m * phi)
            out[:, l - m] = math.sqrt(2.0) * nrm * plm * np.sin(m * phi)
    return out


def monomial_powers(l: int) -> np.ndarray:
    """[(l+1)(l+2)/2, 3] exponent triples (a,b,c) with a+b+c = l."""
    return np.array(
        [(a, b, l - a - b) for a in range(l + 1) for b in range(l - a + 1)],
        dtype=np.int32,
    ).reshape(-1, 3)


def _monomials_np(vecs: np.ndarray, l: int) -> np.ndarray:
    """[K, n_monomials] degree-l monomials of each row, in the
    ``monomial_powers`` ordering — the convention the fitted
    ``sh_coeff_matrix`` coefficients are contracted against."""
    powers = monomial_powers(l)
    return np.prod(vecs[:, None, :] ** powers[None, :, :], axis=-1)


@lru_cache(maxsize=None)
def sh_coeff_matrix(l: int) -> np.ndarray:
    """[n_monomials, 2l+1] coefficients: Y_l(v) = monomials(v) @ C.

    Fitted from the analytic definition at random unit vectors; exact
    because restricted-to-sphere real SH are homogeneous degree-l
    polynomials.
    """
    if l == 0:
        return np.ones((1, 1))
    rng = np.random.default_rng(20240731 + l)
    k = max(4 * len(monomial_powers(l)), 64)
    v = rng.normal(size=(k, 3))
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    mono = _monomials_np(v, l)  # [K, P]
    target = _real_sh_reference(l, v)  # [K, 2l+1]
    coef, residuals, _, _ = np.linalg.lstsq(mono, target, rcond=None)
    fit = mono @ coef
    err = np.abs(fit - target).max()
    if err > 1e-9:
        raise RuntimeError(f"SH l={l} polynomial fit failed: max err {err}")
    return coef


def sh_basis(vec: jax.Array, lmax: int, *, normalize: bool = True) -> jax.Array:
    """Real spherical harmonics of l = 0..lmax, concatenated.

    vec [..., 3] -> [..., (lmax+1)^2]; component normalization. With
    ``normalize`` the input is first projected to the unit sphere
    (matching o3.SphericalHarmonics(normalize=True), reference
    MACEStack.py:158-162).
    """
    if normalize:
        n = jnp.sqrt(jnp.sum(vec * vec, axis=-1, keepdims=True) + 1e-18)
        vec = vec / n
    x, y, z = vec[..., 0], vec[..., 1], vec[..., 2]
    outs = [jnp.ones_like(x)[..., None]]
    for l in range(1, lmax + 1):
        powers = monomial_powers(l)
        coef = jnp.asarray(sh_coeff_matrix(l), vec.dtype)
        mono = jnp.stack(
            [
                (x ** int(a)) * (y ** int(b)) * (z ** int(c))
                for a, b, c in powers
            ],
            axis=-1,
        )
        outs.append(mono @ coef)
    return jnp.concatenate(outs, axis=-1)


# ----------------------------------------------------------------------
# Wigner 3j: complex (Racah) -> real basis (numerically fitted)
# ----------------------------------------------------------------------


def _f(n: int) -> float:
    return float(math.factorial(n))


def _complex_cg(j1: int, j2: int, j3: int, m1: int, m2: int, m3: int) -> float:
    """Clebsch-Gordan <j1 m1 j2 m2 | j3 m3> (standard convention)."""
    if m1 + m2 != m3:
        return 0.0
    if not (abs(j1 - j2) <= j3 <= j1 + j2):
        return 0.0
    pre = math.sqrt(
        (2 * j3 + 1)
        * _f(j3 + j1 - j2)
        * _f(j3 - j1 + j2)
        * _f(j1 + j2 - j3)
        / _f(j1 + j2 + j3 + 1)
    )
    pre *= math.sqrt(
        _f(j3 + m3)
        * _f(j3 - m3)
        * _f(j1 - m1)
        * _f(j1 + m1)
        * _f(j2 - m2)
        * _f(j2 + m2)
    )
    s = 0.0
    for k in range(0, j1 + j2 + j3 + 1):
        denoms = [
            j1 + j2 - j3 - k,
            j1 - m1 - k,
            j2 + m2 - k,
            j3 - j2 + m1 + k,
            j3 - j1 - m2 + k,
        ]
        if any(d < 0 for d in denoms) or k < 0:
            continue
        s += (-1.0) ** k / (
            _f(k) * np.prod([_f(d) for d in denoms])
        )
    return pre * s


@lru_cache(maxsize=None)
def _real_from_complex(l: int) -> np.ndarray:
    """A_l [2l+1, 2l+1] complex: Y_real = A_l @ Y_complex_CS.

    Built against the standard complex SH *with* Condon-Shortley phase
    (so it composes with the standard CG above): for m>0
    real_{+m} = ((-1)^m Y_m + Y_{-m})/sqrt(2),
    real_{-m} = ((-1)^m Y_m - Y_{-m})/(i sqrt(2)), real_0 = Y_0.
    """
    A = np.zeros((2 * l + 1, 2 * l + 1), dtype=complex)
    A[l, l] = 1.0
    for m in range(1, l + 1):
        s = (-1.0) ** m
        A[l + m, l + m] = s / math.sqrt(2)
        A[l + m, l - m] = 1.0 / math.sqrt(2)
        A[l - m, l + m] = s / (1j * math.sqrt(2))
        A[l - m, l - m] = -1.0 / (1j * math.sqrt(2))
    return A


@lru_cache(maxsize=None)
def real_wigner_3j(l1: int, l2: int, l3: int) -> np.ndarray:
    """Real-basis coupling tensor C [2l1+1, 2l2+1, 2l3+1].

    Normalized so that sum C^2 = 2l3+1 (component normalization of the
    coupled output). Rotation invariance under the representations
    carried by ``sh_basis`` is asserted at generation time.
    """
    if not (abs(l1 - l2) <= l3 <= l1 + l2):
        return np.zeros((2 * l1 + 1, 2 * l2 + 1, 2 * l3 + 1))
    # Complex CG in the m-index cube.
    cg = np.zeros((2 * l1 + 1, 2 * l2 + 1, 2 * l3 + 1))
    for m1 in range(-l1, l1 + 1):
        for m2 in range(-l2, l2 + 1):
            m3 = m1 + m2
            if -l3 <= m3 <= l3:
                cg[l1 + m1, l2 + m2, l3 + m3] = _complex_cg(
                    l1, l2, l3, m1, m2, m3
                )
    A1 = _real_from_complex(l1)
    A2 = _real_from_complex(l2)
    A3 = _real_from_complex(l3)
    # C_real[a,b,c] couples real components: Y_real = A Y, so the
    # invariant coupling in the real basis is A1 A2 conj(A3) cg.
    t = np.einsum("au,bv,cw,uvw->abc", A1, A2, np.conj(A3), cg)
    re, im = np.real(t), np.imag(t)
    t = re if np.abs(re).sum() >= np.abs(im).sum() else im
    nrm = np.sqrt((t**2).sum())
    if nrm < 1e-12:
        raise RuntimeError(f"real 3j ({l1},{l2},{l3}) vanished")
    t = t * math.sqrt(2 * l3 + 1) / nrm
    _assert_invariant(t, l1, l2, l3)
    return t


@lru_cache(maxsize=None)
def _rotation_samples() -> Tuple[np.ndarray, ...]:
    rng = np.random.default_rng(7)
    rots = []
    for _ in range(2):
        q, _ = np.linalg.qr(rng.normal(size=(3, 3)))
        if np.linalg.det(q) < 0:
            q[:, 0] = -q[:, 0]
        rots.append(q)
    return tuple(rots)


@lru_cache(maxsize=None)
def _wigner_d_np(l: int, rot_key: int) -> np.ndarray:
    rot = _rotation_samples()[rot_key]
    return wigner_d_from_sh(l, rot)


def _sh_basis_np(vecs: np.ndarray, l: int) -> np.ndarray:
    """[K, 2l+1] single-l harmonics in float64 numpy, from the SAME
    ``sh_coeff_matrix`` constants the runtime ``sh_basis`` matmuls —
    identical math, no device roundtrip. Generation-time code must not
    evaluate through JAX: on TPU the MXU's reduced-precision matmul
    perturbs the harmonics past the 1e-6 fit tolerance below (observed
    live: 'Wigner D fit failed for l=1: err 6.0e-3' on TPU v5 lite).
    Inputs are coerced to float64 numpy for the same reason: a float32
    (or jax, under default x64-off) vector set would drag the whole
    evaluation to fp32, where the tolerance is unreachable."""
    vecs = np.asarray(vecs, dtype=np.float64)
    if l == 0:
        return np.ones((vecs.shape[0], 1))
    return _monomials_np(vecs, l) @ sh_coeff_matrix(l)


def wigner_d_from_sh(l: int, rot: np.ndarray) -> np.ndarray:
    """Wigner D matrix in our real basis: Y_l(R v) = D_l(R) Y_l(v).

    Derived by least squares from the harmonics themselves, so it is
    exactly the representation the rest of the stack uses.

    The fit runs ENTIRELY in float64 numpy regardless of the caller's
    dtype or the jax x64 setting: a float32 (or jax-array, x64-off)
    ``rot`` would otherwise poison ``v @ rot.T`` — numpy's matmul
    defers to ``jax.Array.__rmatmul__``, the whole pipeline silently
    drops to fp32, and the 1e-6 verification tolerance (calibrated for
    fp64 lstsq residuals) becomes unreachable (seen on a TPU as
    ``Wigner D fit failed for l=1: err 0.00599`` — a float32-precision
    error magnitude).
    """
    rot = np.asarray(rot, dtype=np.float64)
    if l == 0:
        return np.ones((1, 1))
    rng = np.random.default_rng(99 + l)
    v = rng.normal(size=(8 * (2 * l + 1), 3))
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    y = _sh_basis_np(v, l)
    yr = _sh_basis_np(v @ rot.T, l)
    d, res, _, _ = np.linalg.lstsq(y, yr, rcond=None)
    err = np.abs(y @ d - yr).max()
    if err > 1e-6:
        raise RuntimeError(f"Wigner D fit failed for l={l}: err {err}")
    return d.T  # y_rot^T = D y^T  with rows = components


def _assert_invariant(t: np.ndarray, l1: int, l2: int, l3: int) -> None:
    for k in range(2):
        d1 = _wigner_d_np(l1, k)
        d2 = _wigner_d_np(l2, k)
        d3 = _wigner_d_np(l3, k)
        t2 = np.einsum("au,bv,cw,uvw->abc", d1, d2, d3, t)
        if np.abs(t2 - t).max() > 1e-5:
            raise RuntimeError(
                f"real 3j ({l1},{l2},{l3}) not invariant: "
                f"{np.abs(t2 - t).max():.2e}"
            )
