"""Adaptive vector decomposition (paper §4, step 1).

A learnable skew-symmetric matrix ``A`` parameterizes a square orthonormal
rotation ``R = expm(A)`` (orthogonality: expm(A)^T = expm(A^T) = expm(-A) =
expm(A)^{-1}).  Rotating ``x → R x`` before the vertical split turns PQ's
fixed chunking into a *learned* decomposition: back-prop through expm adjusts
which (linear combinations of) dimensions land in each sub-vector, balancing
informativeness across subspaces (the paper's Figure 4 case study).

We parameterize by the strictly-upper-triangular entries of ``A`` so the
skew-symmetry constraint can never be violated by an optimizer step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import expm


def init_rotation_params(dim: int, *, scale: float = 0.0,
                         key: jax.Array | None = None) -> jax.Array:
    """Strictly-upper-triangular parameters of the skew-symmetric generator.

    scale=0 initializes R = I (PQ-compatible start, recommended: training
    begins from the classic vertical split and departs only as the losses
    demand).
    """
    n = dim * (dim - 1) // 2
    if scale == 0.0 or key is None:
        return jnp.zeros((n,), jnp.float32)
    return scale * jax.random.normal(key, (n,), jnp.float32)


def skew_from_params(theta: jax.Array, dim: int) -> jax.Array:
    """Reconstruct the (dim, dim) skew-symmetric A from its upper triangle."""
    iu = jnp.triu_indices(dim, k=1)
    a = jnp.zeros((dim, dim), theta.dtype).at[iu].set(theta)
    return a - a.T


def rotation_from_params(theta: jax.Array, dim: int) -> jax.Array:
    """R = expm(A(theta)); differentiable, exactly orthonormal (up to fp)."""
    return expm(skew_from_params(theta, dim))


def rotate(x: jax.Array, r: jax.Array) -> jax.Array:
    """Apply the rotation: x (.., D) → x @ R^T  (i.e. R x for row vectors)."""
    return jnp.matmul(x, r.T, precision=jax.lax.Precision.HIGHEST)


def split_subvectors(x: jax.Array, m: int) -> jax.Array:
    """(..., D) → (..., M, D/M) vertical split of the (rotated) vector."""
    *lead, d = x.shape
    assert d % m == 0, f"D={d} not divisible by M={m}"
    return x.reshape(*lead, m, d // m)


def merge_subvectors(x: jax.Array) -> jax.Array:
    """(..., M, D/M) → (..., D)."""
    *lead, m, dsub = x.shape
    return x.reshape(*lead, m * dsub)
