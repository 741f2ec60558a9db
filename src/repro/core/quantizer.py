"""Differentiable quantizer (paper §4): rotation + Gumbel-Softmax PQ.

State is a plain pytree (:class:`RPQParams`) so the trainer, checkpointing
and sharding layers treat it like any other model.

Conventions
-----------
* All quantization happens in the *rotated* space. Squared Euclidean distance
  is rotation-invariant (R orthonormal), so ADC distances computed there equal
  distances in the original space; queries are rotated once at LUT-build time.
* ``soft_assign`` implements Eq. 6 with the sign fixed (see DESIGN.md):
  ``p(c_k | x_j) = softmax_k(-||x_j - c_k||^2 / T)``.
* ``gumbel_codes`` implements Eq. 7; with ``straight_through=True`` the
  forward value is the exact one-hot argmax (so the decode path equals true
  PQ decode) while the gradient flows through the soft sample.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import rotation as rot
from repro.kernels import ops as kops


class RPQParams(NamedTuple):
    theta: jax.Array      # (D*(D-1)/2,) skew-symmetric generator (upper tri)
    codebooks: jax.Array  # (M, K, D/M) codewords per subspace
    log_alpha: jax.Array  # () learnable loss-mixing coefficient (paper Eq. 11)


class RPQConfig(NamedTuple):
    dim: int
    m: int = 8            # number of subspaces
    k: int = 256          # codewords per subspace (byte codes)
    assign_temp: float = 1.0   # T in softmax(-d/T) (Eq. 6)
    gumbel_tau: float = 1.0    # Gumbel-Softmax temperature (Eq. 7)
    routing_tau: float = 1.0   # τ in the routing loss (Eq. 9)
    adaptive_temp: bool = True  # normalize d by its batch scale before the
                                # softmax so T is data-scale free (without
                                # this, squared distances of O(100) saturate
                                # the softmax and gradients vanish)
    straight_through: bool = True
    learn_rotation: bool = True

    @property
    def dsub(self) -> int:
        return self.dim // self.m


def init_params(cfg: RPQConfig, codebooks: jax.Array) -> RPQParams:
    """Start from R=I and externally-supplied codebooks (k-means init)."""
    assert codebooks.shape == (cfg.m, cfg.k, cfg.dsub), codebooks.shape
    return RPQParams(
        theta=rot.init_rotation_params(cfg.dim),
        codebooks=jnp.asarray(codebooks, jnp.float32),
        log_alpha=jnp.zeros((), jnp.float32),
    )


# --------------------------------------------------------------------------
# Forward paths
# --------------------------------------------------------------------------

def rotation_matrix(cfg: RPQConfig, params: RPQParams) -> jax.Array:
    if not cfg.learn_rotation:
        return jnp.eye(cfg.dim, dtype=jnp.float32)
    return rot.rotation_from_params(params.theta, cfg.dim)


def rotate_split(cfg: RPQConfig, params: RPQParams, x: jax.Array) -> jax.Array:
    """(N, D) → (N, M, dsub) rotated sub-vectors."""
    r = rotation_matrix(cfg, params)
    return rot.split_subvectors(rot.rotate(x, r), cfg.m)


def subspace_distances(cfg: RPQConfig, params: RPQParams, x: jax.Array,
                       *, backend: str = "auto") -> jax.Array:
    """(N, D) → (N, M, K) table of ||rot(x)_j − c_k^j||² (the hot loop)."""
    xs = rotate_split(cfg, params, x)
    return kops.pq_pairwise(xs, params.codebooks, backend=backend)


def _temp_scale(cfg: RPQConfig, d: jax.Array) -> jax.Array:
    """Data-scale normalizer for the assignment softmax.

    Uses the batch-mean *nearest* distance (stop-gradient) so the closest
    codeword sits at d̃ ≈ 1 regardless of the dataset's magnitude.
    """
    if not cfg.adaptive_temp:
        return jnp.asarray(1.0, d.dtype)
    return jax.lax.stop_gradient(jnp.mean(jnp.min(d, axis=-1)) + 1e-12)


def soft_assign(cfg: RPQConfig, params: RPQParams, x: jax.Array,
                *, backend: str = "auto") -> jax.Array:
    """Eq. 6 (sign-fixed): codeword assignment probabilities (N, M, K).
    Differentiable on every backend (kernels.ops.pq_pairwise)."""
    d = subspace_distances(cfg, params, x, backend=backend)
    return jax.nn.softmax(-d / (_temp_scale(cfg, d) * cfg.assign_temp), axis=-1)


def gumbel_codes(cfg: RPQConfig, params: RPQParams, x: jax.Array,
                 key: jax.Array) -> jax.Array:
    """Eq. 7: approximate compact code as a (N, M, K) relaxed one-hot.

    softmax((log p + gumbel_noise) / tau); straight-through optionally
    snaps the forward value to the exact one-hot.
    """
    d = subspace_distances(cfg, params, x)
    logp = jax.nn.log_softmax(-d / (_temp_scale(cfg, d) * cfg.assign_temp),
                              axis=-1)
    g = jax.random.gumbel(key, logp.shape, logp.dtype)
    y = jax.nn.softmax((logp + g) / cfg.gumbel_tau, axis=-1)
    if cfg.straight_through:
        hard = jax.nn.one_hot(jnp.argmax(y, axis=-1), cfg.k, dtype=y.dtype)
        y = hard + (y - jax.lax.stop_gradient(y))
    return y


def decode_soft(cfg: RPQConfig, params: RPQParams, probs: jax.Array) -> jax.Array:
    """(N, M, K) assignment (soft or one-hot) → (N, D) quantized vectors
    in the ROTATED space (probs ⊗ codebooks, merged)."""
    sub = jnp.einsum("nmk,mkd->nmd", probs, params.codebooks)
    return rot.merge_subvectors(sub)


def quantize_st(cfg: RPQConfig, params: RPQParams, x: jax.Array,
                key: jax.Array) -> jax.Array:
    """x → x' : end-to-end differentiable quantized vectors (rotated space)."""
    return decode_soft(cfg, params, gumbel_codes(cfg, params, x, key))


# --------------------------------------------------------------------------
# Inference paths (hard codes, LUTs) — what the serving engine uses
# --------------------------------------------------------------------------

def encode(cfg: RPQConfig, params: RPQParams, x: jax.Array,
           *, backend: str = "auto") -> jax.Array:
    """(N, D) → (N, M) hard compact codes (uint8 if K ≤ 256)."""
    d = subspace_distances(cfg, params, x, backend=backend)
    codes = jnp.argmin(d, axis=-1)
    return codes.astype(jnp.uint8 if cfg.k <= 256 else jnp.int32)


def decode(cfg: RPQConfig, params: RPQParams, codes: jax.Array) -> jax.Array:
    """(N, M) codes → (N, D) quantized vectors in the rotated space."""
    sub = jnp.take_along_axis(
        params.codebooks[None], codes[:, :, None, None].astype(jnp.int32), axis=2
    )[:, :, 0, :]
    return rot.merge_subvectors(sub)


def build_lut(cfg: RPQConfig, params: RPQParams, queries: jax.Array) -> jax.Array:
    """(Q, D) queries → (Q, M, K) ADC lookup tables (rotated space)."""
    qs = rotate_split(cfg, params, jnp.atleast_2d(queries))
    return kops.pq_pairwise(qs, params.codebooks, backend="ref")


def adc_distances(cfg: RPQConfig, params: RPQParams, codes: jax.Array,
                  queries: jax.Array, *, backend: str = "auto") -> jax.Array:
    """(Q, D) × (N, M) → (Q, N) ADC distance estimates."""
    luts = build_lut(cfg, params, queries)
    return kops.adc_scan_batch(codes, luts, backend=backend)


def reconstruction_mse(cfg: RPQConfig, params: RPQParams, x: jax.Array) -> jax.Array:
    """Mean ||rot(x) − decode(encode(x))||²; the classic PQ distortion."""
    codes = encode(cfg, params, x)
    xq = decode(cfg, params, codes)
    r = rotation_matrix(cfg, params)
    return jnp.mean(jnp.sum((rot.rotate(x, r) - xq) ** 2, axis=-1))
