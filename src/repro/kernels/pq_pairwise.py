"""Pallas TPU kernel: per-subspace pairwise squared distances (N, M, K).

This is the training / k-means hot loop of RPQ: both the Lloyd assignment
step and the differentiable soft-assignment (Eq. 6 of the paper) need the
full table of ||x[n,j] - c[j,k]||^2 for every sub-vector and codeword.

TPU formulation: the cross term is a per-subspace (bn, dsub) × (dsub, K)
matmul on the MXU; the norms are rank-1 VPU broadcasts. The kernel works
SUBSPACE-MAJOR: the wrapper lays x out as (M, N, dsub), so each grid step
(j, i) holds one subspace's codebook (K × dsub) and a (bn, dsub) slab of
its sub-vectors, and writes a (bn, K) tile of an (M, N, K) table. Every
block's last two dimensions are then (multiple of 8, full), which the TPU's
(8, 128) tiling rule accepts for any dsub and K.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pq_pairwise_kernel(x_ref, cb_ref, out_ref):
    x = x_ref[0].astype(jnp.float32)                 # (bn, dsub)
    c = cb_ref[0].astype(jnp.float32)                # (K, dsub)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)      # (bn, 1)
    c2 = jnp.sum(c * c, axis=-1)[None, :]            # (1, K)
    xc = jax.lax.dot_general(                        # (bn, K) on the MXU
        x, c, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    out_ref[0] = x2 - 2.0 * xc + c2


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def pq_pairwise(x: jax.Array, codebook: jax.Array, *, block_n: int = 512,
                interpret: bool = False) -> jax.Array:
    """(N, M, dsub) × (M, K, dsub) → (N, M, K) f32 squared distances."""
    n, m, dsub = x.shape
    _, k, _ = codebook.shape
    block_n = min(block_n, -(-n // 8) * 8)
    xt = jnp.transpose(x, (1, 0, 2))                 # (M, N, dsub)
    n_pad = (-n) % block_n
    if n_pad:
        xt = jnp.pad(xt, ((0, 0), (0, n_pad), (0, 0)))
    out = pl.pallas_call(
        _pq_pairwise_kernel,
        grid=(m, xt.shape[1] // block_n),
        in_specs=[
            pl.BlockSpec((1, block_n, dsub), lambda j, i: (j, i, 0)),
            pl.BlockSpec((1, k, dsub), lambda j, i: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n, k), lambda j, i: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, xt.shape[1], k), jnp.float32),
        interpret=interpret,
    )(xt, codebook)
    return jnp.transpose(out[:, :n], (1, 0, 2))
