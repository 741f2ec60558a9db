"""Blocked exact k-nearest-neighbor graph (also the ground-truth engine).

Brute force in row blocks: distances via ‖a‖²−2abᵀ+‖b‖² matmuls so the whole
build is a few big GEMMs — minutes for 1M×128 on one host, trivially
data-parallel across devices (see dist/sharding.py: rows over `data`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Ground truth must be exact: a TPU's default f32 matmul runs bf16 passes,
# whose error (~0.4% of ‖q‖·‖x‖) reorders neighbors closer than that.
HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("k", "block", "exclude_self"))
def knn_ids(x: jax.Array, q: jax.Array, k: int, *, block: int = 1024,
            exclude_self: bool = False) -> tuple[jax.Array, jax.Array]:
    """For each row of q (Q, D), the k nearest rows of x (N, D).

    Returns (ids (Q, k) int32, sqdists (Q, k) f32), ascending by distance.
    `exclude_self` masks exact index matches (for q == x graph builds).
    """
    n, d = x.shape
    qn, _ = q.shape
    x = x.astype(jnp.float32)
    q = q.astype(jnp.float32)
    x2 = jnp.sum(x * x, axis=1)

    q_pad = (-qn) % block
    qp = jnp.pad(q, ((0, q_pad), (0, 0)))
    nb = qp.shape[0] // block
    qb = qp.reshape(nb, block, d)
    base = jnp.arange(nb) * block

    def one(args):
        qi, off = args
        d2 = (jnp.sum(qi * qi, 1)[:, None] + x2[None, :]
              - 2.0 * jnp.matmul(qi, x.T, precision=HIGHEST))
        if exclude_self:
            rows = off + jnp.arange(block)
            d2 = jnp.where(jnp.arange(n)[None, :] == rows[:, None], jnp.inf, d2)
        neg, ids = jax.lax.top_k(-d2, k)
        return ids.astype(jnp.int32), -neg

    ids, dist = jax.lax.map(one, (qb, base))
    return ids.reshape(-1, k)[:qn], dist.reshape(-1, k)[:qn]


def knn_graph(x: jax.Array, k: int, *, block: int = 1024):
    """Exact kNN adjacency (N, k) excluding self — builder substrate."""
    ids, dist = knn_ids(x, x, k, block=block, exclude_self=True)
    return ids, dist
