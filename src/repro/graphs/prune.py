"""Batched RobustPrune (DiskANN Alg. 2 / MRNG edge selection), jitted.

Given per-node candidate sets, iteratively keep the closest candidate p and
discard every candidate c with α·δ(p, c) ≤ δ(v, c) (p "occludes" c). α=1
gives the MRNG/NSG rule; α>1 (DiskANN default 1.2) keeps long-range edges.

Vectorized across a node batch with a fori_loop over the R slots — one XLA
program prunes 1k+ nodes at once (vs. the per-node scalar loop in the C++
implementations).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

INF = jnp.float32(jnp.inf)


@functools.partial(jax.jit, static_argnames=("r",))
def robust_prune(cand_ids: jax.Array, cand_dv: jax.Array, cand_pair: jax.Array,
                 alpha: float, r: int, sentinel: int) -> jax.Array:
    """Prune candidate sets to degree ≤ r.

    Args:
      cand_ids:  (B, C) int32 candidate ids (sentinel = invalid / padding).
      cand_dv:   (B, C) f32 distance candidate → node v.
      cand_pair: (B, C, C) f32 pairwise candidate distances.
      alpha:     occlusion factor (≥ 1).
      r:         max out-degree.
      sentinel:  id used for padding (== N).

    Returns: (B, r) int32 pruned neighbor ids (sentinel-padded).
    """
    b, c = cand_ids.shape
    valid0 = cand_ids != sentinel
    # mask duplicate ids (keep first occurrence of each id per row)
    sort_idx = jnp.argsort(cand_ids, axis=1)
    sorted_ids = jnp.take_along_axis(cand_ids, sort_idx, axis=1)
    dup_sorted = jnp.concatenate(
        [jnp.zeros((b, 1), bool), sorted_ids[:, 1:] == sorted_ids[:, :-1]], axis=1)
    dup = jnp.zeros((b, c), bool).at[
        jnp.arange(b)[:, None], sort_idx].set(dup_sorted)
    alive0 = valid0 & ~dup

    dv = jnp.where(alive0, cand_dv, INF)

    def body(slot, carry):
        alive, out = carry
        has = jnp.any(alive, axis=1)
        d = jnp.where(alive, dv, INF)
        pos = jnp.argmin(d, axis=1)                         # (B,)
        out = out.at[:, slot].set(jnp.where(has, pos, c))   # c == "none"
        d_pc = cand_pair[jnp.arange(b), pos, :]             # (B, C)
        occluded = alpha * d_pc <= cand_dv
        alive = alive & ~occluded & has[:, None]
        # the selected candidate occludes itself (d_pp = 0)
        alive = alive.at[jnp.arange(b), pos].set(False)
        return alive, out

    out0 = jnp.full((b, r), c, jnp.int32)
    _, out = jax.lax.fori_loop(0, r, body, (alive0, out0))
    padded_ids = jnp.concatenate(
        [cand_ids, jnp.full((b, 1), sentinel, jnp.int32)], axis=1)
    return jnp.take_along_axis(padded_ids, out, axis=1)


@functools.partial(jax.jit, static_argnames=("r",))
def prune_from_vectors(x: jax.Array, node_ids: jax.Array, cand_ids: jax.Array,
                       alpha: float, r: int, sentinel: int) -> jax.Array:
    """Convenience: gathers vectors and computes both distance tables.
    Jitted, so the (B, C, C) pair table is reduced in one fusion instead of
    materializing its (B, C, C, D) difference tensor.

    x must be sentinel-padded: x[(N+1), D] with x[N] finite (distances to the
    pad row are masked via the id check inside robust_prune).
    """
    xv = x[node_ids]                        # (B, D)
    xc = x[jnp.where(cand_ids == sentinel, 0, cand_ids)]  # (B, C, D)
    dv = jnp.sum((xc - xv[:, None, :]) ** 2, axis=-1)
    dv = jnp.where(cand_ids == sentinel, INF, dv)
    pair = jnp.sum((xc[:, :, None, :] - xc[:, None, :, :]) ** 2, axis=-1)
    return robust_prune(cand_ids, dv, pair, alpha, r, sentinel)
