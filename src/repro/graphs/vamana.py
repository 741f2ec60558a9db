"""Vamana graph construction (DiskANN, Jayaram Subramanya et al. 2019).

Batched adaptation: the sequential insert loop of the reference C++ becomes
rounds of (a) batched beam searches from the medoid to collect candidate
sets, (b) batched RobustPrune, (c) a reverse-edge pass with re-prune. Stale
reads within a batch are benign (the C++ multi-threaded builder has the same
property).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.graphs.adjacency import Graph, find_medoid
from repro.graphs.prune import prune_from_vectors
from repro.kernels.ops import pad_sentinel_row as _pad_vectors
from repro.search.beam import beam_search, make_exact_dist_fn


def build_vamana(key: jax.Array, x: jax.Array, *, r: int = 32, l: int = 64,
                 alpha: float = 1.2, passes: int = 2, batch: int = 1024,
                 verbose: bool = False) -> Graph:
    """Build a Vamana PG over x (N, D). Returns a padded-adjacency Graph.

    The (N, R) adjacency stays on the device across insert batches; only
    the reverse-edge pass round-trips it through the host, once per pass.
    """
    n, d = x.shape
    x = jnp.asarray(x, jnp.float32)
    xp = _pad_vectors(x)
    medoid = find_medoid(x)

    key, kinit = jax.random.split(key)
    nbrs = jax.random.randint(kinit, (n, r), 0, n, jnp.int32)
    nbrs = jnp.where(nbrs == jnp.arange(n)[:, None], (nbrs + 1) % n, nbrs)

    n_pad = (-n) % batch
    for p in range(passes):
        a = 1.0 if p == 0 else alpha
        key, kperm = jax.random.split(key)
        order = np.asarray(jax.random.permutation(kperm, n))
        order = np.concatenate([order, order[: n_pad]])
        for s in range(0, len(order), batch):
            ids = jnp.asarray(order[s:s + batch])
            cand = _batch_candidates(nbrs, xp, ids, medoid, l=l)  # (B, L+R)
            pruned = prune_from_vectors(xp, ids, cand, a, r, n)
            nbrs = nbrs.at[ids].set(pruned)
        # reverse-edge pass: j gains candidate i for every edge i→j
        nbrs = jnp.asarray(_reverse_pass(xp, np.array(nbrs), a, r, batch))
        if verbose:
            deg = np.asarray(jnp.sum(nbrs < n, axis=1))
            print(f"[vamana] pass {p}: mean degree {deg.mean():.1f}")

    return Graph(neighbors=nbrs, medoid=medoid)


@functools.partial(jax.jit, static_argnames=("l",))
def _batch_candidates(nbrs: jax.Array, xp: jax.Array, ids: jax.Array,
                      medoid: jax.Array, *, l: int) -> jax.Array:
    """Insert candidates of one batch: its beam from the medoid plus its
    current out-edges, self-ids masked to the sentinel. The vectors are an
    argument here, not a constant folded into the compiled beam."""
    n = nbrs.shape[0]
    res = beam_search(nbrs, medoid, xp[ids], make_exact_dist_fn(xp), h=l,
                      max_steps=4 * l)
    cand = jnp.concatenate([res.ids, nbrs[ids]], axis=1)
    return jnp.where(cand == ids[:, None], n, cand)


def _reverse_pass(xp: jax.Array, nbrs: np.ndarray, alpha: float, r: int,
                  batch: int) -> np.ndarray:
    n = nbrs.shape[0]
    src = np.repeat(np.arange(n, dtype=np.int32), r)
    dst = nbrs.reshape(-1)
    keep = dst < n
    src, dst = src[keep], dst[keep]
    # group reverse candidates by destination, cap r per node
    order = np.argsort(dst, kind="stable")
    dst_s, src_s = dst[order], src[order]
    starts = np.searchsorted(dst_s, np.arange(n))
    ends = np.searchsorted(dst_s, np.arange(n) + 1)
    rev = np.full((n, r), n, np.int32)
    cnt = np.minimum(ends - starts, r)
    for i in range(n):  # cheap: pure indexing, no distance math
        if cnt[i]:
            rev[i, : cnt[i]] = src_s[starts[i]: starts[i] + cnt[i]]
    # re-prune nodes whose candidate set grew
    grew = np.nonzero(cnt > 0)[0].astype(np.int32)
    n_pad = (-len(grew)) % batch
    grew_p = np.concatenate([grew, grew[: n_pad]]) if len(grew) else grew
    for s in range(0, len(grew_p), batch):
        ids = grew_p[s:s + batch]
        cand = np.concatenate([nbrs[ids], rev[ids]], axis=1)
        cand[cand == ids[:, None]] = n
        pruned = prune_from_vectors(xp, jnp.asarray(ids), jnp.asarray(cand),
                                    alpha, r, n)
        nbrs[ids] = np.asarray(pruned)
    return nbrs
