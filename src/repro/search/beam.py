"""Batched graph beam search — the routing engine (paper §3.1, Alg. 2 core).

TPU/JAX adaptation (DESIGN.md §3, §9): instead of a scalar CPU heap per query
we run a *fixed-shape* best-first beam entirely in `jax.lax`:

* beam = three (h,) arrays (ids, dists, expanded) kept sorted by merge+top_k;
* visited set = uint32 bitset (N/32 words) — O(1) membership, vmappable;
* one `while_loop` per batch; vmapped lanes step together until all converge
  (the classic SIMD-ification of best-first search);
* distances come from a pluggable `dist_fn` (ADC LUT gather or exact), so the
  same engine serves PQ-routing and exact-routing.

A round's steps 1, 2 and 4 run under the named scopes `beam.select`,
`beam.visited` and `beam.merge`: `op_name` metadata in the compiled HLO,
which ties each device op of a profiler trace to its step. They change no
operation. Step 3, the distance call, keeps the hop kernel's own name.

**Frontier batching** (`expand=E`, DESIGN.md §9): every `while_loop` round
expands the E best unexpanded beam entries at once — their E·R neighbor ids
are deduplicated (against each other and the visited bitset; width-adaptive
first-occurrence, sort-based once the frontier outgrows the all-pairs
compare's sweet spot) and scored in ONE `dist_fn` call, then merged in a
single (h + E·R)-wide top-k.
This is DiskANN's beam-width trick aimed at the TPU's expensive medium: the
kernel invocation. Sequential trip count drops from `hops` to `rounds`
(≈ hops/E) and the vmapped lockstep-convergence tail shrinks with it.
`expand=1` (the default) is bit-identical to the classic one-hop-per-step
beam. `SearchResult.rounds` reports the measured round count.

**Tombstones** (streaming deletes, DESIGN.md §10): `beam_search(...,
tombstones=bitset)` takes a uint32 bitset over vertex ids (same word layout
as the visited set) and masks every tombstoned frontier distance to +inf —
a deleted vertex is never expanded, never ranks, and is scrubbed from the
returned beam (sentinel id, +inf dist). The bitset is a TRACED argument, so
churning deletes never re-trigger jit (unlike baking the mask into
`dist_fn`, which is a static jit argument). A tombstoned ENTRY vertex gets a
large-but-finite distance instead, so the search still starts and routes
off it (it is scrubbed from the results like any other tombstone).

**Multi-entry seeding** (adaptive routing, DESIGN.md §11): ``entry`` may be
a (Q, S) per-query entry SET instead of one shared/per-query vertex —
``search/seed.py`` produces such sets from a PQ-hash coarse index. The S
entries are deduplicated, scored in one ``dist_fn`` call, sorted, and
installed as the initial beam; invalid lanes (sentinel ``-1`` padding from
the seeder) start expanded at +inf, and each tombstoned entry individually
gets ``DEAD_ENTRY_DIST`` (so an all-tombstoned entry set still routes off
its best dead entry, exactly like the classic dead-medoid case). ``S=1``
is bit-identical to the classic single-entry beam.

**Probabilistic hop pruning** (DESIGN.md §11): with ``lb_dist_fn`` (a
partial-LUT distance over the first ``m_prefix < m_total`` subspaces —
``make_adc_dist_fn(m_prefix=)``) and ``prune_eps > 0``, every round first
scores the frontier's LOWER BOUND ``d_m′`` (per-subspace LUT entries are
non-negative, so ``d_m′ ≤ d_M``), extrapolates it to a full-distance
estimate ``d̂ = d_m′ · cal``, and only full-scores candidates with
``d̂ · (1 + ε) ≤ τ``, where τ is the current worst beam distance. The
estimate (not the raw bound) drives the gate: the bound sits well below
the full sum, so comparing IT to a full-distance τ would prune almost
nothing — extrapolation prunes like the full distance would at m′/M of
the cost, mis-pruning with small ε-bounded probability (hence
"probabilistic"). The extrapolation factor ``cal`` defaults to the
uniform-mass ratio ``M/m′``, but that overshoots on anisotropic data
(leading subspaces carry MORE than m′/M of the distance mass, so the
estimate comes out too large and over-prunes); pass
``lb_scale_fn = make_lb_scale_fn(...)`` to calibrate it per query from
the query's own LUT mass instead. Pruned lanes are masked to the
sentinel — shapes never change, so churn never retraces. ``prune_eps=0``
disables the pass entirely (bit-identical).
``n_dist`` then counts full-LUT-equivalents: each partial score adds
``m_prefix / m_total`` of a distance evaluation, each full score adds one.

**Deadline budgets** (resilience, DESIGN.md §13): ``max_rounds`` /
``max_n_dist`` bound the per-call compute — rounds and (full-LUT-equivalent)
distance evaluations respectively. Both are TRACED scalars, so sweeping a
deadline never retraces, and both gate only the ``while_loop`` *condition*:
under ``vmap``, JAX's while_loop batching masks the whole carry for any lane
whose own cond is false, so an exhausted query freezes — best-so-far beam,
honest counters — while other lanes keep stepping, with zero body-side
masking. The early exit is fixed-shape (the beam arrays never change size);
``SearchResult.truncated`` flags every query that stopped with unexpanded
finite candidates still pending — whether the round budget, the n_dist
budget, or ``max_steps`` cut it off. ``None`` (the default) compiles the
check out entirely: bit-identical to the pre-budget beam, the same
zero-cost-when-off contract as ``expand=1`` and ``prune_eps=0``.

`beam_search_trace` additionally records the ranked candidate beam at every
round — exactly the paper's Definition 6 routing features.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

INF = jnp.float32(jnp.inf)

# Distance assigned to a tombstoned ENTRY vertex: large enough that any real
# candidate outranks it, finite so the while_loop still expands it (an +inf
# entry would end the search before the first hop — the "deleted medoid"
# case must keep routing).
DEAD_ENTRY_DIST = jnp.float32(1e30)


class SearchResult(NamedTuple):
    ids: jax.Array     # (Q, h) int32 ascending by dist (sentinel-padded)
    dists: jax.Array   # (Q, h) f32
    hops: jax.Array    # (Q,) int32 — number of node expansions
    n_dist: jax.Array  # (Q,) int32 — number of distance computations
    # (Q,) int32 — while_loop rounds (sequential trips). With expand=E each
    # round expands up to E nodes, so rounds ∈ [ceil(hops/E), hops]; at
    # expand=1, rounds == hops. None for results that never ran a beam
    # (hand-built tuples, pure-scan engines).
    rounds: Optional[jax.Array] = None
    # (Q,) bool — True where the search stopped with unexpanded finite
    # candidates still pending (a deadline budget or max_steps cut it off):
    # the beam is an honest best-so-far, not a converged answer. None for
    # results that never ran a beam.
    truncated: Optional[jax.Array] = None
    # Host-side python bool set by the sharded engines: True when the
    # answer is known incomplete at the SERVING layer (dead shards dropped
    # from the merge, stragglers charged dead by the quorum deadline).
    # None for single-process engines and raw beam results.
    degraded: Optional[bool] = None


class Trace(NamedTuple):
    beam_ids: jax.Array    # (Q, T, h) beam AFTER each round's merge
    beam_dists: jax.Array  # (Q, T, h)
    hop_valid: jax.Array   # (Q, T) bool — round actually happened
    result: SearchResult


def _bit_get(bits: jax.Array, idx: jax.Array) -> jax.Array:
    return (bits[idx >> 5] >> (idx & 31)) & 1


# Width where the sort-based first-occurrence overtakes the all-pairs
# compare. Measured on the CPU CI host (Q=200 vmapped): all-pairs 4.1 ms vs
# sort 19.5 ms at W=256, 61 ms vs 40 ms at W=512 — quadratic lanes are
# VPU/SIMD-parallel and beat the sort's large constant until W ≈ 256-512;
# past that the O(W log W) sort keeps very wide frontiers cheap.
_SORT_DEDUP_MIN_W = 257


def _first_occurrence(idx: jax.Array, on: jax.Array) -> jax.Array:
    """True for the FIRST ``on`` lane holding each distinct id, else False.

    Width-adaptive (see ``_SORT_DEDUP_MIN_W``): up to W = 256 the strictly-
    lower-triangular all-pairs compare (the pre-PR ``_scatter_or`` idiom,
    O(W²) lanes but embarrassingly lane-parallel); beyond that, stable-
    argsort the ids (off lanes pushed to +max so they sort last), mark lanes
    equal to their sorted predecessor as duplicates, and scatter the flags
    back — O(W log W), so frontier dedup stays cheap however wide
    ``expand``·R grows.
    """
    w = idx.shape[0]
    idx = idx.astype(jnp.int32)
    if w < _SORT_DEDUP_MIN_W:
        same = (idx[:, None] == idx[None, :]) & on[None, :]
        tri = jnp.arange(w)[:, None] > jnp.arange(w)[None, :]
        return on & ~jnp.any(same & tri, axis=1)
    key = jnp.where(on, idx, jnp.int32(2**31 - 1))
    order = jnp.argsort(key)                      # stable → first = lowest lane
    sk = key[order]
    first_sorted = jnp.concatenate(
        [jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    return jnp.zeros((w,), bool).at[order].set(first_sorted) & on


def _scatter_bits(bits: jax.Array, idx: jax.Array, on: jax.Array) -> jax.Array:
    """OR bit ``idx[i]`` into the bitset for every ``on`` lane.

    Precondition: the ``on`` lanes hold DISTINCT ids. Then every (word, bit)
    contribution is unique, so a single scatter-ADD into a zero array equals
    the (missing) scatter-OR primitive.
    """
    word = jnp.where(on, idx >> 5, 0)
    mask = jnp.where(on, jnp.uint32(1) << (idx & 31).astype(jnp.uint32),
                     jnp.uint32(0))
    return bits | jnp.zeros_like(bits).at[word].add(mask)


def _scatter_or(bits: jax.Array, idx: jax.Array, on: jax.Array) -> jax.Array:
    """OR bit ``idx[i]`` into the bitset for every ``on`` lane, duplicate-safe
    (sort-based first-occurrence dedup + one scatter-add)."""
    return _scatter_bits(bits, idx, _first_occurrence(idx, on))


def _single_query(neighbors: jax.Array, entries: jax.Array, qdata,
                  dist_fn: Callable, h: int, max_steps: int,
                  trace_len: int = 0, expand: int = 1,
                  tombstones: Optional[jax.Array] = None,
                  lb_dist_fn: Optional[Callable] = None,
                  m_prefix: int = 0, m_total: int = 0,
                  prune_eps: float = 0.0,
                  lb_scale_fn: Optional[Callable] = None,
                  max_rounds: Optional[jax.Array] = None,
                  max_n_dist: Optional[jax.Array] = None):
    """Search for ONE query; built to be vmapped. ``entries`` is the (S,)
    per-query entry set (S=1 ≡ the classic single-entry beam, bit-identical).
    ``max_rounds`` / ``max_n_dist`` are TRACED deadline budgets gating only
    the loop condition (see module docstring). Returns result (+trace)."""
    n = neighbors.shape[0]
    r = neighbors.shape[1]
    e = max(1, min(expand, h))
    s = entries.shape[0]
    # hop pruning is compiled in only when fully configured; prune_eps=0 is
    # the documented OFF switch (bit-identical to the unpruned beam)
    prune = (lb_dist_fn is not None and prune_eps > 0.0
             and 0 < m_prefix < m_total)
    if prune:
        # extrapolation factor d̂ = d_m′ · cal, folded together with (1+ε)
        # into one loop-invariant gate scale. Per-query calibration
        # (lb_scale_fn) corrects the uniform M/m′ ratio for anisotropic
        # subspace masses — computed ONCE per query, outside the loop.
        cal = (lb_scale_fn(qdata) if lb_scale_fn is not None
               else jnp.float32(m_total) / jnp.float32(m_prefix))
        gate_scale = cal * jnp.float32(1.0 + prune_eps)
    # sentinel-inclusive id range is [0, n]: word(n) = n//32, so n//32 + 1
    # words always suffice ((n+31)//32 + 1 is a safe ceiling of that; the
    # old (n+32)//32 + 1 over-allocated a word for most n)
    nwords = (n + 31) // 32 + 1

    def is_dead(idx: jax.Array) -> jax.Array:
        # bitset lookup guarded to [0, n): sentinel slots and padding lanes
        # read bit 0's word but their result is never used un-masked
        safe = jnp.where(idx < n, idx, 0)
        return _bit_get(tombstones, safe).astype(bool) & (idx < n)

    if s == 1:
        # the classic single-entry init, op for op (bit-identity contract)
        entry = entries[0]
        ids0 = jnp.full((h,), n, jnp.int32).at[0].set(entry)
        d_entry = dist_fn(qdata, entries)[0]
        if tombstones is not None:
            d_entry = jnp.where(is_dead(entry), DEAD_ENTRY_DIST, d_entry)
        dists0 = jnp.full((h,), INF).at[0].set(d_entry)
        exp0 = jnp.ones((h,), bool).at[0].set(False)
        visited0 = _scatter_or(jnp.zeros((nwords,), jnp.uint32), entries,
                               jnp.ones((1,), bool))
        n_seeds = jnp.int32(1)
    else:
        # multi-entry init: dedupe the set, score every distinct valid
        # entry in ONE dist_fn call, sort, install as the initial beam
        sh = min(s, h)
        ok = (entries >= 0) & (entries < n)
        uniq = _first_occurrence(entries, ok)
        d_ent = dist_fn(qdata, jnp.where(uniq, entries, 0))
        d_ent = jnp.where(uniq, d_ent, INF)
        if tombstones is not None:
            # per-entry DEAD_ENTRY_DIST: a dead seed still routes (finite)
            # but any live seed outranks it; all-dead falls back to pure
            # DEAD_ENTRY_DIST routing like the classic deleted-medoid case
            d_ent = jnp.where(uniq & is_dead(entries), DEAD_ENTRY_DIST,
                              d_ent)
        neg, order = jax.lax.top_k(-d_ent, s)
        sd = -neg
        sids = jnp.where(sd < INF, entries[order], n)
        ids0 = jnp.full((h,), n, jnp.int32).at[:sh].set(sids[:sh])
        dists0 = jnp.full((h,), INF).at[:sh].set(sd[:sh])
        exp0 = jnp.ones((h,), bool).at[:sh].set(sd[:sh] == INF)
        visited0 = _scatter_bits(jnp.zeros((nwords,), jnp.uint32), entries,
                                 uniq)
        n_seeds = jnp.sum(uniq.astype(jnp.int32))

    do_trace = trace_len > 0
    tb_ids0 = jnp.full((max(trace_len, 1), h), n, jnp.int32)
    tb_d0 = jnp.full((max(trace_len, 1), h), INF)
    tb_v0 = jnp.zeros((max(trace_len, 1),), bool)

    def cond(state):
        step, ids, dists, exp, visited, hops, ndist, tbi, tbd, tbv = state
        live = jnp.logical_and(step < max_steps,
                               jnp.any(~exp & (dists < INF)))
        # deadline budgets (None compiles out — bit-identical): checked
        # before each round, so rounds never exceeds max_rounds and n_dist
        # overshoots its cap by at most one round's frontier. Under vmap
        # the while_loop batching rule freezes the whole carry of a lane
        # whose cond is false, so an exhausted query keeps its best-so-far
        # beam while the rest of the batch keeps stepping.
        if max_rounds is not None:
            live = jnp.logical_and(live, step < max_rounds)
        if max_n_dist is not None:
            # loop-internal ndist is in SUBSPACE units when pruning is on
            # (converted back after the loop); scale the cap to match
            cap = jnp.int32(max_n_dist) * (jnp.int32(m_total) if prune
                                           else jnp.int32(1))
            live = jnp.logical_and(live, ndist < cap)
        return live

    def body(state):
        step, ids, dists, exp, visited, hops, ndist, tbi, tbd, tbv = state
        # 1. pick the best `e` unexpanded beam entries (e=1 ≡ argmin; top_k
        #    breaks ties toward the lowest index, like argmin)
        with jax.named_scope("beam.select"):
            cand = jnp.where(~exp & (dists < INF), dists, INF)
            neg_sel, sel = jax.lax.top_k(-cand, e)
            sel_ok = -neg_sel < INF                # lanes actually selected
            # non-ok lanes are already expanded or INF slots (exp True by
            # the merge invariant below), so the unconditional set is a
            # no-op there
            exp = exp.at[sel].set(True)
            hops = hops + jnp.sum(sel_ok.astype(jnp.int32))
        # 2. expand the frontier: gather e·R neighbor ids, drop pads,
        #    visited vertices, and (e>1) cross-row duplicates
        with jax.named_scope("beam.visited"):
            nbr = neighbors[jnp.where(sel_ok, ids[sel], 0)]      # (e, R)
            flat = nbr.reshape(e * r)
            valid = (sel_ok[:, None] & (nbr < n)).reshape(e * r)
            seen = _bit_get(visited, jnp.where(valid, flat, 0)).astype(bool)
            fresh = valid & ~seen
            if e > 1:
                # two frontier rows may share a neighbor; keep the first lane
                # (then every fresh id is distinct — _scatter_bits suffices)
                fresh = _first_occurrence(flat, fresh)
                visited = _scatter_bits(visited, flat, fresh)
            else:
                # legacy semantics exactly: fresh keeps theoretical in-row
                # dups (scored twice, like the pre-PR beam), dedup only inside
                # the duplicate-safe scatter — bit-identical regression
                # contract
                visited = _scatter_or(visited, flat, fresh)
        # 3. ONE dist_fn call for the whole e·R frontier (on TPU: one fused
        #    hop-ADC kernel invocation instead of e narrow ones)
        if prune:
            # probabilistic gate: score the frontier on the first m_prefix
            # subspaces only (a certified lower bound — d_m′ ≤ d_M, every
            # LUT entry ≥ 0), EXTRAPOLATE it to a full-distance estimate
            # d̂ = d_m′·cal (cal = calibrated or uniform M/m′ mass ratio,
            # hoisted above the loop), and full-score just the lanes whose
            # estimate beats the worst beam slot by margin ε. The raw bound
            # prunes only ~nothing (it sits far below any full-distance τ);
            # the extrapolation prunes like the full distance would, at
            # m′/M of the cost — mistaken prunes are possible (hence
            # "probabilistic"), bounded by ε. τ = INF while the
            # beam is unfilled, so nothing is pruned before the beam warms
            # up. Pruned lanes stay VISITED — churn never retraces them —
            # and mask to the sentinel, so shapes never change. n_dist here
            # is in SUBSPACE units (every fresh lane paid m_prefix, kept
            # lanes m_total on top); it is converted back to
            # full-LUT-equivalents after the loop.
            tau = dists[h - 1]
            d_lb = lb_dist_fn(qdata, jnp.where(fresh, flat, 0))
            keep = fresh & (d_lb * gate_scale <= tau)
            nd = dist_fn(qdata, jnp.where(keep, flat, 0))
            nd = jnp.where(keep, nd, INF)
            ndist = ndist + (m_prefix * jnp.sum(fresh.astype(jnp.int32))
                             + m_total * jnp.sum(keep.astype(jnp.int32)))
            front = keep
        else:
            nd = dist_fn(qdata, jnp.where(fresh, flat, 0))
            nd = jnp.where(fresh, nd, INF)
            ndist = ndist + jnp.sum(fresh.astype(jnp.int32))
            front = fresh
        if tombstones is not None:
            # tombstoned neighbors were scored (counted in ndist — the
            # kernel did the work) but rank +inf: marked expanded by the
            # merge invariant, so routing never continues THROUGH them
            nd = jnp.where(is_dead(flat), INF, nd)
        # 4. merge beam ∪ frontier in a single (h + e·R)-wide top-k
        with jax.named_scope("beam.merge"):
            all_ids = jnp.concatenate([ids, jnp.where(front, flat, n)])
            all_d = jnp.concatenate([dists, nd])
            all_e = jnp.concatenate([exp, jnp.zeros((e * r,), bool)])
            neg, order = jax.lax.top_k(-all_d, h)
            ids = all_ids[order]
            dists = -neg
            exp = all_e[order] | (dists == INF)
        # 5. trace the ranked candidate beam (paper Def. 6); rounds beyond
        #    trace_len must NOT clobber the last recorded slot
        if do_trace:
            ti = jnp.minimum(step, trace_len - 1)
            in_range = step < trace_len
            tbi = tbi.at[ti].set(jnp.where(in_range, ids, tbi[ti]))
            tbd = tbd.at[ti].set(jnp.where(in_range, dists, tbd[ti]))
            tbv = tbv.at[ti].set(tbv[ti] | in_range)
        return (step + 1, ids, dists, exp, visited, hops, ndist, tbi, tbd, tbv)

    ndist0 = jnp.int32(m_total) * n_seeds if prune else n_seeds
    state = (jnp.int32(0), ids0, dists0, exp0, visited0,
             jnp.int32(0), ndist0, tb_ids0, tb_d0, tb_v0)
    step, ids, dists, exp, visited, hops, ndist, tbi, tbd, tbv = \
        jax.lax.while_loop(cond, body, state)
    if prune:
        # subspace units → full-LUT-equivalents (ceil: a lone partial score
        # still counts as work done)
        ndist = (ndist + jnp.int32(m_total - 1)) // jnp.int32(m_total)
    # honest truncation flag: unexpanded finite candidates still pending
    # means SOMETHING stopped us short of convergence (budget or max_steps)
    # — the beam is best-so-far, not the converged answer. Computed before
    # the tombstone scrub: the pending frontier, not the scrub, decides it.
    truncated = jnp.any(~exp & (dists < INF))
    if tombstones is not None:
        # scrub: a tombstoned id (incl. a dead entry at DEAD_ENTRY_DIST)
        # NEVER appears in the returned beam, at any width
        dead = is_dead(ids)
        ids = jnp.where(dead, n, ids)
        dists = jnp.where(dead, INF, dists)
    res = (ids, dists, hops, ndist, step, truncated)
    return res + ((tbi, tbd, tbv) if do_trace else ())


def _normalize_entries(entry: jax.Array, nq: int) -> jax.Array:
    """Canonicalize ``entry`` to a (Q, S) per-query entry-set matrix:
    () shared vertex → (Q, 1); (Q,) per-query vertex → (Q, 1); (Q, S)
    entry sets pass through. S=1 runs the classic single-entry init."""
    entry = jnp.asarray(entry, jnp.int32)
    if entry.ndim == 0:
        return jnp.broadcast_to(entry, (nq, 1))
    if entry.ndim == 1:
        return entry[:, None]
    return entry


@functools.partial(jax.jit,
                   static_argnames=("dist_fn", "h", "max_steps", "expand",
                                    "lb_dist_fn", "m_prefix", "m_total",
                                    "prune_eps", "lb_scale_fn"))
def beam_search(neighbors: jax.Array, entry: jax.Array, qdatas,
                dist_fn: Callable, *, h: int = 32,
                max_steps: int = 256, expand: int = 1,
                tombstones: Optional[jax.Array] = None,
                lb_dist_fn: Optional[Callable] = None,
                m_prefix: int = 0, m_total: int = 0,
                prune_eps: float = 0.0,
                lb_scale_fn: Optional[Callable] = None,
                max_rounds=None, max_n_dist=None) -> SearchResult:
    """Batched beam search.

    Args:
      neighbors: (N, R) padded adjacency (sentinel N).
      entry:     () int32 entry vertex (shared) — the PG medoid; or (Q,)
                 per-query entries; or a (Q, S) per-query entry SET
                 (multi-entry seeding, DESIGN.md §11 — search/seed.py
                 produces these; lanes < 0 or ≥ N are ignored padding).
      qdatas:    per-query pytree, leading axis Q (e.g. LUTs (Q, M, K) for ADC
                 routing or raw queries (Q, D) for exact routing).
      dist_fn:   (qdata, ids (B,)) -> (B,) f32 distances for one query; B is
                 the frontier width expand·R.
      h:         beam width (the paper's global candidate set size).
      max_steps: ROUND cap (safety for pathological graphs). With expand=E a
                 round expands up to E nodes, so the hop budget it implies is
                 max_steps·E.
      expand:    frontier batch size E — nodes expanded per round
                 (DESIGN.md §9). 1 (default) is the classic, bit-identical
                 best-first beam; larger E trades a few wasted expansions for
                 ~E× fewer sequential trips.
      tombstones: optional (W,) uint32 deleted-vertex bitset, shared across
                 the batch (streaming deletes, DESIGN.md §10): bit i set ⇒
                 vertex i ranks +inf, is never expanded, and is scrubbed
                 from the returned beam. W must cover ids [0, N) — the
                 visited-set sizing (N+31)//32 + 1 always does. Traced (not
                 static): updating the bitset between calls never re-jits.
      lb_dist_fn / m_prefix / m_total / prune_eps: probabilistic hop pruning
                 (DESIGN.md §11). ``lb_dist_fn`` scores the first
                 ``m_prefix`` of ``m_total`` subspaces
                 (``make_adc_dist_fn(m_prefix=)``) — a certified lower
                 bound d_m′ ≤ d_M; each round full-scores only frontier
                 lanes whose EXTRAPOLATED estimate satisfies
                 ``d_m′·cal·(1+ε) ≤ τ`` (τ = worst beam distance). All
                 four must be set; ``prune_eps=0`` (default) compiles the
                 pass out — bit-identical to the unpruned beam.
      lb_scale_fn: optional per-query extrapolation calibration
                 (``make_lb_scale_fn``): qdata -> scalar cal ≥ 1. Default
                 None uses the uniform mass ratio cal = M/m′, which
                 over-prunes on anisotropic data (DESIGN.md §11).
      max_rounds / max_n_dist: per-call deadline budgets (DESIGN.md §13) —
                 a round cap and a distance-evaluation cap (full-LUT
                 equivalents; under hop pruning the n_dist overshoot is at
                 most one round's frontier). TRACED scalars shared across
                 the batch: sweeping a deadline never retraces, and the
                 early exit is fixed-shape. An exhausted query returns its
                 best-so-far beam with ``truncated=True``; ``None``
                 (default) compiles the check out — bit-identical to the
                 unbudgeted beam.
    """
    nq = jax.tree.leaves(qdatas)[0].shape[0]
    entries = _normalize_entries(entry, nq)
    fn = lambda e, qd: _single_query(neighbors, e, qd, dist_fn, h, max_steps,
                                     expand=expand, tombstones=tombstones,
                                     lb_dist_fn=lb_dist_fn,
                                     m_prefix=m_prefix, m_total=m_total,
                                     prune_eps=prune_eps,
                                     lb_scale_fn=lb_scale_fn,
                                     max_rounds=max_rounds,
                                     max_n_dist=max_n_dist)
    ids, dists, hops, ndist, rounds, truncated = jax.vmap(fn)(entries, qdatas)
    return SearchResult(ids, dists, hops, ndist, rounds, truncated)


@functools.partial(jax.jit, static_argnames=("dist_fn", "h", "max_steps",
                                             "trace_len", "expand",
                                             "lb_dist_fn", "m_prefix",
                                             "m_total", "prune_eps",
                                             "lb_scale_fn"))
def beam_search_trace(neighbors: jax.Array, entry: jax.Array, qdatas,
                      dist_fn: Callable, *, h: int = 32, max_steps: int = 256,
                      trace_len: int = 64, expand: int = 1,
                      tombstones: Optional[jax.Array] = None,
                      lb_dist_fn: Optional[Callable] = None,
                      m_prefix: int = 0, m_total: int = 0,
                      prune_eps: float = 0.0,
                      lb_scale_fn: Optional[Callable] = None,
                      max_rounds=None, max_n_dist=None) -> Trace:
    """Beam search that also records the ranked beam at every round.

    ``hop_valid[q, t]`` flags ROUNDS (while_loop trips): with expand=E one
    valid slot covers up to E expansions, and the flagged prefix counts
    min(rounds, trace_len) — at expand=1 that is min(hops, trace_len).
    """
    nq = jax.tree.leaves(qdatas)[0].shape[0]
    entries = _normalize_entries(entry, nq)
    fn = lambda e, qd: _single_query(neighbors, e, qd, dist_fn, h, max_steps,
                                     trace_len=trace_len, expand=expand,
                                     tombstones=tombstones,
                                     lb_dist_fn=lb_dist_fn,
                                     m_prefix=m_prefix, m_total=m_total,
                                     prune_eps=prune_eps,
                                     lb_scale_fn=lb_scale_fn,
                                     max_rounds=max_rounds,
                                     max_n_dist=max_n_dist)
    ids, dists, hops, ndist, rounds, truncated, tbi, tbd, tbv = \
        jax.vmap(fn)(entries, qdatas)
    return Trace(tbi, tbd, tbv,
                 SearchResult(ids, dists, hops, ndist, rounds, truncated))


# --------------------------------------------------------------------------
# Distance functions
# --------------------------------------------------------------------------

def make_exact_dist_fn(vectors: jax.Array) -> Callable:
    """qdata = query vector (D,). vectors must be (N+1, D) sentinel-padded."""
    def dist_fn(q, ids):
        v = vectors[ids]
        return jnp.sum((v - q[None, :]) ** 2, axis=-1)
    return dist_fn


def make_lb_scale_fn(*, packed: bool = False, m_prefix: int) -> Callable:
    """Per-query calibration of the hop-pruning extrapolation factor.

    qdata matches ``make_adc_dist_fn``: a LUT (M, K), or a per-query
    ``pq.pack.QuantizedLUT`` when ``packed=True``. Returns a scalar
    ``cal ≥ 1`` — the estimate of ``E[d_M] / E[d_m′]`` under
    code-independent subspace draws: the ratio of the full LUT's mean mass
    to the first-``m_prefix`` rows' mean mass. The naive uniform ratio
    ``M/m′`` assumes every subspace carries equal distance mass; on
    anisotropic data (decaying spectrum) the LEADING subspaces carry more,
    so the uniform extrapolation overshoots and over-prunes — this ratio is
    the data-corrected replacement, free to compute (the query already
    built the LUT) and exact in expectation when sub-codes are uniform.
    Clamped below at 1 so d̂ never drops under the certified bound d_m′.
    """
    if packed:
        def scale_fn(qlut):
            lut, scale, bias = qlut             # (M, 16) u8, (), ()
            m = lut.shape[0]
            # zero padding in unused LUT columns deflates every row's mean
            # by the same K/16 factor — it cancels in the ratio
            rm = jnp.mean(lut.astype(jnp.float32), axis=-1)   # (M,)
            num = scale * jnp.sum(rm) + m * bias
            den = scale * jnp.sum(rm[:m_prefix]) + m_prefix * bias
            return jnp.maximum(num / jnp.maximum(den, jnp.float32(1e-20)),
                               jnp.float32(1.0))
        return scale_fn

    def scale_fn(lut):
        rm = jnp.mean(lut, axis=-1)                           # (M,)
        return jnp.maximum(jnp.sum(rm) / jnp.maximum(jnp.sum(rm[:m_prefix]),
                                                     jnp.float32(1e-20)),
                           jnp.float32(1.0))
    return scale_fn


def make_adc_dist_fn(codes: jax.Array, *, packed: bool = False,
                     backend: str = "auto",
                     tombstones: Optional[jax.Array] = None,
                     m_prefix: int = 0) -> Callable:
    """qdata = LUT (M, K) — or a per-query ``pq.pack.QuantizedLUT``
    ((M, 16) u8 lut, scale, bias) when ``packed=True``. codes must be
    (N+1, M) sentinel-padded (fs4: (N+1, ceil(M/2)) packed bytes).

    ``tombstones`` (optional (W,) uint32 bitset over ids [0, N)) bakes a
    deleted-vertex mask into the dist fn: tombstoned ids return +inf.
    Because dist fns are STATIC jit arguments, each distinct bitset makes a
    distinct callable — fine for a frozen snapshot, wrong for churn. A
    streaming caller should pass ``beam_search(..., tombstones=)`` instead,
    where the bitset is traced, updates never re-jit, a tombstoned ENTRY
    still routes (DEAD_ENTRY_DIST), and the returned beam is scrubbed.
    The baked mask has neither entry rescue nor scrub: a search ENTERED at
    a tombstoned vertex sees d_entry = +inf and terminates empty, so don't
    point it at a graph whose entry may be deleted.

    The ids vector is ONE beam frontier — width R classically, E·R under
    multi-expansion (``beam_search(expand=E)``); the fused kernels auto-tune
    their query tile to the width (kernels/hop_adc.py).

    Backend dispatch for the per-hop hot loop (kernels.ops semantics):

    * CPU (``backend="auto"`` off-TPU, or ``"ref"``): a jnp gather — the
      per-round read is small (≤ E·R rows) and XLA fuses it. The fs4 path
      nibble-unpacks the gathered bytes and accumulates the uint8 LUT in
      int32 before the one affine dequant.
    * TPU (``"auto"`` on-TPU, or ``"pallas"``/``"interpret"``): the fused
      hop-ADC Pallas kernel (kernels/hop_adc.py; packed twin for fs4) —
      neighbor-row gather and LUT reduce in ONE kernel, so the gathered
      codes never round-trip HBM. The kernel is batched over queries;
      under beam_search's vmap the per-query call batches into the
      kernel's query grid axis.

    ``m_prefix > 0`` makes a PARTIAL-LUT distance over only the first
    ``m_prefix`` subspaces — a lower bound on the full distance (every LUT
    entry is a squared subdistance ≥ 0; fs4 dequant uses ``m_prefix · bias``
    with bias ≥ 0, so the bound also holds in the quantized metric). This is
    the ``lb_dist_fn`` for ``beam_search`` hop pruning. ``m_prefix=0`` (or
    ≥ M) is the full distance, code path untouched.
    """
    if tombstones is not None:
        ts = jnp.asarray(tombstones, jnp.uint32)
        inner = make_adc_dist_fn(codes, packed=packed, backend=backend,
                                 m_prefix=m_prefix)
        n = codes.shape[0] - 1              # codes are sentinel-padded

        def dist_fn(qdata, ids):
            d = inner(qdata, ids)
            dead = (_bit_get(ts, jnp.where(ids < n, ids, 0)).astype(bool)
                    & (ids < n))
            return jnp.where(dead, INF, d)
        return dist_fn

    from repro.kernels import ops

    use_fused = ops._resolve(backend) != "ref"
    if packed:
        if use_fused:
            def dist_fn(qlut, ids):
                return ops.hop_adc_fs(codes, ids[None], qlut.lut[None],
                                      qlut.scale[None], qlut.bias[None],
                                      backend=backend, m_prefix=m_prefix)[0]
            return dist_fn

        def dist_fn(qlut, ids):
            lut, scale, bias = qlut                   # (M, 16) u8, (), ()
            m = lut.shape[0]
            mp = m_prefix if 0 < m_prefix < m else m
            p = codes[ids].astype(jnp.int32)          # (B, ceil(M/2))
            nib = jnp.stack([p & 0xF, (p >> 4) & 0xF], axis=-1)
            c = nib.reshape(p.shape[0], -1)[:, :mp]   # (B, mp)
            vals = lut.astype(jnp.int32)[jnp.arange(mp)[None, :], c]
            acc = jnp.sum(vals, axis=-1)              # (B,) int32, exact
            return scale * acc.astype(jnp.float32) + mp * bias
        return dist_fn

    m = codes.shape[1]
    mp = m_prefix if 0 < m_prefix < m else m
    if use_fused:
        def dist_fn(lut, ids):
            return ops.hop_adc(codes, ids[None], lut[None],
                               backend=backend, m_prefix=m_prefix)[0]
        return dist_fn

    def dist_fn(lut, ids):
        c = codes[ids].astype(jnp.int32)[:, :mp]      # (B, mp)
        vals = lut[jnp.arange(mp)[None, :], c]        # (B, mp)
        # subspace order, as the fused kernel accumulates: the two paths
        # then agree bit for bit, so a beam never forks on rounding
        acc = vals[:, 0]
        for j in range(1, mp):
            acc = acc + vals[:, j]
        return acc
    return dist_fn
