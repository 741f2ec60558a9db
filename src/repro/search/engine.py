"""Serving engines (paper §7): in-memory, SSD-hybrid (DiskANN), and the two
sharded scatter-gather scenarios (exhaustive scan and graph-routed).

All engines route with PQ-ADC distances. They accept any quantizer exposing
the (codes, lut_fn) protocol — classic PQ / OPQ (pq.base.QuantizerModel),
the learned RPQ (core.rpq), or Catalyst.

All beam-routed engines also thread ``expand`` (frontier batching,
DESIGN.md §9): each beam round expands E nodes through one E·R-wide fused
hop-ADC call, and results report ``rounds`` (sequential trips) next to
``hops`` (expansions).

They additionally thread the adaptive-routing knobs (DESIGN.md §11):
``entries=S`` seeds each query's beam with S near-query entry points from a
PQ-hash coarse index over the resident codes (search/seed.py — built
lazily on the first seeded search, per shard for the sharded engines), and
``prune_eps=ε`` gates each round's full-LUT scoring behind a partial-LUT
lower bound (``m_prefix`` subspaces, default half). ``entries=1,
prune_eps=0`` (the defaults) is bit-identical to the classic beam.

* :class:`InMemoryEngine` — codes + codebook + PG in RAM; next-hop selection
  and the final top-k use ONLY PQ distances (no rerank). Memory = N·M bytes
  + graph.
* :class:`HybridEngine` — DiskANN: codes + codebook in RAM; full vectors +
  PG "on SSD". Routing uses ADC; every expansion costs one simulated SSD
  read (the node's 4 KiB block holds its vector + adjacency, as in DiskANN's
  disk layout); the final candidates are re-ranked with exact distances.
  IO time is modeled as reads × latency (default 100 µs, ~NVMe) — reported
  separately from compute time so real-hardware numbers can be projected.
* :class:`ShardedEngine` — multi-device scatter-gather SCAN: codes
  (+ vectors) row-sharded over the mesh via dist.sharding.rpq_rows_spec;
  each shard exhaustively scans its rows with the ADC kernel and returns a
  LOCAL top-k, merged with dist.fault.partial_merge so a dead/straggler
  shard degrades recall instead of failing the query.
* :class:`ShardedGraphEngine` — multi-device graph ROUTING (DESIGN.md §6):
  each shard owns a contiguous row range AND an independent Vamana subgraph
  over it (graphs/partition.py); the batched beam search runs inside
  shard_map, per-hop distances come from the fused hop-ADC Pallas kernel on
  TPU, optional DiskANN-style local exact rerank, same partial_merge
  gather. O(hops·R) distance work per shard per query instead of O(N/S).

The per-shard bodies below are the ONE implementation of each scatter-
gather pattern — launch/cells.py's adc_bulk / serve_1m / sharded_graph
dry-run cells compile these same functions, and launch/serve.py serves
them for real.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as shd
from repro.dist.fault import partial_merge, resolve_quorum
from repro.graphs.adjacency import Graph
from repro.graphs.partition import PartitionedGraph
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.pq.pack import QuantizedLUT, unpack_codes
from repro.search import beam
from repro.search import seed as sseed
from repro.search.beam import SearchResult

# Layout dispatch: every engine accepts EITHER the classic u8 layout
# ((N, M) byte codes + (Q, M, K) f32 LUTs) or the fast-scan fs4 layout
# ((N, ceil(M/2)) packed nibble codes + pq.pack.QuantizedLUT uint8 tables,
# DESIGN.md §8). The lut_fn's return type is the single source of truth —
# a QuantizedLUT means the codes are packed; no separate flag to desync.


def _is_packed(luts) -> bool:
    return isinstance(luts, QuantizedLUT)


def _bulk_adc(codes_l, luts) -> jax.Array:
    """(n_local, M|Mb) codes × (Q,...) LUTs → (Q, n_local) ADC distances,
    dispatching on layout (the one switch for the scan engines)."""
    if _is_packed(luts):
        return kops.adc_scan_fs(codes_l, luts.lut, luts.scale, luts.bias)
    return kref.adc_scan_batch_ref(codes_l, luts)


def _lut_specs(luts):
    """Replicated shard_map in_specs for a LUT input that may be a plain
    (Q, M, K) array or a QuantizedLUT pytree."""
    return jax.tree.map(lambda a: P(*([None] * jnp.ndim(a))), luts)


def _cached_dist_fn(cache: dict, codes_p, luts, m_prefix: int = 0,
                    backend: str = "auto"):
    """Per-(layout, prefix) hop dist fn, cached so beam_search's jit sees
    ONE static callable per layout (u8 vs fs4-packed, decided by the lut
    type) and per partial-LUT prefix (``m_prefix>0`` builds the hop-pruning
    lower-bound fn, DESIGN.md §11). ``backend`` picks the per-hop kernel
    (beam.make_adc_dist_fn)."""
    packed = _is_packed(luts)
    fn = cache.get((packed, m_prefix, backend))
    if fn is None:
        fn = beam.make_adc_dist_fn(codes_p, packed=packed,
                                   m_prefix=m_prefix, backend=backend)
        cache[(packed, m_prefix, backend)] = fn
    return fn


def _cached_scale_fn(cache: dict, luts, m_prefix: int):
    """Per-(layout, prefix) extrapolation-calibration fn
    (``beam.make_lb_scale_fn``), cached for the same static-identity reason
    as ``_cached_dist_fn`` — beam_search's jit must see ONE callable per
    configuration or every search recompiles."""
    packed = _is_packed(luts)
    key = ("cal", packed, m_prefix)
    fn = cache.get(key)
    if fn is None:
        fn = beam.make_lb_scale_fn(packed=packed, m_prefix=m_prefix)
        cache[key] = fn
    return fn


def _lut_m(luts) -> int:
    """Number of subquantizers M from either LUT layout."""
    return (luts.lut if _is_packed(luts) else luts).shape[1]


def _prune_cfg(luts, prune_eps: float, m_prefix: int) -> tuple:
    """Resolve the hop-pruning statics (m_prefix, m_total) for beam_search:
    ε ≤ 0 disables — (0, 0), the bit-identical path; ``m_prefix=0``
    auto-picks a QUARTER of the subspaces (an M=1 corpus can never prune).
    The gate extrapolates the prefix to a full-distance estimate, so a
    short prefix keeps the partial pass cheap — empirically M/4 prunes as
    accurately as M/2 at half the partial-pass cost (DESIGN.md §11)."""
    if prune_eps <= 0:
        return 0, 0
    mt = _lut_m(luts)
    if mt < 2:
        return 0, 0
    mp = m_prefix if m_prefix > 0 else max(1, mt // 4)
    return max(1, min(mp, mt - 1)), mt


@dataclasses.dataclass
class InMemoryEngine:
    graph: Graph
    codes: jax.Array                  # (N, M) compact codes
    lut_fn: Callable                  # (Q, D) queries -> (Q, M, K) LUTs
    entry_fn: Optional[Callable] = None  # queries -> (Q,) entries (HNSW descend)
    backend: str = "auto"             # per-hop kernel (kernels.ops backends)

    def __post_init__(self):
        self._codes_p = kops.pad_sentinel_row(self.codes)
        self._dist_fns = {}
        self._seedix = None

    def _seed_index(self, luts) -> sseed.SeedIndex:
        """Coarse seeding index over the resident codes, built lazily on
        the first ``entries>1`` search (the lut type reveals the layout:
        fs4 corpora unpack once, host-side)."""
        if self._seedix is None:
            codes = jnp.asarray(self.codes)
            if _is_packed(luts):
                codes = unpack_codes(codes, _lut_m(luts))
            self._seedix = sseed.build_seed_index(np.asarray(codes))
        return self._seedix

    def search(self, queries: jax.Array, *, k: int = 10, h: int = 32,
               max_steps: int = 512, expand: int = 1, entries: int = 1,
               prune_eps: float = 0.0, m_prefix: int = 0,
               max_rounds=None, max_n_dist=None) -> SearchResult:
        """``max_rounds``/``max_n_dist`` are per-call deadline budgets
        (DESIGN.md §13): traced round / distance-evaluation caps; an
        exhausted query returns best-so-far with ``truncated=True``."""
        luts = self.lut_fn(queries)
        dist_fn = _cached_dist_fn(self._dist_fns, self._codes_p, luts,
                                  backend=self.backend)
        mp, mt = _prune_cfg(luts, prune_eps, m_prefix)
        lb_fn = (_cached_dist_fn(self._dist_fns, self._codes_p, luts, mp,
                                 self.backend) if mp else None)
        cal_fn = _cached_scale_fn(self._dist_fns, luts, mp) if mp else None
        seed_cost = jnp.int32(0)
        if entries > 1:
            ix = self._seed_index(luts)
            entry = ix.seed_entries(luts, entries)
            seed_cost = jnp.int32(ix.n_candidates)
        else:
            entry = (self.entry_fn(queries) if self.entry_fn is not None
                     else self.graph.medoid)
        res = beam.beam_search(self.graph.neighbors, entry, luts,
                               dist_fn, h=h, max_steps=max_steps,
                               expand=expand, lb_dist_fn=lb_fn,
                               m_prefix=mp, m_total=mt,
                               prune_eps=prune_eps if mp else 0.0,
                               lb_scale_fn=cal_fn,
                               max_rounds=max_rounds, max_n_dist=max_n_dist)
        return SearchResult(res.ids[:, :k], res.dists[:, :k], res.hops,
                            res.n_dist + seed_cost, res.rounds,
                            res.truncated)

    def memory_bytes(self) -> int:
        return (self.codes.size * self.codes.dtype.itemsize
                + self.graph.neighbors.size * 4)


@dataclasses.dataclass
class HybridEngine:
    """DiskANN-style: ADC routing + exact rerank from "SSD" vectors."""
    graph: Graph
    codes: jax.Array
    lut_fn: Callable
    vectors: jax.Array                # (N, D) original vectors ("on SSD")
    io_latency_s: float = 100e-6     # per 4 KiB node read (NVMe-class)
    entry_fn: Optional[Callable] = None
    backend: str = "auto"             # per-hop kernel (kernels.ops backends)

    def __post_init__(self):
        self._codes_p = kops.pad_sentinel_row(self.codes)
        self._vec_p = kops.pad_sentinel_row(
            jnp.asarray(self.vectors, jnp.float32))
        self._dist_fns = {}
        self._seedix = None

    def _seed_index(self, luts) -> sseed.SeedIndex:
        if self._seedix is None:
            codes = jnp.asarray(self.codes)
            if _is_packed(luts):
                codes = unpack_codes(codes, _lut_m(luts))
            self._seedix = sseed.build_seed_index(np.asarray(codes))
        return self._seedix

    @partial(jax.profiler.annotate_function, name="rpq.search")
    def search(self, queries: jax.Array, *, k: int = 10, h: int = 32,
               max_steps: int = 512, rerank: int = 0, expand: int = 1,
               entries: int = 1, prune_eps: float = 0.0,
               m_prefix: int = 0, max_rounds=None,
               max_n_dist=None) -> SearchResult:
        """rerank = how many beam candidates to re-rank exactly (0 → h;
        NEGATIVE skips the exact rerank entirely and answers from ADC
        distances — degradation-ladder level 4, DESIGN.md §13, saving the
        rerank's "SSD" vector reads under a tight deadline).
        ``max_rounds``/``max_n_dist``: traced per-call deadline budgets;
        exhausted queries return best-so-far with ``truncated=True``."""
        skip_rerank = rerank < 0
        rerank = h if rerank <= 0 else rerank
        k = min(k, rerank)  # cannot return more results than candidates
        # host spans on the profiler's clock; the beam's device steps carry
        # named scopes of their own (search/beam.py)
        with jax.profiler.TraceAnnotation("rpq.search.lut"):
            luts = self.lut_fn(queries)
        with jax.profiler.TraceAnnotation("rpq.search.beam"):
            dist_fn = _cached_dist_fn(self._dist_fns, self._codes_p, luts,
                                      backend=self.backend)
            mp, mt = _prune_cfg(luts, prune_eps, m_prefix)
            lb_fn = (_cached_dist_fn(self._dist_fns, self._codes_p, luts, mp,
                                     self.backend) if mp else None)
            cal_fn = (_cached_scale_fn(self._dist_fns, luts, mp) if mp
                      else None)
            seed_cost = jnp.int32(0)
            if entries > 1:
                ix = self._seed_index(luts)
                entry = ix.seed_entries(luts, entries)
                seed_cost = jnp.int32(ix.n_candidates)
            else:
                entry = (self.entry_fn(queries) if self.entry_fn is not None
                         else self.graph.medoid)
            res = beam.beam_search(self.graph.neighbors, entry, luts,
                                   dist_fn, h=h, max_steps=max_steps,
                                   expand=expand, lb_dist_fn=lb_fn,
                                   m_prefix=mp, m_total=mt,
                                   prune_eps=prune_eps if mp else 0.0,
                                   lb_scale_fn=cal_fn,
                                   max_rounds=max_rounds,
                                   max_n_dist=max_n_dist)
        with jax.profiler.TraceAnnotation("rpq.search.rerank"):
            if skip_rerank:
                ids, dists = res.ids[:, :k], res.dists[:, :k]
            else:
                ids, dists = _exact_rerank(self._vec_p, queries, res.ids,
                                           rerank, k)
            return SearchResult(ids, dists, res.hops, res.n_dist + seed_cost,
                                res.rounds, res.truncated)

    def io_time(self, res: SearchResult, *, expand: int = 1,
                entries: int = 1, io_fault_p: float = 0.0,
                retry=None, measured_io_s=None) -> jax.Array:
        """Modeled SSD time per query: one 4 KiB block read per expansion,
        but with frontier batching (``expand=E``) the ≤E reads of a round
        are issued CONCURRENTLY — DiskANN's beam-width IO batching — so the
        wall-clock is ROUNDS × latency, not hops × latency. Uses the
        measured per-query round count when the result carries one, else
        the ceil(hops/E) model.

        Multi-entry seeding (``entries>1``) charges ONE extra batched read:
        the bucket-probe candidates are contiguous small rows fetched in a
        single IO burst (the same batching model as a round's ≤E
        concurrent block reads), not a read per entry.

        ``io_fault_p``/``retry`` extend the model with transient-fault
        recovery (DESIGN.md §13): each round's batched read independently
        fails with probability ``io_fault_p`` per attempt and is retried
        under ``retry`` (a ``dist.retry.RetryPolicy``) — the per-read cost
        becomes the closed-form expected time over attempts + nominal
        backoff sleeps (``dist.retry.expected_retry_time_s``), so the
        resilience bench's retry-overhead rows are deterministic.

        ``measured_io_s`` swaps the model for a MEASUREMENT: pass a real
        storage tier's batch-total I/O stall (``DiskEngine.last_io
        ["io_wait_s"]``) and the per-query charge becomes that total
        amortized over the batch — the model stays the no-storage
        fallback, and benchmarks/disk_serving.py cross-checks the two."""
        if measured_io_s is not None:
            q = int(res.hops.shape[0])
            return jnp.full((q,), jnp.float32(measured_io_s / max(1, q)))
        if res.rounds is not None:
            rounds = res.rounds.astype(jnp.float32)
        else:
            rounds = jnp.ceil(res.hops.astype(jnp.float32) / expand)
        if entries > 1:
            rounds = rounds + jnp.float32(1.0)
        per_read = self.io_latency_s
        if io_fault_p > 0.0 and retry is not None:
            from repro.dist.retry import expected_retry_time_s
            per_read = expected_retry_time_s(retry, self.io_latency_s,
                                             io_fault_p)
        return rounds * jnp.float32(per_read)

    def memory_bytes(self) -> int:
        # resident = codes (+ codebook, negligible); graph+vectors on SSD
        return self.codes.size * self.codes.dtype.itemsize


@partial(jax.jit, static_argnames=("rerank", "k"))
def _exact_rerank(vec_p, queries, cand_ids, rerank: int, k: int):
    cand = cand_ids[:, :rerank]
    v = vec_p[cand]                                       # (Q, rerank, D)
    d = jnp.sum((v - queries[:, None, :]) ** 2, axis=-1)
    d = jnp.where(cand == vec_p.shape[0] - 1, jnp.inf, d)
    neg, order = jax.lax.top_k(-d, k)
    return jnp.take_along_axis(cand, order, axis=1), -neg


# ==========================================================================
# Sharded scatter-gather substrate (shared by ShardedEngine AND the
# launch/cells.py adc_bulk / serve_1m dry-run cells)
# ==========================================================================

flat_shard_index = shd.flat_shard_index  # the one definition of shard order


def _local_adc_topk(codes_l, luts, *, mesh, axes, n_local: int, k: int,
                    n_valid: Optional[int]):
    """One shard's scatter half: ADC-scan my rows, return LOCAL top-k with
    GLOBAL ids. (1, Q, k) leading shard axis for the gather."""
    d = _bulk_adc(codes_l, luts)                          # (Q, N_local)
    shard = flat_shard_index(mesh, axes)
    if n_valid is not None:  # mask divisibility-padding rows
        gid_row = shard * n_local + jnp.arange(n_local)
        d = jnp.where(gid_row[None, :] < n_valid, d, jnp.inf)
    neg, ids = jax.lax.top_k(-d, k)
    return (ids + shard * n_local)[None], (-neg)[None]


def _local_adc_serve(codes_l, vectors_l, luts, queries, *, mesh, axes,
                     n_local: int, k: int, shortlist: int,
                     n_valid: Optional[int]):
    """Scatter half with DiskANN-style local refinement: ADC shortlist →
    exact rerank against my vector rows → LOCAL top-k, global ids."""
    d = _bulk_adc(codes_l, luts)                          # (Q, N_local)
    shard = flat_shard_index(mesh, axes)
    if n_valid is not None:
        gid_row = shard * n_local + jnp.arange(n_local)
        d = jnp.where(gid_row[None, :] < n_valid, d, jnp.inf)
    _, cand = jax.lax.top_k(-d, shortlist)                # ADC shortlist
    cv = vectors_l[cand]                                  # (Q, shortlist, D)
    exact = jnp.sum((cv - queries[:, None, :]) ** 2, -1)
    if n_valid is not None:
        exact = jnp.where(cand + shard * n_local < n_valid, exact, jnp.inf)
    neg, order = jax.lax.top_k(-exact, k)
    gids = jnp.take_along_axis(cand, order, axis=1) + shard * n_local
    return gids[None], (-neg)[None]


def sharded_adc_scan(mesh, axes: tuple, codes, luts, *, k: int,
                     n_valid: Optional[int] = None):
    """Scatter: row-sharded (N, M) codes × replicated (Q, M, K) LUTs →
    per-shard (n_shards, Q, k) global ids + ADC distances.

    O(shards·k) gather traffic instead of the (Q, N) distance matrix
    (GSPMD's sharded top_k gathered it: 8.2 GB/dev → MBs)."""
    n_local = codes.shape[0] // shd.axis_size(mesh, axes)
    body = partial(_local_adc_topk, mesh=mesh, axes=axes, n_local=n_local,
                   k=k, n_valid=n_valid)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axes, None), _lut_specs(luts)),
        out_specs=(P(axes, None, None), P(axes, None, None)))(codes, luts)


def sharded_adc_serve(mesh, axes: tuple, codes, vectors, luts, queries, *,
                      k: int, shortlist: int, n_valid: Optional[int] = None):
    """Scatter with local exact rerank (serve_1m): row-sharded codes AND
    vectors; each shard reranks its own ADC shortlist from its local vector
    rows — the DiskANN shortlist pattern distributed faiss-style."""
    n_local = codes.shape[0] // shd.axis_size(mesh, axes)
    body = partial(_local_adc_serve, mesh=mesh, axes=axes, n_local=n_local,
                   k=k, shortlist=min(shortlist, n_local), n_valid=n_valid)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axes, None), P(axes, None), _lut_specs(luts),
                  P(None, None)),
        out_specs=(P(axes, None, None), P(axes, None, None)))(
            codes, vectors, luts, queries)


def merge_shard_topk(gids, dists, k: int):
    """Gather: (n_shards, Q, k_s) per-shard shortlists → global (Q, k)
    top-k. The in-jit, all-shards-alive merge; ShardedEngine uses
    dist.fault.partial_merge on the host instead to tolerate dead shards."""
    q = gids.shape[1]
    ds = dists.transpose(1, 0, 2).reshape(q, -1)
    is_ = gids.transpose(1, 0, 2).reshape(q, -1)
    neg, order = jax.lax.top_k(-ds, k)
    return jnp.take_along_axis(is_, order, axis=1), -neg


@dataclasses.dataclass
class ShardedEngine:
    """Scatter-gather serving over a device mesh (exhaustive ADC scan).

    Codes (and, when ``vectors`` is given, full vectors for the hybrid
    local-rerank scenario) are row-sharded across every mesh axis via
    dist.sharding.rpq_rows_spec. A query broadcasts its LUTs, every shard
    scans its rows and answers a local top-k, and the host merges the
    shard shortlists with dist.fault.partial_merge — shards reported dead
    via ``alive`` are simply dropped from the merge (graceful recall
    degradation, never a failed query).
    """
    codes: jax.Array                  # (N, M) compact codes
    lut_fn: Callable                  # (Q, D) queries -> (Q, M, K) LUTs
    vectors: Optional[jax.Array] = None   # (N, D): enables local exact rerank
    mesh: Optional[jax.sharding.Mesh] = None
    shortlist_mult: int = 4           # rerank shortlist = mult × k

    def __post_init__(self):
        if self.mesh is None:
            self.mesh = jax.make_mesh((len(jax.devices()),), ("data",))
        self._axes = shd.row_axes(self.mesh)
        self.n_shards = shd.axis_size(self.mesh, self._axes)
        self.n = int(self.codes.shape[0])
        rows = shd.named(self.mesh, shd.rpq_rows_spec(self.mesh))
        codes = jnp.asarray(self.codes)
        self._codes_bytes = codes.size * codes.dtype.itemsize
        self._codes_s = jax.device_put(
            kops.pad_rows_to_multiple(codes, self.n_shards), rows)
        self.codes = self._codes_s   # drop the unsharded copy
        self._vec_bytes = 0
        if self.vectors is not None:
            vec = jnp.asarray(self.vectors, jnp.float32)
            self._vec_bytes = vec.size * 4
            self._vec_s = jax.device_put(
                kops.pad_rows_to_multiple(vec, self.n_shards), rows)
            self.vectors = self._vec_s

    def _scatter(self, luts, queries, k: int):
        if not hasattr(self, "_jit_cache"):
            self._jit_cache = {}
        fn = self._jit_cache.get(k)
        if fn is None:
            if self.vectors is None:
                fn = jax.jit(lambda codes, luts: sharded_adc_scan(
                    self.mesh, self._axes, codes, luts, k=k, n_valid=self.n))
            else:
                fn = jax.jit(lambda codes, vec, luts, q: sharded_adc_serve(
                    self.mesh, self._axes, codes, vec, luts, q, k=k,
                    shortlist=self.shortlist_mult * k, n_valid=self.n))
            self._jit_cache[k] = fn
        if self.vectors is None:
            return fn(self._codes_s, luts)
        return fn(self._codes_s, self._vec_s, luts, queries)

    def search(self, queries: jax.Array, *, k: int = 10,
               alive: Optional[Sequence[bool]] = None,
               h: Optional[int] = None,
               expand: Optional[int] = None,
               entries: Optional[int] = None,
               prune_eps: Optional[float] = None,
               m_prefix: Optional[int] = None) -> SearchResult:
        """Exhaustive sharded scan (``h``/``expand``/``entries``/
        ``prune_eps``/``m_prefix`` accepted for engine-protocol
        compatibility and ignored — there is no beam to seed or prune)."""
        del h, expand, entries, prune_eps, m_prefix
        queries = jnp.atleast_2d(jnp.asarray(queries, jnp.float32))
        n_local = self._codes_s.shape[0] // self.n_shards
        kk = min(k, n_local)
        luts = jax.tree.map(jnp.asarray, self.lut_fn(queries))
        gids, dists = self._scatter(luts, queries, kk)
        gids, dists = np.asarray(gids), np.asarray(dists)
        if alive is None:
            alive = [True] * self.n_shards
        merged = partial_merge(list(gids), list(dists), alive, k)
        q = queries.shape[0]
        # n_dist counts REAL rows scanned: each alive shard scanned its
        # slice of the n corpus rows — the divisibility-padding rows it
        # also touched are +inf-masked sentinels, not distance work
        scanned = sum(
            max(0, min(self.n - i * n_local, n_local))
            for i, a in enumerate(alive) if a)
        return SearchResult(jnp.asarray(merged.ids), jnp.asarray(merged.dists),
                            hops=jnp.zeros((q,), jnp.int32),
                            n_dist=jnp.full((q,), scanned, jnp.int32),
                            rounds=jnp.zeros((q,), jnp.int32),
                            truncated=jnp.zeros((q,), bool),
                            degraded=merged.degraded)

    def memory_bytes(self) -> int:
        # UNPADDED sizes: what the index costs, not the divisibility slack
        return self._codes_bytes + self._vec_bytes


# ==========================================================================
# Graph-routed sharded serving (DESIGN.md §6): every shard runs the batched
# beam search over its OWN Vamana subgraph inside shard_map. Shared by
# ShardedGraphEngine, launch/serve.py --scenario sharded-graph, and the
# sharded_graph dry-run cell in launch/cells.py.
# ==========================================================================

def _shard_codes_pad(codes_l: jax.Array) -> jax.Array:
    """(1, n_local, M) shard block → (n_local + 1, M) sentinel-padded codes
    for beam.make_adc_dist_fn (sentinel row never read: beam masks ids)."""
    return kops.pad_sentinel_row(codes_l[0])


def _local_beam(neighbors_l, medoid_l, codes_l, luts, *, h: int,
                max_steps: int, backend: str, expand: int,
                seed_l=None, seed_cfg=None, prune_eps: float = 0.0,
                m_prefix: int = 0, max_rounds=None, max_n_dist=None):
    """Route over THIS shard's subgraph with ADC distances (u8 or fs4-
    packed layout, decided by the lut type). Returns the raw per-shard
    beam result (local ids).

    ``seed_l`` = (table, pivots, codes) shard blocks (leading shard axis 1)
    with ``seed_cfg`` = (k, m_hash, entries) statics: each shard seeds its
    local beam from its OWN coarse index — no cross-shard traffic, the
    seeding runs inside the scatter body. ``prune_eps``/``m_prefix``
    compile the partial-LUT hop-pruning pass into the local beam
    (DESIGN.md §11). Seeded searches fold the probe's scored candidates
    into ``n_dist``."""
    codes_p = _shard_codes_pad(codes_l)
    packed = _is_packed(luts)
    dist_fn = beam.make_adc_dist_fn(codes_p, packed=packed, backend=backend)
    mp, mt = _prune_cfg(luts, prune_eps, m_prefix)
    lb_fn = (beam.make_adc_dist_fn(codes_p, packed=packed, backend=backend,
                                   m_prefix=mp) if mp else None)
    cal_fn = (beam.make_lb_scale_fn(packed=packed, m_prefix=mp)
              if mp else None)
    seed_cost = 0
    if seed_l is not None:
        sk, smh, n_entries = seed_cfg
        tbl, piv, scodes = seed_l
        entry = sseed.seed_entries_from(tbl[0], piv[0], scodes[0], luts,
                                        k=sk, m_hash=smh, s=n_entries)
        seed_cost = int(tbl.shape[2] + piv.shape[1])
    else:
        entry = medoid_l[0]
    res = beam.beam_search(neighbors_l[0], entry, luts, dist_fn,
                           h=h, max_steps=max_steps, expand=expand,
                           lb_dist_fn=lb_fn, m_prefix=mp, m_total=mt,
                           prune_eps=prune_eps if mp else 0.0,
                           lb_scale_fn=cal_fn,
                           max_rounds=max_rounds, max_n_dist=max_n_dist)
    if seed_cost:
        res = res._replace(n_dist=res.n_dist + jnp.int32(seed_cost))
    return res


def _split_budget(rest: tuple, budget_cfg: tuple):
    """Peel the trailing traced budget scalars off a shard_map body's
    ``*rest`` (appended after the regular inputs by the wrappers below;
    ``budget_cfg`` = (has_max_rounds, has_max_n_dist) statics)."""
    nb = sum(bool(b) for b in budget_cfg)
    if not nb:
        return rest, None, None
    rest, tail = rest[:-nb], list(rest[-nb:])
    mr = tail.pop(0) if budget_cfg[0] else None
    mnd = tail.pop(0) if budget_cfg[1] else None
    return rest, mr, mnd


def _mask_to_global(ids, dists, *, mesh, axes, n_local: int, n_valid: int):
    """Local beam ids → global ids; sentinel slots and divisibility-padding
    rows become (-1, +inf) so the host merge never sees them."""
    shard = flat_shard_index(mesh, axes)
    n_valid_local = jnp.clip(n_valid - shard * n_local, 0, n_local)
    ok = (ids < n_valid_local) & jnp.isfinite(dists)
    gids = jnp.where(ok, ids + shard * n_local, -1)
    return gids, jnp.where(ok, dists, jnp.inf)


def _local_graph_topk(neighbors_l, medoid_l, codes_l, *rest, mesh, axes,
                      n_local: int, k: int, h: int, max_steps: int,
                      n_valid: int, backend: str, expand: int,
                      seed_cfg=None, prune_eps: float = 0.0,
                      m_prefix: int = 0, budget_cfg=(False, False)):
    """One shard's scatter half: beam-search my subgraph, return LOCAL
    top-k with GLOBAL ids. (1, Q, k) leading shard axis for the gather.
    ``rest`` is (luts,) classically, (table, pivots, seed_codes, luts)
    when per-shard seeding rides along (``seed_cfg`` set), with the traced
    deadline-budget scalars appended last per ``budget_cfg``."""
    rest, max_rounds, max_n_dist = _split_budget(rest, budget_cfg)
    seed_l = rest[:3] if seed_cfg is not None else None
    luts = rest[-1]
    res = _local_beam(neighbors_l, medoid_l, codes_l, luts, h=h,
                      max_steps=max_steps, backend=backend, expand=expand,
                      seed_l=seed_l, seed_cfg=seed_cfg,
                      prune_eps=prune_eps, m_prefix=m_prefix,
                      max_rounds=max_rounds, max_n_dist=max_n_dist)
    gids, d = _mask_to_global(res.ids[:, :k], res.dists[:, :k], mesh=mesh,
                              axes=axes, n_local=n_local, n_valid=n_valid)
    return gids[None], d[None], res.hops[None], res.n_dist[None], \
        res.rounds[None], res.truncated[None]


def _local_graph_serve(neighbors_l, medoid_l, codes_l, vectors_l, *rest,
                       mesh, axes, n_local: int, k: int, h: int,
                       shortlist: int, max_steps: int, n_valid: int,
                       backend: str, expand: int, seed_cfg=None,
                       prune_eps: float = 0.0, m_prefix: int = 0,
                       budget_cfg=(False, False)):
    """Scatter half with DiskANN-style local refinement: beam shortlist →
    exact rerank against my vector rows → LOCAL top-k, global ids.
    ``rest`` is (luts, queries), preceded by the three seed blocks when
    ``seed_cfg`` is set (as in :func:`_local_graph_topk`), with the traced
    deadline-budget scalars appended last per ``budget_cfg``."""
    rest, max_rounds, max_n_dist = _split_budget(rest, budget_cfg)
    seed_l = rest[:3] if seed_cfg is not None else None
    luts, queries = rest[-2], rest[-1]
    res = _local_beam(neighbors_l, medoid_l, codes_l, luts, h=h,
                      max_steps=max_steps, backend=backend, expand=expand,
                      seed_l=seed_l, seed_cfg=seed_cfg,
                      prune_eps=prune_eps, m_prefix=m_prefix,
                      max_rounds=max_rounds, max_n_dist=max_n_dist)
    cand = jnp.minimum(res.ids[:, :shortlist], n_local)   # clamp sentinel
    vec_p = kops.pad_sentinel_row(vectors_l[0])
    cv = vec_p[cand]                                      # (Q, shortlist, D)
    exact = jnp.sum((cv - queries[:, None, :]) ** 2, -1)
    exact = jnp.where(jnp.isfinite(res.dists[:, :shortlist]), exact, jnp.inf)
    neg, order = jax.lax.top_k(-exact, k)
    ids = jnp.take_along_axis(cand, order, axis=1)
    gids, d = _mask_to_global(ids, -neg, mesh=mesh, axes=axes,
                              n_local=n_local, n_valid=n_valid)
    return gids[None], d[None], res.hops[None], res.n_dist[None], \
        res.rounds[None], res.truncated[None]


def sharded_graph_topk(mesh, axes: tuple, neighbors, medoids, codes, luts, *,
                       k: int, h: int = 32, max_steps: int = 512,
                       n_valid: Optional[int] = None, backend: str = "auto",
                       expand: int = 1, seed_stack=None, seed_k: int = 0,
                       seed_m_hash: int = 0, entries: int = 1,
                       prune_eps: float = 0.0, m_prefix: int = 0,
                       max_rounds=None, max_n_dist=None):
    """Scatter: shard-stacked independent subgraphs × replicated LUTs →
    per-shard (S, Q, k) GLOBAL ids + ADC distances (+ (S, Q)
    hops/n_dist/rounds).

    Args:
      mesh/axes:  device mesh and the row-sharding axes (shd.row_axes).
      neighbors:  (S, n_local, R) stacked local adjacency (graphs/partition).
      medoids:    (S,) local entry vertices.
      codes:      (S, n_local, M) shard-stacked compact codes.
      luts:       (Q, M, K) query LUTs, replicated to every shard.
      k:          per-shard shortlist size (the gather is O(S·k)/query).
      h/max_steps: beam width and round cap of each LOCAL beam search.
      n_valid:    total REAL rows (masks the last shard's padding).
      backend:    per-hop distance backend (beam.make_adc_dist_fn).
      expand:     frontier batch size E of each local beam (DESIGN.md §9) —
                  every round scores one E·R-wide fused hop-ADC call
                  instead of E narrow ones.
      seed_stack: optional (table (S, B, C), pivots (S, P), codes
                  (S, n_local, M)) shard-stacked coarse-index arrays
                  (seed.build_seed_index per shard) with ``seed_k``/
                  ``seed_m_hash`` their shared statics: each shard seeds
                  ``entries`` local entry points inside its scatter body
                  (DESIGN.md §11).
      prune_eps/m_prefix: partial-LUT hop pruning of each local beam
                  (ε = 0 off — bit-identical).
      max_rounds/max_n_dist: traced per-call deadline budgets of each
                  local beam (DESIGN.md §13), replicated to every shard
                  (spec P()); None compiles out — bit-identical.

    Each shard routes ONLY over its own subgraph — no inter-shard edges, no
    mid-search collectives; the only cross-device traffic is the O(S·Q·k)
    shortlist gather (vs. O(Q·N/S) for the scan engine's full distances).
    The sixth output is the per-shard (S, Q) ``truncated`` flags.
    """
    s = shd.axis_size(mesh, axes)
    n_local = neighbors.shape[1]
    seeding = seed_stack is not None and entries > 1
    budget_cfg = (max_rounds is not None, max_n_dist is not None)
    body = partial(_local_graph_topk, mesh=mesh, axes=axes, n_local=n_local,
                   k=k, h=h, max_steps=max_steps,
                   n_valid=s * n_local if n_valid is None else n_valid,
                   backend=backend, expand=expand,
                   seed_cfg=(seed_k, seed_m_hash, entries) if seeding
                   else None, prune_eps=prune_eps, m_prefix=m_prefix,
                   budget_cfg=budget_cfg)
    ins = [neighbors, medoids, codes]
    specs = [P(axes, None, None), P(axes), P(axes, None, None)]
    if seeding:
        ins += list(seed_stack)
        specs += [P(axes, None, None), P(axes, None), P(axes, None, None)]
    ins.append(luts)
    specs.append(_lut_specs(luts))
    for b in (max_rounds, max_n_dist):
        if b is not None:
            ins.append(jnp.asarray(b, jnp.int32))
            specs.append(P())
    # check_vma=False: the beam's while_loop starts from constants (empty
    # visited set, unexpanded beam) that the body then mixes with this
    # shard's blocks; the varying-axes check rejects that carry, and every
    # output here is per-shard anyway (out_specs split over the mesh axes).
    return jax.shard_map(
        body, mesh=mesh, in_specs=tuple(specs),
        out_specs=(P(axes, None, None), P(axes, None, None),
                   P(axes, None), P(axes, None), P(axes, None),
                   P(axes, None)), check_vma=False)(*ins)


def sharded_graph_serve(mesh, axes: tuple, neighbors, medoids, codes,
                        vectors, luts, queries, *, k: int, h: int = 32,
                        shortlist: int = 0, max_steps: int = 512,
                        n_valid: Optional[int] = None,
                        backend: str = "auto", expand: int = 1,
                        seed_stack=None, seed_k: int = 0,
                        seed_m_hash: int = 0, entries: int = 1,
                        prune_eps: float = 0.0, m_prefix: int = 0,
                        max_rounds=None, max_n_dist=None):
    """Scatter with local exact rerank: like :func:`sharded_graph_topk` but
    every shard re-ranks its beam shortlist against its resident vector
    rows (S, n_local, D) before answering — the DiskANN shortlist pattern
    with the SSD replaced by the shard's own HBM. Adaptive-routing kwargs
    (``seed_stack``/``entries``/``prune_eps``/``m_prefix``) and the traced
    deadline budgets (``max_rounds``/``max_n_dist``) as in
    :func:`sharded_graph_topk`."""
    s = shd.axis_size(mesh, axes)
    n_local = neighbors.shape[1]
    seeding = seed_stack is not None and entries > 1
    budget_cfg = (max_rounds is not None, max_n_dist is not None)
    body = partial(_local_graph_serve, mesh=mesh, axes=axes,
                   n_local=n_local, k=k, h=h,
                   shortlist=min(shortlist or h, h), max_steps=max_steps,
                   n_valid=s * n_local if n_valid is None else n_valid,
                   backend=backend, expand=expand,
                   seed_cfg=(seed_k, seed_m_hash, entries) if seeding
                   else None, prune_eps=prune_eps, m_prefix=m_prefix,
                   budget_cfg=budget_cfg)
    ins = [neighbors, medoids, codes, vectors]
    specs = [P(axes, None, None), P(axes), P(axes, None, None),
             P(axes, None, None)]
    if seeding:
        ins += list(seed_stack)
        specs += [P(axes, None, None), P(axes, None), P(axes, None, None)]
    ins += [luts, queries]
    specs += [_lut_specs(luts), P(None, None)]
    for b in (max_rounds, max_n_dist):
        if b is not None:
            ins.append(jnp.asarray(b, jnp.int32))
            specs.append(P())
    # check_vma=False: the beam's while_loop starts from constants (empty
    # visited set, unexpanded beam) that the body then mixes with this
    # shard's blocks; the varying-axes check rejects that carry, and every
    # output here is per-shard anyway (out_specs split over the mesh axes).
    return jax.shard_map(
        body, mesh=mesh, in_specs=tuple(specs),
        out_specs=(P(axes, None, None), P(axes, None, None),
                   P(axes, None), P(axes, None), P(axes, None),
                   P(axes, None)), check_vma=False)(*ins)


def _stack_rows(x: jax.Array, n_shards: int, n_local: int) -> jax.Array:
    """(N, ...) global rows → (S, n_local, ...) shard-stacked, zero-padded."""
    pad = n_shards * n_local - x.shape[0]
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    return x.reshape((n_shards, n_local) + x.shape[1:])


@dataclasses.dataclass
class ShardedGraphEngine:
    """Graph-ROUTED scatter-gather serving over a device mesh.

    Where :class:`ShardedEngine` exhaustively scans every shard's rows, this
    engine routes: the dataset is partitioned into contiguous per-shard row
    ranges with an independent Vamana subgraph per shard
    (graphs/partition.py), and every query's beam search runs *inside*
    ``shard_map`` — each shard walks its own subgraph with ADC distances
    (per-hop hot loop = the fused hop-ADC Pallas kernel on TPU), optionally
    exact-reranks its beam against its resident vector rows (DiskANN-style),
    and answers a LOCAL top-k with GLOBAL ids. The host merges shard
    shortlists with ``dist.fault.partial_merge``: a dead shard's row range
    drops out of the answer (graceful recall degradation), the query never
    fails.

    Per-query distance work is O(hops·R) per shard instead of O(N/S), so
    this is the scenario that scales ROUTING — not just scanning — with the
    mesh. Recall is within a few points of a single-device in-memory beam
    at equal width, because every shard is searched and the merge keeps the
    global best (the partition can only *split* a query's true neighborhood
    across shards, each of which still finds its part).

    Attributes:
      graph:    PartitionedGraph over the same row order as ``codes``.
      codes:    (N, M) compact codes (global row order).
      lut_fn:   (Q, D) queries → (Q, M, K) LUTs.
      vectors:  optional (N, D) full vectors; enables local exact rerank.
      mesh:     device mesh (default: all local devices on one axis).
      backend:  per-hop kernel dispatch, see beam.make_adc_dist_fn.
    """
    graph: PartitionedGraph
    codes: jax.Array
    lut_fn: Callable
    vectors: Optional[jax.Array] = None
    mesh: Optional[jax.sharding.Mesh] = None
    backend: str = "auto"

    def __post_init__(self):
        if self.mesh is None:
            self.mesh = jax.make_mesh((len(jax.devices()),), ("data",))
        self._axes = shd.row_axes(self.mesh)
        self.n_shards = shd.axis_size(self.mesh, self._axes)
        if self.n_shards != self.graph.n_shards:
            raise ValueError(
                f"graph has {self.graph.n_shards} shards but the mesh has "
                f"{self.n_shards} — partition with n_shards="
                f"{self.n_shards}")
        self.n = int(self.graph.n)
        if int(self.codes.shape[0]) != self.n:
            raise ValueError(f"codes rows {self.codes.shape[0]} != "
                             f"graph rows {self.n}")
        n_local = self.graph.n_local
        rows3 = shd.named(self.mesh, shd.rpq_shard_stack_spec(self.mesh))
        rows1 = shd.named(self.mesh, shd.rpq_shard_stack_spec(self.mesh, 1))
        codes = jnp.asarray(self.codes)
        self._codes_bytes = codes.size * codes.dtype.itemsize
        self._codes_s = jax.device_put(
            _stack_rows(codes, self.n_shards, n_local), rows3)
        self.codes = self._codes_s
        self._nbrs_s = jax.device_put(self.graph.neighbors, rows3)
        self._medoids_s = jax.device_put(self.graph.medoids, rows1)
        self._vec_bytes = 0
        if self.vectors is not None:
            vec = jnp.asarray(self.vectors, jnp.float32)
            self._vec_bytes = vec.size * 4
            self._vec_s = jax.device_put(
                _stack_rows(vec, self.n_shards, n_local), rows3)
            self.vectors = self._vec_s
        self._jit_cache = {}
        self._seedstk = None

    def _seed_stack(self, luts):
        """Per-shard coarse seeding indexes, built lazily on the first
        ``entries>1`` search: one seed.build_seed_index over each shard's
        LOCAL rows (padding rows of the last shard excluded — a beam must
        never START on padding), stacked to (S, ...) arrays and device_put
        with the shard-stack layout. ``k``/``m_hash`` are shared across
        shards so one static shard_map body serves all of them."""
        if self._seedstk is None:
            codes = np.asarray(jax.device_get(self._codes_s))  # (S, nl, .)
            if _is_packed(luts):
                m = _lut_m(luts)
                codes = np.stack([np.asarray(unpack_codes(jnp.asarray(c), m))
                                  for c in codes])
            s, nl = codes.shape[:2]
            k = int(codes.max()) + 1
            m_hash = sseed.auto_m_hash(codes.shape[2], k)
            tbls, pivs = [], []
            for i in range(s):
                real = max(1, min(self.n - i * nl, nl))
                ix = sseed.build_seed_index(codes[i, :real], k=k,
                                            m_hash=m_hash)
                tbls.append(np.asarray(ix.table))
                pivs.append(np.asarray(ix.pivots))
            pw = max(p.shape[0] for p in pivs)
            pivs = [np.pad(p, (0, pw - p.shape[0]), constant_values=-1)
                    for p in pivs]
            rows3 = shd.named(self.mesh, shd.rpq_shard_stack_spec(self.mesh))
            rows2 = shd.named(self.mesh,
                              shd.rpq_shard_stack_spec(self.mesh, 2))
            self._seedstk = (
                jax.device_put(jnp.asarray(np.stack(tbls)), rows3),
                jax.device_put(jnp.asarray(np.stack(pivs)), rows2),
                jax.device_put(jnp.asarray(codes, jnp.int32), rows3),
                k, m_hash)
        return self._seedstk

    def _scatter(self, luts, queries, k: int, h: int, max_steps: int,
                 expand: int, entries: int, prune_eps: float,
                 m_prefix: int, max_rounds=None, max_n_dist=None):
        # budgets are TRACED — the cache keys on their PRESENCE (a distinct
        # compiled body with/without the check), never on their values, so
        # sweeping a deadline hits one cache entry
        key = (k, h, max_steps, expand, entries, prune_eps, m_prefix,
               max_rounds is not None, max_n_dist is not None)
        seed_stack = seed_k = seed_m_hash = None
        if entries > 1:
            *seed_stack, seed_k, seed_m_hash = self._seed_stack(luts)
            seed_stack = tuple(seed_stack)
        fn = self._jit_cache.get(key)
        if fn is None:
            adaptive = dict(entries=entries, prune_eps=prune_eps,
                            m_prefix=m_prefix, seed_k=seed_k or 0,
                            seed_m_hash=seed_m_hash or 0)
            if self.vectors is None:
                fn = jax.jit(
                    lambda nb, md, cd, lu, seed, mr, mnd: sharded_graph_topk(
                        self.mesh, self._axes, nb, md, cd, lu, k=k, h=h,
                        max_steps=max_steps, n_valid=self.n,
                        backend=self.backend, expand=expand,
                        seed_stack=seed, max_rounds=mr, max_n_dist=mnd,
                        **adaptive))
            else:
                fn = jax.jit(
                    lambda nb, md, cd, vc, lu, q, seed, mr, mnd:
                    sharded_graph_serve(
                        self.mesh, self._axes, nb, md, cd, vc, lu, q, k=k,
                        h=h, shortlist=h, max_steps=max_steps,
                        n_valid=self.n, backend=self.backend,
                        expand=expand, seed_stack=seed, max_rounds=mr,
                        max_n_dist=mnd, **adaptive))
            self._jit_cache[key] = fn
        if self.vectors is None:
            return fn(self._nbrs_s, self._medoids_s, self._codes_s, luts,
                      seed_stack, max_rounds, max_n_dist)
        return fn(self._nbrs_s, self._medoids_s, self._codes_s, self._vec_s,
                  luts, queries, seed_stack, max_rounds, max_n_dist)

    def search(self, queries: jax.Array, *, k: int = 10, h: int = 32,
               max_steps: int = 512, expand: int = 1,
               alive: Optional[Sequence[bool]] = None, entries: int = 1,
               prune_eps: float = 0.0, m_prefix: int = 0,
               max_rounds=None, max_n_dist=None,
               deadline_s: Optional[float] = None,
               quorum: Optional[int] = None,
               shard_latency_s: Optional[Sequence[float]] = None
               ) -> SearchResult:
        """Route every query on every (alive) shard, merge the shortlists.

        ``hops``/``n_dist`` report the SUM over alive shards — the total
        work the mesh did for the query, comparable to a single-device
        beam's counters. ``rounds`` reports the MAX over alive shards: the
        shards route concurrently, so the slowest shard's sequential trip
        count is the query's latency proxy. ``entries``/``prune_eps``/
        ``m_prefix`` are the adaptive-routing knobs (DESIGN.md §11),
        applied PER SHARD: every shard seeds its local beam from its own
        coarse index and prunes its own hops.

        ``max_rounds``/``max_n_dist`` are per-call compute budgets applied
        to EVERY shard's local beam (traced — sweeping them never
        retraces). ``deadline_s``+``shard_latency_s`` model the quorum
        merge (DESIGN.md §13): shards whose modeled latency exceeds the
        straggler deadline are charged as dead for this call — provided at
        least ``quorum`` (default: majority of alive) fast shards remain;
        otherwise the fastest ``quorum`` alive shards are kept even past
        the deadline (quorum outranks deadline). ``truncated`` is
        any-over-merged-shards; ``degraded`` is True whenever the answer
        merged fewer shards than were declared alive, or none at all.
        """
        queries = jnp.atleast_2d(jnp.asarray(queries, jnp.float32))
        kk = min(k, h, self.graph.n_local)
        luts = jax.tree.map(jnp.asarray, self.lut_fn(queries))
        gids, dists, hops, ndist, rounds, trunc = self._scatter(
            luts, queries, kk, h, max_steps, expand, entries, prune_eps,
            m_prefix, max_rounds=max_rounds, max_n_dist=max_n_dist)
        gids, dists = np.asarray(gids), np.asarray(dists)
        if alive is None:
            alive = [True] * self.n_shards
        alive = list(alive)
        quorum_degraded = False
        if deadline_s is not None or quorum is not None:
            lat = (list(shard_latency_s) if shard_latency_s is not None
                   else [0.0] * self.n_shards)
            decision = resolve_quorum(alive, lat, deadline_s, quorum)
            alive = list(decision.alive)
            quorum_degraded = decision.degraded
        merged = partial_merge(list(gids), list(dists), alive, k)
        mask = np.asarray(alive, bool)
        q = queries.shape[0]
        if mask.any():
            hops = np.asarray(hops)[mask].sum(0)
            ndist = np.asarray(ndist)[mask].sum(0)
            rounds = np.asarray(rounds)[mask].max(0)
            trunc = np.asarray(trunc)[mask].any(0)
        else:  # every shard dead: sentinel answer, zero-work counters
            hops = np.zeros((q,), np.int32)
            ndist = np.zeros((q,), np.int32)
            rounds = np.zeros((q,), np.int32)
            trunc = np.zeros((q,), bool)
        return SearchResult(jnp.asarray(merged.ids), jnp.asarray(merged.dists),
                            hops=jnp.asarray(hops, jnp.int32),
                            n_dist=jnp.asarray(ndist, jnp.int32),
                            rounds=jnp.asarray(rounds, jnp.int32),
                            truncated=jnp.asarray(trunc),
                            degraded=bool(merged.degraded or quorum_degraded))

    def memory_bytes(self) -> int:
        # UNPADDED codes + per-shard adjacency (+ vectors when resident)
        return (self._codes_bytes
                + self.graph.neighbors.size * 4 + self._vec_bytes)
