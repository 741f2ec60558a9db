"""Plain reference of hybrid graph-PQ search, and the exact kNN.

Written for the benchmark and independent of the program: it imports
nothing of ``repro``. It takes from the program's build the graph's
adjacency and entry vertex, the rotation and the PQ codebooks, and holds
the served code table to them (``code_rows_wrong``) before it routes by
it. Then it computes what the served search must return, in
straightforward ``jax.numpy`` at float32 (``dtype=jnp.bfloat16`` gives
the control):

0. codes: each sub-vector of the rotated base (``x @ r.T`` at
   ``Precision.HIGHEST``) has its nearest codeword, the squared
   differences summed directly; a served code whose codeword lies further
   than the nearest by more than ``ENCODE_TIE`` of the sub-vector's and
   the codeword's squared norms marks its row wrong. fs4 codes are
   unpacked by the nibble rule below;
1. LUT, per call and op by op as a served call builds it:
   ``lut[j, c] = |q_j|^2 - 2 q_j . codebook[j, c] + |codebook[j, c]|^2``
   per subspace of the rotated query, the product at
   ``Precision.HIGHEST`` (float32-exact); for the fs4 layout the same
   table quantized to uint8 with one affine per query
   (scale = (max - min) / 255, bias = min), accumulated in int32.
   The table is float32 arithmetic that the served path states; computed
   in another order it would differ in the last bit, and a last-bit
   difference between two near-tied PQ distances forks the beam;
2. beam: best-first search from the entry vertex with a beam of ``h``
   candidates; each round expands the nearest unexpanded candidate, scores
   its unvisited neighbours by PQ distance (table entries gathered, and
   subspaces added in order) and keeps the ``h`` nearest, ties to the
   earlier position; at most ``max_steps`` rounds; run on the host's CPU
   (``answer`` says why);
3. rerank: the exact squared L2 distance to each of the ``h`` candidates,
   and the ``k`` nearest of them.

A sharded deployment (``answer_sharded``) is the same search run on each
shard alone: shard s of S owns the base rows
``[s * ceil(n/S), min((s + 1) * ceil(n/S), n))``, and its own graph (the
program's, in local ids, taken as given) is searched from its own entry
vertex over that shard's code and base rows; the local ids are mapped to
global ids and the S shards' top-k lists are merged by exact float32
distance, ties to the smaller global id. ``shards_misplaced`` holds the
program's resident arrays to that layout on the devices.

``exact_knn`` is the ground truth for recall: blocked brute force at
``Precision.HIGHEST`` (a TPU's default float32 matmul runs bfloat16
passes, which reorder neighbours closer than 0.4% of the distance).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

# A served code may lie this far above the nearest codeword, as a share of
# |x_j|^2 + |c|^2: the rounding of a float32 distance table computed in
# the expanded form (a few float32 ulps of those norms).
ENCODE_TIE = 1e-5
# An id that only one side returns still agrees when its distance lies
# this close, relatively, to the k-th: a tie in the last bits.
TIE_RTOL = 1e-5


@functools.partial(jax.jit, static_argnames=("k", "block"))
def exact_knn(base, queries, k: int, block: int = 256):
    """(Q, D) queries × (N, D) base → ((Q, k) int32 ids, (Q, k) sq. dists)."""
    n, d = base.shape
    nq = queries.shape[0]
    base = base.astype(jnp.float32)
    b2 = jnp.sum(base * base, axis=1)
    qp = jnp.pad(queries.astype(jnp.float32), ((0, (-nq) % block), (0, 0)))

    def one(qb):
        d2 = (jnp.sum(qb * qb, axis=1)[:, None] + b2[None, :]
              - 2.0 * jnp.matmul(qb, base.T, precision=HIGHEST))
        neg, ids = jax.lax.top_k(-d2, k)
        return ids.astype(jnp.int32), -neg

    ids, d2 = jax.lax.map(one, qp.reshape(-1, block, d))
    return ids.reshape(-1, k)[:nq], d2.reshape(-1, k)[:nq]


def unpack_nibbles(packed, m: int):
    """(N, ceil(M/2)) bytes → (N, M) sub-codes: byte b holds sub-code 2b in
    its low nibble and 2b + 1 in its high nibble."""
    p = packed.astype(jnp.int32)
    both = jnp.stack([p & 0xF, (p >> 4) & 0xF], axis=-1)
    return both.reshape(p.shape[0], -1)[:, :m]


def rotate(x, r, dtype=jnp.float32):
    """(B, D) rows in the quantizer's rotated space: ``x @ r.T``."""
    prec = HIGHEST if dtype == jnp.float32 else None
    return jnp.matmul(jnp.asarray(x).astype(dtype),
                      jnp.asarray(r).astype(dtype).T, precision=prec)


def served_subcodes(codes, m: int, layout: str):
    """(N, M) int32 sub-codes of a served code table: u8 as stored, fs4
    unpacked by the nibble rule."""
    codes = jnp.asarray(codes)
    if layout == "fs4":
        return unpack_nibbles(codes, m)
    return codes.astype(jnp.int32)


def _sub_dists(x, r, codebooks, dtype=jnp.float32):
    """Rotated (B, M, dsub) sub-vectors, the codebooks, and the (B, M, K)
    squared distances between them, the differences squared and summed."""
    c = codebooks.astype(dtype)
    m, _, dsub = c.shape
    xs = rotate(x, r, dtype).reshape(-1, m, dsub)
    return xs, c, jnp.sum((xs[:, :, None, :] - c[None]) ** 2, axis=-1)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _nearest(x, r, codebooks, dtype):
    _, _, d = _sub_dists(x, r, codebooks, dtype)
    return jnp.argmin(d, axis=-1).astype(jnp.int32)


def pack_nibbles(sub):
    """(N, M) sub-codes in [0, 16) → (N, ceil(M/2)) bytes, the inverse of
    ``unpack_nibbles``."""
    sub = jnp.asarray(sub, jnp.int32)
    if sub.shape[1] % 2:
        sub = jnp.pad(sub, ((0, 0), (0, 1)))
    return (sub[:, 0::2] | (sub[:, 1::2] << 4)).astype(jnp.uint8)


def encode(base, r, codebooks, layout: str, dtype=jnp.float32,
           block: int = 2048):
    """The code table of the base, as a served table is laid out: the
    nearest codeword of each rotated sub-vector (uint8 (N, M) for u8,
    packed nibbles for fs4). ``dtype=jnp.bfloat16`` gives the control's."""
    sub = jnp.concatenate([_nearest(base[s:s + block], r, codebooks, dtype)
                           for s in range(0, base.shape[0], block)])
    return pack_nibbles(sub) if layout == "fs4" else sub.astype(jnp.uint8)


@jax.jit
def _wrong_rows(x, r, codebooks, sub):
    xs, c, d = _sub_dists(x, r, codebooks)
    m, k, _ = c.shape
    ok = (sub >= 0) & (sub < k)
    s = jnp.where(ok, sub, 0)
    got = jnp.take_along_axis(d, s[:, :, None], axis=2)[..., 0]
    c2 = jnp.sum(c * c, axis=-1)[jnp.arange(m)[None, :], s]
    tol = ENCODE_TIE * (jnp.sum(xs * xs, axis=-1) + c2)
    bad = ~ok | (got - jnp.min(d, axis=-1) > tol)
    return jnp.sum(jnp.any(bad, axis=1).astype(jnp.int32))


def code_rows_wrong(base, r, codebooks, codes, layout: str,
                    block: int = 2048) -> float:
    """The share of the served code table's rows that do not hold, in
    every subspace, a nearest codeword of the rotated base row (up to
    ``ENCODE_TIE``); a table of another row count is wrong throughout."""
    n = base.shape[0]
    m = jnp.asarray(codebooks).shape[0]
    sub = served_subcodes(codes, m, layout)
    if sub.shape != (n, m):
        return 1.0
    wrong = 0
    for s in range(0, n, block):
        wrong += int(_wrong_rows(base[s:s + block], r, codebooks,
                                 sub[s:s + block]))
    return wrong / n


def luts(queries, r, codebooks, layout: str, dtype=jnp.float32):
    """LUTs of one call's (B, D) queries, op by op as a served call builds
    them: (B, M, K) float for u8; (uint8 (B, M, 16) table, scale, bias)
    for fs4. ``dtype=jnp.bfloat16`` computes the table in bfloat16 at the
    default precision (the control)."""
    c = jnp.asarray(codebooks).astype(dtype)
    m, k, dsub = c.shape
    x = rotate(queries, r, dtype).reshape(-1, m, dsub)
    prec = HIGHEST if dtype == jnp.float32 else None
    lut = (jnp.sum(x * x, axis=-1)[:, :, None]
           - 2.0 * jnp.einsum("nmd,mkd->nmk", x, c, precision=prec)
           + jnp.sum(c * c, axis=-1)[None, :, :])
    if layout == "u8":
        return lut
    lut = lut.astype(jnp.float32)
    flat = lut.reshape(lut.shape[0], -1)
    lo, hi = jnp.min(flat, axis=1), jnp.max(flat, axis=1)
    scale = jnp.where(hi > lo, (hi - lo) / 255.0, 1.0)
    lut_q = jnp.clip(jnp.round((lut - lo[:, None, None])
                               / scale[:, None, None]), 0, 255)
    return lut_q.astype(jnp.uint8), scale, lo


def _select(table, codes):
    """table[j, codes[i, j]] for every row i and subspace j, gathered:
    (M, K) table, (B, M) codes → (B, M)."""
    return table[jnp.arange(table.shape[0])[None, :], codes]


def _pq_dist_fn(lut, codes, layout: str):
    """One query's PQ distance to a vector of ids: subspaces added in
    order (u8), or an exact int32 sum and one affine (fs4)."""
    if layout == "u8":
        m = lut.shape[0]

        def dist(ids):
            vals = _select(lut, codes[ids].astype(jnp.int32))
            acc = vals[:, 0]
            for j in range(1, m):
                acc = acc + vals[:, j]
            return acc.astype(jnp.float32)
        return dist
    lut_q, scale, bias = lut
    m = lut_q.shape[0]

    def dist(ids):
        acc = jnp.sum(_select(lut_q.astype(jnp.int32),
                              served_subcodes(codes[ids], m, "fs4")), axis=1)
        return scale * acc.astype(jnp.float32) + m * bias
    return dist


def _search_one(q, lut, neighbors, entry, codes, base_p, *, layout, dtype,
                h, k, max_steps):
    n, r = neighbors.shape
    inf = jnp.float32(jnp.inf)
    dist = _pq_dist_fn(lut, codes, layout)
    ids = jnp.full((h,), n, jnp.int32).at[0].set(entry)
    d = jnp.full((h,), inf).at[0].set(dist(entry[None])[0])
    done = jnp.ones((h,), bool).at[0].set(False)
    # visited set: one bit per vertex, in uint32 words
    seen = jnp.zeros(((n + 32) // 32,), jnp.uint32)
    seen = seen.at[entry >> 5].add(jnp.uint32(1) << (entry & 31).astype(
        jnp.uint32))
    row = jnp.arange(r)

    def cond(s):
        step, ids, d, done, seen = s
        return (step < max_steps) & jnp.any(~done & (d < inf))

    def body(s):
        step, ids, d, done, seen = s
        sel = jnp.argmin(jnp.where(done, inf, d))
        done = done.at[sel].set(True)
        nbr = neighbors[ids[sel]]
        bit = jnp.uint32(1) << (nbr & 31).astype(jnp.uint32)
        fresh = (nbr < n) & ((seen[nbr >> 5] & bit) == 0)
        # each fresh id's bit once (a row holding an id twice scores it
        # twice, as the served beam does); distinct bits add as an OR
        first = ~jnp.any((nbr[:, None] == nbr[None, :])
                         & (row[:, None] > row[None, :]) & fresh[None, :],
                         axis=1)
        seen = seen.at[jnp.where(fresh, nbr >> 5, 0)].add(
            jnp.where(fresh & first, bit, jnp.uint32(0)))
        nd = jnp.where(fresh, dist(jnp.where(fresh, nbr, 0)), inf)
        all_ids = jnp.concatenate([ids, jnp.where(fresh, nbr, n)])
        all_d = jnp.concatenate([d, nd])
        all_done = jnp.concatenate([done, jnp.zeros((r,), bool)])
        keep = jnp.argsort(all_d, stable=True)[:h]
        d = all_d[keep]
        return (step + 1, all_ids[keep], d, all_done[keep] | (d == inf),
                seen)

    _, ids, _, _, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), ids, d, done, seen))
    exact = exact_dists(q, ids, base_p, dtype)
    keep = jnp.argsort(exact, stable=True)[:k]
    return ids[keep], exact[keep]


def exact_dists(q, ids, base_p, dtype=jnp.float32):
    """Squared L2 from one query to rows ``ids`` of the sentinel-padded
    base; the sentinel row (index N) is +inf."""
    v = base_p[ids].astype(dtype)
    dd = jnp.sum((v - q.astype(dtype)[None, :]) ** 2, axis=-1)
    return jnp.where(ids == base_p.shape[0] - 1, jnp.inf,
                     dd.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("layout", "dtype", "h", "k",
                                             "max_steps"))
def search(queries, lut, neighbors, entry, codes, base, *, layout: str,
           h: int, k: int, max_steps: int, dtype=jnp.float32):
    """The served hybrid search, plainly: (Q, D) queries and their LUTs
    (``luts``) → ((Q, k) ids, (Q, k) exact squared distances), ascending;
    the exact distances in ``dtype``."""
    base_p = jnp.concatenate(
        [base.astype(jnp.float32), jnp.zeros((1, base.shape[1]), jnp.float32)])
    fn = functools.partial(_search_one, neighbors=neighbors,
                           entry=jnp.asarray(entry, jnp.int32), codes=codes,
                           base_p=base_p, layout=layout, dtype=dtype, h=h,
                           k=k, max_steps=max_steps)
    return jax.vmap(fn)(queries, lut)


@jax.jit
def returned_dists(queries, ids, base):
    """Exact float32 squared distance of each returned id; +inf for the
    sentinel (id N) and for ids outside [0, N]."""
    n = base.shape[0]
    base_p = jnp.concatenate(
        [base.astype(jnp.float32), jnp.zeros((1, base.shape[1]), jnp.float32)])
    safe = jnp.where((ids >= 0) & (ids <= n), ids, n)
    return jax.vmap(lambda q, i: exact_dists(q, i, base_p))(queries, safe)


def answer(calls, index: dict, *, layout: str, h: int, k: int,
           max_steps: int, dtype=jnp.float32, block: int = 1024):
    """The reference's answers to a list of calls' (B, D) query arrays:
    each call's LUTs built as the call builds them, on the device the call
    ran on; then ``search`` over all calls' queries in blocks of
    ``block``, on the host's CPU; returned on the host.

    The beam runs on the CPU because there it computes what it states. On
    a TPU v5e the same ``search``, compiled as one 1024-lane program,
    departed from itself run on the CPU on 22% of the queries (and agreed
    with a 3-lane run of it on the chip), with tables, PQ distances and
    tie order all equal; the CPU run agreed with the served answers on
    every query."""
    tables = [luts(q, index["r"], index["codebooks"], layout, dtype)
              for q in calls]
    cpu = jax.devices("cpu")[0]
    lut = jax.device_put(jax.tree.map(lambda *t: jnp.concatenate(t),
                                      *tables), cpu)
    graph = {key: jax.device_put(index[key], cpu)
             for key in ("neighbors", "entry", "codes", "base")}
    queries = np.concatenate(calls)
    ids, dists = [], []
    with jax.default_device(cpu):
        for s in range(0, queries.shape[0], block):
            part = jax.tree.map(lambda t: t[s:s + block], lut)
            i, d = search(jnp.asarray(queries[s:s + block]), part,
                          graph["neighbors"], graph["entry"], graph["codes"],
                          graph["base"], layout=layout, h=h, k=k,
                          max_steps=max_steps, dtype=dtype)
            ids.append(np.asarray(i))
            dists.append(np.asarray(d))
    return np.concatenate(ids), np.concatenate(dists)


def shard_bounds(n: int, shards: int) -> list:
    """Global [lo, hi) rows of each shard: ceil(n / shards) rows a shard,
    the last ones shorter."""
    n_local = -(-n // shards)
    return [(s * n_local, min((s + 1) * n_local, n)) for s in range(shards)]


def global_rows(stacked, n: int):
    """A shard-stacked (S, ceil(n/S), ...) table in global row order, the
    padding rows dropped; None for a table of another shape."""
    stacked = np.asarray(stacked)
    if stacked.ndim < 2 or stacked.shape[1] != -(-n // stacked.shape[0]):
        return None
    return stacked.reshape((-1,) + stacked.shape[2:])[:n]


def stack_rows(table, shards: int):
    """(n, ...) rows in global order → (S, ceil(n/S), ...) shard-stacked,
    zero rows padding the last shard: the inverse of ``global_rows``."""
    table = np.asarray(table)
    n_local = -(-table.shape[0] // shards)
    pad = shards * n_local - table.shape[0]
    table = np.concatenate([table, np.zeros((pad,) + table.shape[1:],
                                            table.dtype)])
    return table.reshape((shards, n_local) + table.shape[1:])


def answer_sharded(calls, index: dict, *, layout: str, h: int, k: int,
                   max_steps: int, dtype=jnp.float32, block: int = 1024):
    """The reference's answers of a sharded deployment: ``answer`` on each
    shard alone (its graph ``index["neighbors"][s]`` in local ids, entry
    ``index["medoids"][s]``, its rows of the shard-stacked code table and
    of the base), local ids mapped to global ids, then the S top-k lists
    merged by exact distance, ties to the smaller id. A sentinel or a
    padding row answers (-1, +inf)."""
    neighbors = np.asarray(index["neighbors"])
    codes, medoids = np.asarray(index["codes"]), np.asarray(index["medoids"])
    base = np.asarray(index["base"])
    shards, n_local = neighbors.shape[:2]
    ids, dists = [], []
    for s, (lo, hi) in enumerate(shard_bounds(base.shape[0], shards)):
        rows = np.zeros((n_local, base.shape[1]), base.dtype)
        rows[: hi - lo] = base[lo:hi]
        shard = {"neighbors": neighbors[s], "entry": medoids[s],
                 "codes": codes[s], "base": rows, "r": index["r"],
                 "codebooks": index["codebooks"]}
        i, d = answer(calls, shard, layout=layout, h=h, k=k,
                      max_steps=max_steps, dtype=dtype, block=block)
        real = i < hi - lo
        ids.append(np.where(real, i + lo, -1))
        dists.append(np.where(real, d, np.inf).astype(np.float32))
    ids, dists = np.concatenate(ids, axis=1), np.concatenate(dists, axis=1)
    order = np.lexsort((ids, dists), axis=1)[:, :k]
    return (np.take_along_axis(ids, order, axis=1),
            np.take_along_axis(dists, order, axis=1))


def shards_misplaced(arrays: dict, devices) -> int:
    """How many (array, device) pairs break the sharded layout: each of
    the S ``devices`` holds exactly one (1, n_local, ...) block of each
    (S, n_local, ...) array, block s on device s. An index kept whole on
    one device, or copied to every device, breaks it."""
    bad = 0
    for a in arrays.values():
        held = {}
        for piece in a.addressable_shards:
            held.setdefault(piece.device, []).append(piece)
        for s, dev in enumerate(devices):
            mine = held.get(dev, [])
            ok = (len(mine) == 1 and a.shape[0] == len(devices)
                  and mine[0].data.shape == (1,) + a.shape[1:]
                  and range(a.shape[0])[mine[0].index[0]] == range(s, s + 1))
            bad += not ok
    return bad


def compare(ids, dists, ref_ids, ref_dists, true_dists):
    """Numbers for answers of the same queries; a configuration's
    ``limits`` say which of them decide ``correct``.

    * ``id_mismatch_share``: the share of queries whose returned id set
      differs from the reference's, an id that only one side returns
      counting as a mismatch unless its distance ties the k-th (within
      ``TIE_RTOL``);
    * ``dist_rel_gap_max``: the widest relative gap between a returned
      distance and the exact float32 distance of the id returned with it
      (``true_dists``), over all queries and ranks; an id without a finite
      exact distance counts as a gap of 1.
    """
    ids, ref_ids = np.asarray(ids), np.asarray(ref_ids)
    dists, ref_dists = np.asarray(dists), np.asarray(ref_dists)
    true_dists = np.asarray(true_dists)
    kth = np.maximum(ref_dists[:, -1:], dists[:, -1:])
    tol = TIE_RTOL * np.where(np.isfinite(kth), np.abs(kth), 0.0)

    def lone_untied(mine, d_mine, other):
        lone = ~(mine[:, :, None] == other[:, None, :]).any(-1)
        tied = np.isfinite(d_mine) & (np.abs(d_mine - kth) <= tol)
        return (lone & ~tied).any(1)

    mismatch = (lone_untied(ids, dists, ref_ids)
                | lone_untied(ref_ids, ref_dists, ids))
    finite = np.isfinite(true_dists)
    with np.errstate(invalid="ignore", divide="ignore"):
        gap = np.abs(dists - true_dists) / np.maximum(np.abs(true_dists),
                                                      np.float32(1e-30))
    gap = np.where(finite, gap, 1.0)
    return {"id_mismatch_share": float(mismatch.mean()),
            "dist_rel_gap_max": float(gap.max())}
