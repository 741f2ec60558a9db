"""Fused Pallas TPU kernel: gather + ADC reduce for one beam-search hop.

The per-hop hot loop of graph-routed serving does two things per query:
gather the compact code rows of its R′ candidate neighbors, then reduce each
row against the query's LUT. As two XLA ops that round-trips a (Q, R′, M)
gathered-codes array through HBM between the gather and the reduce
(`hop_gather.py` only covers the reduce half). This kernel fuses both: the
ids never leave SMEM, the gathered rows never leave VMEM.

R′ is the FRONTIER width: the adjacency degree R classically, E·R under
multi-expansion beam search (``search/beam.py`` with ``expand=E``,
DESIGN.md §9). ``block_q`` auto-tunes to the width (``_auto_block_q``): the
query tile shrinks 8 → 4 → 2 as R′ grows 64 → 128 → 256.

Layout (DESIGN.md §6, §9):

* The code table stays in HBM (``memory_space=pl.ANY``); only the
  frontier's rows are copied into VMEM. A TPU DMA moves whole 128-lane
  rows of 32-bit words, so the table is viewed as WORD ROWS
  (:func:`code_words`): each code row's bytes (u8 codes, or fs4 packed
  nibbles) are little-endian packed into W = next_pow2(ceil(bytes/4))
  int32 words, and 128 / W code rows share one 128-lane word row. This is
  a reinterpretation of the uint8 bytes at rest, not a wider dtype: 1M ×
  16-byte rows are 16 MB either way.
* ``ids`` (Q, R′) int32 ride in per query tile as an SMEM block; each id
  starts one async row copy (``pltpu.make_async_copy``) into an (R′, 128)
  VMEM scratch, and records its lane offset inside the word row.
* Per query the reduce extracts each needed word column with a masked lane
  sum, shifts out each sub-code, and does the K-lane iota compare against
  the query's LUT row (the VPU formulation of adc_scan; M static unroll).
* grid = (Q / bq,); output tile (1, bq, R′) of a (Q / bq, bq, R′) array,
  so any bq satisfies the TPU's (8, 128) block rule.

``hop_adc_fs`` is the FAST-SCAN twin (DESIGN.md §8): the same kernel over
4-bit packed rows (half the bytes) with a K=16 integer LUT and exact int32
accumulation; the dequant lives in ``ops.hop_adc_fs``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # words per DMA row: one full lane row of 32-bit words


def _auto_block_q(r: int) -> int:
    """Default query tile for a frontier of width ``r``: 8 at R′ ≤ 64,
    4 at 128, 2 at 256+ — keeps the LUT tile + out tile + gather scratch
    working set roughly constant as multi-expansion widens the hop
    (DESIGN.md §9 VMEM budget)."""
    return max(1, 512 // max(r, 64))


def _row_words(row_bytes: int) -> int:
    """int32 words per code row: a power of two, so rows tile a lane row."""
    w = 1
    while 4 * w < row_bytes:
        w *= 2
    assert w <= LANES, f"code rows of {row_bytes} bytes exceed one lane row"
    return w


def code_words(codes: jax.Array) -> jax.Array:
    """(N, B) uint8 code rows → (ceil(N·W/128), 128) int32 word rows.

    Code row r occupies words ``[(r % (128/W))·W, +W)`` of word row
    ``r // (128/W)``; byte b of the row sits in word b // 4 at bits
    8·(b % 4), so sub-code j of a row with ``bits``-wide codes sits at bit
    offset j·bits of the row (pq.pack's nibble convention included)."""
    n, b = codes.shape
    w = _row_words(b)
    c = jnp.pad(codes.astype(jnp.uint32),
                ((0, (-n) % (LANES // w)), (0, 4 * w - b))).reshape(-1, w, 4)
    words = c[..., 0] | (c[..., 1] << 8) | (c[..., 2] << 16) | (c[..., 3] << 24)
    return jax.lax.bitcast_convert_type(words, jnp.int32).reshape(-1, LANES)


def _hop_kernel(ids_ref, words_hbm, luts_ref, out_ref, buf, lane0, sem, *,
                bits: int, m_eff: int, k: int, w: int, rp: int,
                block_q: int):
    """One grid step: block_q queries × R′ fused gather-reduce. ``m_eff ≤ M``
    statically shortens the reduce unroll — the partial-LUT lower-bound pass
    of hop pruning (DESIGN.md §11)."""
    rows_per_word_row = LANES // w
    code_mask = (1 << bits) - 1

    def row_copy(src_row, dst_row):
        return pltpu.make_async_copy(words_hbm.at[pl.ds(src_row, 1)],
                                     buf.at[pl.ds(dst_row, 1)], sem)

    def q_body(qi, carry):
        # 1. one row copy per neighbor id, ids read straight from SMEM
        def issue(j, c):
            row = ids_ref[0, qi, j]
            row_copy(row // rows_per_word_row, j).start()
            lane0[pl.ds(j, 1), :] = jnp.full(
                (1, LANES), (row % rows_per_word_row) * w, jnp.int32)
            return c

        jax.lax.fori_loop(0, rp, issue, 0)

        def wait(j, c):
            row_copy(0, 0).wait()
            return c

        jax.lax.fori_loop(0, rp, wait, 0)
        # 2. word columns of this query's rows: masked lane sums (R′, 1)
        rel = jax.lax.broadcasted_iota(jnp.int32, (rp, LANES), 1) - lane0[...]
        words = buf[...]
        cols = {}

        def word_col(i):
            if i not in cols:
                cols[i] = jnp.sum(jnp.where(rel == i, words, 0), axis=1,
                                  keepdims=True)
            return cols[i]

        # 3. LUT reduce: K-lane iota compare per subspace (VPU formulation)
        lut = luts_ref[pl.ds(qi, 1)][0]                    # (M, K)
        iota = jax.lax.broadcasted_iota(jnp.int32, (rp, k), 1)
        acc = jnp.zeros((rp,), lut.dtype)
        for j in range(m_eff):                             # M static unroll
            bit = j * bits
            code = jax.lax.shift_right_logical(
                word_col(bit // 32), bit % 32) & code_mask  # (R′, 1)
            acc = acc + jnp.sum(
                jnp.where(code == iota, lut[j, :][None, :], 0), axis=1)
        out_ref[0, pl.ds(qi, 1), :] = acc[None]
        return carry

    jax.lax.fori_loop(0, block_q, q_body, 0)


def _hop_call(words, ids, luts, *, bits: int, row_bytes: int, m_eff: int,
              block_q: int | None, interpret: bool):
    q, r = ids.shape
    _, m, k = luts.shape
    w = _row_words(row_bytes)
    block_q = min(block_q or _auto_block_q(r), q)
    q_pad = (-q) % block_q
    ids_i = ids.astype(jnp.int32)
    if q_pad:  # padded queries gather row 0 — cheap, discarded below
        ids_i = jnp.pad(ids_i, ((0, q_pad), (0, 0)))
        luts = jnp.pad(luts, ((0, q_pad), (0, 0), (0, 0)))
    g = ids_i.shape[0] // block_q
    out = pl.pallas_call(
        functools.partial(_hop_kernel, bits=bits, m_eff=m_eff, k=k, w=w,
                          rp=r, block_q=block_q),
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, block_q, r), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),              # HBM-resident
            pl.BlockSpec((block_q, m, k), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, r), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, block_q, r), luts.dtype),
        scratch_shapes=[pltpu.VMEM((r, LANES), jnp.int32),
                        pltpu.VMEM((r, LANES), jnp.int32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(ids_i.reshape(g, block_q, r), words, luts)
    return out.reshape(g * block_q, r)[:q]


@functools.partial(jax.jit,
                   static_argnames=("block_q", "interpret", "m_prefix"))
def hop_adc(codes: jax.Array, ids: jax.Array, luts: jax.Array, *,
            block_q: int | None = None, interpret: bool = False,
            m_prefix: int = 0) -> jax.Array:
    """Fused per-hop ADC: (N, M) uint8 codes, (Q, R′) ids, (Q, M, K ≤ 256)
    f32 LUTs → (Q, R′) f32.

    ``out[q, i] = sum_j luts[q, j, codes[ids[q, i], j]]`` — the distance of
    query q to its i-th candidate neighbor. All ids must be valid rows in
    ``[0, N)`` (the beam passes masked-to-0 ids for dead lanes and infs the
    distances afterwards). ``block_q=None`` auto-tunes the query tile to the
    frontier width (``_auto_block_q``). ``0 < m_prefix < M`` reduces only
    the first m_prefix subspaces — the hop-pruning lower bound.
    """
    m = codes.shape[1]
    return _hop_call(code_words(codes), ids, luts.astype(jnp.float32),
                     bits=8, row_bytes=m,
                     m_eff=m_prefix if 0 < m_prefix < m else m,
                     block_q=block_q, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("m", "block_q", "interpret",
                                             "m_prefix"))
def hop_adc_fs(packed: jax.Array, ids: jax.Array, luts_u8: jax.Array, *,
               m: int, block_q: int | None = None, interpret: bool = False,
               m_prefix: int = 0) -> jax.Array:
    """Fused per-hop fast-scan ADC: (N, ceil(M/2)) packed codes, (Q, R′)
    ids, (Q, M, 16) u8 LUTs → (Q, R′) int32 exact accumulators.

    Pure-integer on purpose — the per-query dequant affine is applied by
    ``ops.hop_adc_fs`` so the float op sequence matches the oracle
    ``ref.hop_adc_fs_ref`` exactly on every backend. ``0 < m_prefix < m``
    accumulates only the first m_prefix subspaces (hop-pruning lower
    bound); the caller's dequant must then use ``m_prefix · bias``.
    """
    return _hop_call(code_words(packed), ids, luts_u8.astype(jnp.int32),
                     bits=4, row_bytes=packed.shape[1],
                     m_eff=m_prefix if 0 < m_prefix < m else m,
                     block_q=block_q, interpret=interpret)
