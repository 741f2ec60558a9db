"""Pallas TPU kernel: per-hop ADC lookup for a BATCH of beam searches.

The beam search's inner op: at each hop, every query gathers its R
neighbors' codes and sums LUT entries — shapes (Q, R, M) codes × (Q, M, K)
LUTs → (Q, R). R is tiny (≤64), so unlike adc_scan this is lane-bound, not
MXU-bound; the kernel keeps each query's LUT resident in VMEM and does the
K-lane iota-compare per subspace (same trick as adc_scan, batched over Q).

grid = (Q / bq,); per step: codes tile (bq, R, M) + LUT tile (bq, M, K).
VMEM @ bq=8, R=64, M=16, K=256: 8·16·256·4 = 128 KiB LUTs + codes ≪ 1 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hop_gather_kernel(codes_ref, luts_ref, out_ref, *, m: int, k: int):
    codes = codes_ref[...]                           # (bq, R, M) int32
    luts = luts_ref[...]                             # (bq, M, K) f32
    bq, r, _ = codes.shape
    acc = jnp.zeros((bq, r), jnp.float32)
    iota = jax.lax.broadcasted_iota(jnp.int32, (bq, r, k), 2)
    for j in range(m):                               # M static unroll
        mask = codes[:, :, j:j + 1] == iota          # (bq, R, K)
        row = luts[:, j, :]                          # (bq, K)
        acc = acc + jnp.sum(
            jnp.where(mask, row[:, None, :], 0.0), axis=2)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def hop_gather(codes: jax.Array, luts: jax.Array, *, block_q: int = 8,
               interpret: bool = False) -> jax.Array:
    """(Q, R, M) int codes × (Q, M, K) LUTs → (Q, R) f32 distances."""
    q, r, m = codes.shape
    _, _, k = luts.shape
    q_pad = (-q) % block_q
    codes_i = codes.astype(jnp.int32)
    luts_f = luts.astype(jnp.float32)
    if q_pad:
        codes_i = jnp.pad(codes_i, ((0, q_pad), (0, 0), (0, 0)))
        luts_f = jnp.pad(luts_f, ((0, q_pad), (0, 0), (0, 0)))
    grid = (codes_i.shape[0] // block_q,)
    out = pl.pallas_call(
        functools.partial(_hop_gather_kernel, m=m, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, r, m), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_q, m, k), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, r), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((codes_i.shape[0], r), jnp.float32),
        interpret=interpret,
    )(codes_i, luts_f)
    return out[:q]
