"""Pallas TPU kernels for the ADC (asymmetric distance computation) scan.

This is the serving hot loop of PQ-integrated graph ANNS: given the compact
codes of N database vectors and a query's LUT of per-subspace distances,
estimate N squared distances.

TPU adaptation (see DESIGN.md §3)
---------------------------------
The CPU/GPU idiom is a per-lane byte-shuffle gather (AVX `pshufb` over 16-entry
LUTs, or warp gathers). The TPU has no shuffle/gather unit in the hot path, so
we re-derive the scan around the MXU/VPU:

* `adc_scan_kernel` (one query): codes tile (bn, M) lives in VMEM; the LUT
  (M, K) f32 is ≤ 64 KiB and is broadcast to every grid step. For each
  subspace j (static unroll, M ≤ 64) build the comparison mask
  `codes[:, j:j+1] == iota(K)` and reduce `mask * lut[j]` over K — a pure VPU
  (8,128)-lane operation; K = 256 is two lane groups.

* `adc_scan_batch_kernel` (Q queries): the real TPU insight — batching
  queries turns the LUT gather into a GEMM on the MXU. The one-hot expansion
  of a codes tile, onehot(codes) ∈ {0,1}^(bn × M·K), is query-independent, so
  `dists = onehot(codes) @ luts.reshape(Q, M·K).T` scores a (bn, Q) tile with
  one (bn, MK) × (MK, bq) matmul: arithmetic intensity ~bq× higher than the
  scalar scan. bn=256, bq=128, M·K=4096 keeps the one-hot tile (bn × MK bf16 =
  2 MiB) comfortably in VMEM.

Both kernels are validated against kernels/ref.py in interpret mode (CPU) by
tests/test_kernels.py; ops.py picks pallas-on-TPU / jnp-on-CPU automatically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# --------------------------------------------------------------------------
# Single-query scan (VPU formulation)
# --------------------------------------------------------------------------

def _adc_scan_kernel(codes_ref, lut_ref, out_ref, *, m: int, k: int):
    codes = codes_ref[...]                        # (bn, M) int32
    bn = codes.shape[0]
    acc = jnp.zeros((bn,), jnp.float32)
    iota = jax.lax.broadcasted_iota(jnp.int32, (bn, k), 1)
    for j in range(m):                            # static unroll, M small
        mask = (codes[:, j:j + 1] == iota)        # (bn, K) bool
        row = lut_ref[j, :].astype(jnp.float32)   # (K,)
        acc = acc + jnp.sum(jnp.where(mask, row[None, :], 0.0), axis=1)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def adc_scan(codes: jax.Array, lut: jax.Array, *, block_n: int = 1024,
             interpret: bool = False) -> jax.Array:
    """(N, M) int codes × (M, K) LUT → (N,) f32 distances. Pallas path."""
    n, m = codes.shape
    _, k = lut.shape
    n_pad = (-n) % block_n
    codes_i = codes.astype(jnp.int32)
    if n_pad:
        codes_i = jnp.pad(codes_i, ((0, n_pad), (0, 0)))
    grid = (codes_i.shape[0] // block_n,)
    out = pl.pallas_call(
        functools.partial(_adc_scan_kernel, m=m, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, m), lambda i: (i, 0)),
            pl.BlockSpec((m, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((codes_i.shape[0],), jnp.float32),
        interpret=interpret,
    )(codes_i, lut)
    return out[:n]


# --------------------------------------------------------------------------
# Batched-query scan (MXU one-hot GEMM formulation)
# --------------------------------------------------------------------------

def _adc_scan_batch_kernel(codes_ref, luts_ref, out_ref, *, m: int, k: int):
    codes = codes_ref[...]                          # (bn, M) int32
    bn = codes.shape[0]
    # one-hot (bn, M*K) built with a single iota compare; bf16 feeds the MXU.
    iota = jax.lax.broadcasted_iota(jnp.int32, (bn, m, k), 2)
    onehot = (codes[:, :, None] == iota).astype(jnp.bfloat16).reshape(bn, m * k)
    luts = luts_ref[...]                            # (bq, M*K) f32
    # (bn, MK) @ (MK, bq) -> (bn, bq) on the MXU, fp32 accumulation.
    acc = jax.lax.dot_general(
        onehot, luts.astype(jnp.bfloat16).T,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    out_ref[...] = acc.T                            # (bq, bn)


@functools.partial(jax.jit, static_argnames=("block_n", "block_q", "interpret"))
def adc_scan_batch(codes: jax.Array, luts: jax.Array, *, block_n: int = 256,
                   block_q: int = 128,
                   interpret: bool = False) -> jax.Array:
    """(N, M) codes × (Q, M, K) LUTs → (Q, N) f32 distances. Pallas path."""
    n, m = codes.shape
    q, _, k = luts.shape
    n_pad = (-n) % block_n
    q_pad = (-q) % block_q
    codes_i = codes.astype(jnp.int32)
    luts_f = luts.reshape(q, m * k)
    if n_pad:
        codes_i = jnp.pad(codes_i, ((0, n_pad), (0, 0)))
    if q_pad:
        luts_f = jnp.pad(luts_f, ((0, q_pad), (0, 0)))
    np_, qp_ = codes_i.shape[0], luts_f.shape[0]
    grid = (qp_ // block_q, np_ // block_n)
    out = pl.pallas_call(
        functools.partial(_adc_scan_batch_kernel, m=m, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, m), lambda iq, jn: (jn, 0)),
            pl.BlockSpec((block_q, m * k), lambda iq, jn: (iq, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda iq, jn: (iq, jn)),
        out_shape=jax.ShapeDtypeStruct((qp_, np_), jnp.float32),
        interpret=interpret,
    )(codes_i, luts_f)
    return out[:q, :n]
