"""Pallas TPU kernel: fast-scan bulk ADC over 4-bit packed codes.

The f32 scan (adc_scan.py) moves 1 byte/code and 4 bytes/LUT-entry through
VMEM; this kernel is the fast-scan layout (DESIGN.md §8): K=16 sub-codebooks
pack two 4-bit codes per byte — HALF the code bytes per distance — and the
LUT rides in as uint8 with a per-query (scale, bias) affine — a QUARTER of
the LUT bytes. The tile budget that the layout buys:

* codes tile (bn, ceil(M/2)) uint8: bn=512, M=16 → 4 KiB (vs 8 KiB u8,
  32 KiB of the old int32 staging);
* LUT tile (bq, M·16) uint8: bq=64, M=16 → 16 KiB (vs 64 KiB f32 — and vs
  1 MiB f32 at K=256 for the same M·K=4096 table width).

Compute: each packed byte is spread across its 16 one-hot lanes with a
(bn, Mb) × (Mb, Mb·16) 0/1 matmul (a lane repeat the MXU does exactly),
its two nibbles are split with VPU shifts, and the two one-hot halves hit
the MXU as (bn, Mb·16) × (Mb·16, bq) GEMMs against the even- and
odd-subspace LUT halves — the same batching insight as adc_scan_batch, but
the contraction is 16× narrower so the one-hot tile is 16× smaller too.
Every operand is an exact small integer in bf16 (one-hot ∈ {0,1}, bytes
and LUT entries ≤ 255 — bf16 holds integers up to 256 exactly) and the f32
accumulator is exact below 2²⁴, so the int32 accumulators this kernel
emits are BIT-EXACT with the oracle ``ref.adc_scan_fs_ref``. The kernel
stays pure-integer on purpose: the affine dequant (`scale·acc + M·bias`)
lives in ``ops.adc_scan_fs`` so the float op sequence is identical on
every backend (an in-kernel dequant could be FMA-fused by XLA and drift an ulp from the eager oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bf16(x):
    """Exact small integers → bf16 (uint8 has no direct bf16 cast on the
    TPU, so widen through int32 and f32)."""
    return x.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)


def _mxu(a, b_t):
    """(r, c) × (s, c)ᵀ → (r, s) f32 on the MXU."""
    return jax.lax.dot_general(a, b_t, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _adc_scan_fs_kernel(codes_ref, luts_ref, out_ref, *, mb: int):
    w = mb * 16
    # byte b of a row → lanes [16b, 16b + 16): repeat via a 0/1 matmul
    lane = jax.lax.broadcasted_iota(jnp.int32, (w, mb), 0)
    spread = ((lane >> 4) == jax.lax.broadcasted_iota(jnp.int32, (w, mb), 1))
    p = _mxu(_bf16(codes_ref[...]), spread.astype(jnp.bfloat16))
    p = p.astype(jnp.int32)                         # (bn, Mb·16) packed
    value = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) & 0xF
    lo = ((p & 0xF) == value).astype(jnp.bfloat16)  # even sub-codes 2b
    hi = ((p >> 4) == value).astype(jnp.bfloat16)   # odd sub-codes 2b+1
    luts = _bf16(luts_ref[...])                     # (bq, 2·Mb·16)
    # (bn, Mb16) @ (Mb16, bq) twice → exact integer sums (≤ M·255 < 2²⁴)
    acc = _mxu(lo, luts[:, :w]) + _mxu(hi, luts[:, w:])
    out_ref[...] = acc.T.astype(jnp.int32)          # (bq, bn)


@functools.partial(jax.jit, static_argnames=("block_n", "block_q", "interpret"))
def adc_scan_fs(packed: jax.Array, luts_u8: jax.Array, *, block_n: int = 512,
                block_q: int = 64, interpret: bool = False) -> jax.Array:
    """(N, ceil(M/2)) packed codes × (Q, M, 16) u8 LUTs → (Q, N) int32
    accumulators (``sum_j lut[q, j, code_j]``, exact).

    Callers go through :func:`repro.kernels.ops.adc_scan_fs`, which casts
    the packed codes to uint8 once at the dispatch boundary and applies the
    per-query dequantization affine.
    """
    n, mb = packed.shape
    q, m, k = luts_u8.shape
    assert k == 16, f"fast-scan LUTs are (Q, M, 16); got K={k}"
    # [even subspaces | odd subspaces], odd M zero-pads the dangling nibble
    lut = jnp.pad(luts_u8, ((0, 0), (0, 2 * mb - m), (0, 0)))
    luts_flat = jnp.concatenate([lut[:, 0::2], lut[:, 1::2]], axis=1
                                ).reshape(q, 2 * mb * 16)
    n_pad = (-n) % block_n
    q_pad = (-q) % block_q
    if n_pad:
        packed = jnp.pad(packed, ((0, n_pad), (0, 0)))
    if q_pad:
        luts_flat = jnp.pad(luts_flat, ((0, q_pad), (0, 0)))
    np_, qp_ = packed.shape[0], luts_flat.shape[0]
    grid = (qp_ // block_q, np_ // block_n)
    out = pl.pallas_call(
        functools.partial(_adc_scan_fs_kernel, mb=mb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, mb), lambda iq, jn: (jn, 0)),
            pl.BlockSpec((block_q, 2 * mb * 16), lambda iq, jn: (iq, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda iq, jn: (iq, jn)),
        out_shape=jax.ShapeDtypeStruct((qp_, np_), jnp.int32),
        interpret=interpret,
    )(packed, luts_flat)
    return out[:q, :n]
