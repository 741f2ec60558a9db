"""Fast-scan code packing + LUT quantization (the FAISS "fast scan" layout).

The serving hot loops are memory-bound (DESIGN.md §8): at K=16 a PQ code
needs only 4 bits, so two sub-codes pack into one byte — half the bytes per
distance — and the (M, K) f32 LUT quantizes to uint8 with a per-query affine
(scale, bias) — a quarter of the LUT bytes, small enough that a whole query
LUT tile lives in VMEM/L1. Distances accumulate exactly in int32 and
dequantize once per output:

    dist_f32 = scale * sum_j lut_u8[j, code_j] + M * bias

Packing convention (shared with kernels/ref.py and the fs Pallas kernels):
byte b of a row holds sub-code 2b in its LOW nibble and sub-code 2b+1 in its
HIGH nibble; odd M leaves the last byte's high nibble zero.

Everything here is pure jnp with no intra-repo imports, so any layer
(kernels, search, launch) may depend on it without cycles.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

FS_K = 16  # fast-scan codebook size: 4-bit codes, fixed by the nibble layout


class QuantizedLUT(NamedTuple):
    """Per-query uint8 ADC tables with the affine to undo them.

    lut:   (..., M, 16) uint8 — quantized per-subspace distance tables.
    scale: (...,) float32     — per-query step size ((max-min)/255).
    bias:  (...,) float32     — per-query minimum LUT entry.

    ``dist = scale * int_accumulate + M * bias``; the quantization error of
    a single distance is bounded by ``M * scale / 2`` (each of the M summed
    entries is off by at most half a step).
    """
    lut: jax.Array
    scale: jax.Array
    bias: jax.Array

    def dequantize(self) -> jax.Array:
        """(..., M, 16) f32 reconstruction (debug/error-analysis helper)."""
        sb = (None,) * (self.lut.ndim - self.scale.ndim - 2)
        return (self.lut.astype(jnp.float32)
                * self.scale[(...,) + sb + (None, None)]
                + self.bias[(...,) + sb + (None, None)])


def packed_width(m: int) -> int:
    """Bytes per packed code row for M sub-codes: ceil(M / 2)."""
    return (m + 1) // 2


def pack_codes(codes: jax.Array) -> jax.Array:
    """(N, M) sub-codes in [0, 16) → (N, ceil(M/2)) uint8 packed rows.

    Values ≥ 16 are a caller bug (train with K ≤ 16 for the fs4 layout);
    they are masked to 4 bits rather than silently corrupting neighbors.
    """
    n, m = codes.shape
    c = (codes.astype(jnp.uint8) & 0xF)
    if m % 2:
        c = jnp.concatenate([c, jnp.zeros((n, 1), jnp.uint8)], axis=1)
    lo, hi = c[:, 0::2], c[:, 1::2]
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_codes(packed: jax.Array, m: int) -> jax.Array:
    """(N, ceil(M/2)) packed bytes → (N, M) uint8 sub-codes (inverse)."""
    p = packed.astype(jnp.uint8)
    lo = p & 0xF
    hi = (p >> 4) & 0xF
    full = jnp.stack([lo, hi], axis=-1).reshape(p.shape[0], -1)
    return full[:, :m]


def quantize_luts(luts: jax.Array) -> QuantizedLUT:
    """(Q, M, K≤16) f32 LUTs → per-query uint8 tables + (scale, bias).

    The affine is per QUERY (one scale/bias over the whole (M, K) table),
    matching the int32-accumulate dequantization above. K < 16 tables are
    zero-padded to 16 columns — codes never reference the padding because
    they were trained with the same K.
    """
    q, m, k = luts.shape
    if k > FS_K:
        raise ValueError(f"fast-scan LUTs need K <= {FS_K}, got K={k}")
    luts = luts.astype(jnp.float32)
    lo = jnp.min(luts.reshape(q, -1), axis=1)              # (Q,)
    hi = jnp.max(luts.reshape(q, -1), axis=1)
    # Compiled, a division by a literal becomes a multiplication by its
    # rounded reciprocal (up to 2 ulps off on a TPU v5e); op by op it is a
    # division by an operand. Behind the barrier the divisor is an operand
    # in both, so a compiled scale keeps the op-by-op bits.
    levels = jax.lax.optimization_barrier(jnp.float32(255.0))
    scale = jnp.where(hi > lo, (hi - lo) / levels, 1.0)
    qv = jnp.clip(jnp.round((luts - lo[:, None, None]) / scale[:, None, None]),
                  0, 255).astype(jnp.uint8)
    if k < FS_K:
        qv = jnp.pad(qv, ((0, 0), (0, 0), (0, FS_K - k)))
    return QuantizedLUT(lut=qv, scale=scale, bias=lo)
