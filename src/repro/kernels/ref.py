"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics contract: each kernel in adc_scan.py / pq_pairwise.py
must match its oracle here (tests/test_kernels.py sweeps shapes & dtypes and
asserts allclose). They are also the CPU fallback used by ops.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def adc_scan_ref(codes: jax.Array, lut: jax.Array) -> jax.Array:
    """Asymmetric-distance scan for ONE query.

    Args:
      codes: (N, M) integer compact codes, values in [0, K).
      lut:   (M, K) float LUT; lut[j, k] = ||q_j - c_k^j||^2.

    Returns:
      (N,) float32 estimated squared distances: sum_j lut[j, codes[:, j]].
    """
    n, m = codes.shape
    # take_along_axis over the K axis, one gather per subspace.
    gathered = jnp.take_along_axis(
        lut[None, :, :], codes[:, :, None].astype(jnp.int32), axis=2
    )  # (N, M, 1)
    return jnp.sum(gathered[..., 0].astype(jnp.float32), axis=1)


def adc_scan_batch_ref(codes: jax.Array, luts: jax.Array) -> jax.Array:
    """Batched-query ADC scan.

    Args:
      codes: (N, M) integer compact codes.
      luts:  (Q, M, K) float LUTs, one per query.

    Returns:
      (Q, N) float32 estimated squared distances.
    """
    q, m, k = luts.shape
    gathered = luts[:, jnp.arange(m)[None, :], codes.astype(jnp.int32)]  # (Q, N, M)
    return jnp.sum(gathered.astype(jnp.float32), axis=-1)


def hop_gather_ref(codes: jax.Array, luts: jax.Array) -> jax.Array:
    """Per-hop beam ADC: (Q, R, M) codes × (Q, M, K) LUTs → (Q, R) f32."""
    q, r, m = codes.shape
    gathered = jnp.take_along_axis(
        luts[:, None, :, :],                          # (Q, 1, M, K)
        codes[:, :, :, None].astype(jnp.int32), axis=3)[..., 0]  # (Q, R, M)
    return jnp.sum(gathered.astype(jnp.float32), axis=-1)


def hop_adc_ref(codes: jax.Array, ids: jax.Array, luts: jax.Array
                ) -> jax.Array:
    """Fused per-hop ADC (gather + LUT reduce) — oracle for hop_adc.py.

    Width-agnostic in R′: the semantics contract covers the classic R ≤ 64
    hop and the multi-expansion frontier R′ = E·R up to 256+ alike
    (DESIGN.md §9) — one gather + reduce, whatever the row count.

    Args:
      codes: (N, M) integer compact codes of the (local) corpus.
      ids:   (Q, R′) int32 candidate rows per query, all in [0, N).
      luts:  (Q, M, K) float LUTs, one per query.

    Returns:
      (Q, R′) float32: out[q, i] = sum_j luts[q, j, codes[ids[q, i], j]].
    """
    return hop_gather_ref(codes[ids.astype(jnp.int32)], luts)


# --------------------------------------------------------------------------
# Fast-scan (fs4) oracles: two 4-bit codes per byte, uint8 LUTs, exact int32
# accumulation, one affine dequant per output (DESIGN.md §8).
# --------------------------------------------------------------------------

def _pair_lut(luts_u8: jax.Array) -> jax.Array:
    """(..., M, 16) u8 LUT → (..., ceil(M/2), 256) int32 PAIRED table.

    ``pair[..., b, byte] = lut[..., 2b, byte & 15] + lut[..., 2b+1, byte >> 4]``
    so ONE gather with the raw packed byte scores TWO sub-codes — the
    fast-scan idiom that halves gather traffic (nibble convention =
    :mod:`repro.pq.pack`, re-derived here so the kernels package keeps
    zero intra-repo imports). Odd M pads a zero row. Integer sums are
    associative, so this is exactly the per-nibble sum.
    """
    m = luts_u8.shape[-2]
    li = luts_u8.astype(jnp.int32)
    if m % 2:
        li = jnp.pad(li, [(0, 0)] * (li.ndim - 2) + [(0, 1), (0, 0)])
    byte = jnp.arange(256)
    return li[..., 0::2, byte & 0xF] + li[..., 1::2, byte >> 4]


def adc_scan_fs_ref(packed: jax.Array, luts_u8: jax.Array, scale: jax.Array,
                    bias: jax.Array) -> jax.Array:
    """Batched fast-scan ADC — oracle for kernels/adc_scan_fs.py.

    Args:
      packed:  (N, ceil(M/2)) uint8 packed codes (pq.pack convention).
      luts_u8: (Q, M, 16) uint8 quantized LUTs.
      scale:   (Q,) float32 per-query dequant step.
      bias:    (Q,) float32 per-query dequant offset.

    Returns:
      (Q, N) float32: ``scale[q] * sum_j luts_u8[q, j, code_j] + M * bias[q]``
      with the inner sum in exact int32.
    """
    q, m, _ = luts_u8.shape
    pair = _pair_lut(luts_u8)                              # (Q, Mb, 256)
    mb = pair.shape[1]
    qi = jnp.arange(q)[:, None, None]
    bi = jnp.arange(mb)[None, None, :]
    vals = pair[qi, bi, packed.astype(jnp.int32)[None]]    # (Q, N, Mb)
    acc = jnp.sum(vals, axis=-1)                           # (Q, N) int32
    return (jnp.asarray(scale, jnp.float32)[:, None] * acc.astype(jnp.float32)
            + m * jnp.asarray(bias, jnp.float32)[:, None])


def hop_adc_fs_ref(packed: jax.Array, ids: jax.Array, luts_u8: jax.Array,
                   scale: jax.Array, bias: jax.Array) -> jax.Array:
    """Fused per-hop fast-scan ADC — oracle for hop_adc.py's packed variant
    (width-agnostic in R′, like :func:`hop_adc_ref`).

    Args:
      packed:  (N, ceil(M/2)) uint8 packed codes of the (local) corpus.
      ids:     (Q, R′) int32 candidate rows per query, all in [0, N).
      luts_u8: (Q, M, 16) uint8 quantized LUTs.
      scale/bias: (Q,) float32 per-query dequant affine.

    Returns:
      (Q, R′) float32 dequantized distances (exact int32 accumulation).
    """
    q, m, _ = luts_u8.shape
    pair = _pair_lut(luts_u8)                              # (Q, Mb, 256)
    mb = pair.shape[1]
    rows = packed.astype(jnp.int32)[ids.astype(jnp.int32)]  # (Q, R, Mb)
    qi = jnp.arange(q)[:, None, None]
    bi = jnp.arange(mb)[None, None, :]
    acc = jnp.sum(pair[qi, bi, rows], axis=-1)             # (Q, R) int32
    return (jnp.asarray(scale, jnp.float32)[:, None] * acc.astype(jnp.float32)
            + m * jnp.asarray(bias, jnp.float32)[:, None])


def pq_pairwise_ref(x: jax.Array, codebook: jax.Array) -> jax.Array:
    """Per-subspace squared distances between sub-vectors and codewords.

    Args:
      x:        (N, M, dsub) sub-vectors.
      codebook: (M, K, dsub) codewords.

    Returns:
      (N, M, K) float32 squared distances ||x[n,j] - codebook[j,k]||^2.
    """
    x = x.astype(jnp.float32)
    c = codebook.astype(jnp.float32)
    x2 = jnp.sum(x * x, axis=-1)[:, :, None]           # (N, M, 1)
    c2 = jnp.sum(c * c, axis=-1)[None, :, :]           # (1, M, K)
    xc = jnp.einsum("nmd,mkd->nmk", x, c,              # (N, M, K)
                    precision=jax.lax.Precision.HIGHEST)
    return x2 - 2.0 * xc + c2


def kmeans_assign_ref(x: jax.Array, centroids: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Nearest-centroid assignment (flat, single space).

    Args:
      x:         (N, D)
      centroids: (K, D)

    Returns:
      (assign (N,) int32, sqdist (N,) float32)
    """
    d = pq_pairwise_ref(x[:, None, :], centroids[None, :, :])[:, 0, :]  # (N, K)
    idx = jnp.argmin(d, axis=1).astype(jnp.int32)
    return idx, jnp.take_along_axis(d, idx[:, None], axis=1)[:, 0]
