"""Public, jitted entry points for the PQ kernels with backend dispatch.

Call these from library code. On TPU they run the compiled Pallas kernels;
elsewhere they run the pure-jnp oracle, which XLA fuses well — the Pallas
path is still exercised on CPU via interpret mode in the tests.

Backends:

* ``"auto"``      — compiled Pallas on TPU, jnp oracle elsewhere (default).
* ``"pallas"``    — the compiled Pallas kernel; raises off-TPU, so a run
                    that asked for the device kernel never silently gets
                    the interpreter instead.
* ``"interpret"`` — the Pallas kernel in interpreter mode (CPU tests).
* ``"ref"``       — the pure-jnp oracle from :mod:`repro.kernels.ref`.

Dtype boundary: callers hand in codes in whatever integer dtype they store
(uint8 for K ≤ 256 indices, uint8 packed bytes for the fs4 layout, int32
ids) and THIS module casts once to the canonical kernel dtypes — int32
codes/ids for the scans, uint8 code rows for the hop gathers, f32 LUTs.
Kernel modules and oracles assume the canonical dtypes; no per-call casting
in callers.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

# Submodules are imported EAGERLY (not inside the dispatch functions):
# kernels/__init__ re-exports same-named functions (adc_scan_fs, hop_adc,
# hop_gather), and a lazy first import of the submodule would setattr the
# MODULE over the package-level function binding, breaking the API
# mid-session. Importing them all here, before __init__ binds the
# functions, keeps the package attributes deterministic.
from repro.kernels import adc_scan as _adc
from repro.kernels import adc_scan_fs as _adcfs
from repro.kernels import hop_adc as _hop
from repro.kernels import hop_gather as _hopg
from repro.kernels import pq_pairwise as _pqp
from repro.kernels import ref as _ref

Backend = Literal["auto", "pallas", "interpret", "ref"]


# --------------------------------------------------------------------------
# Row-padding helpers — the ONE home for the sentinel/divisibility padding
# idiom (search/engine.py, graphs/vamana.py, repro/index/* all pad this way).
# --------------------------------------------------------------------------

def pad_sentinel_row(x: jax.Array) -> jax.Array:
    """(N, ...) → (N+1, ...): append one all-zero row at index N.

    Row N is the sentinel every padded adjacency points at (graphs/
    adjacency.py), so code/vector tables gathered by beam ids must carry a
    readable — never trusted — row there. Callers mask sentinel slots by id,
    not by the row's contents.
    """
    return jnp.concatenate(
        [x, jnp.zeros((1,) + x.shape[1:], x.dtype)], axis=0)


def pad_rows_to_multiple(x: jax.Array, mult: int) -> jax.Array:
    """(N, ...) → (N', ...) with N' the next multiple of ``mult`` (zero-row
    padded) — shard-divisibility padding for row-sharded device_puts."""
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)


def _codes_i32(codes) -> jax.Array:
    """Canonicalize plain (unpacked) codes / id arrays: any int → int32."""
    return jnp.asarray(codes).astype(jnp.int32)


def _codes_u8(packed) -> jax.Array:
    """Canonicalize fs4 packed code bytes: any int → uint8."""
    return jnp.asarray(packed).astype(jnp.uint8)


def _dequant(acc, scale, bias, m: int) -> jax.Array:
    """Per-query affine undo for fs4 int32 accumulators: (Q, X) int32 +
    (Q,) scale/bias → (Q, X) f32. The SAME eager op sequence as the tail of
    the fs oracles, so pallas and ref paths agree bitwise (an in-kernel
    dequant could be FMA-fused under jit and drift an ulp)."""
    return (jnp.asarray(scale, jnp.float32)[:, None] * acc.astype(jnp.float32)
            + m * jnp.asarray(bias, jnp.float32)[:, None])


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(backend: Backend) -> str:
    if backend == "auto":
        return "pallas" if _on_tpu() else "ref"
    return backend


# Names of the Pallas kernels this process has traced into a program
# (compiled or interpreted) — chip_smoke.py prints which ones ran.
TRACED_KERNELS: set[str] = set()


def _interpret_flag(mode: str, kernel: str) -> bool:
    """interpret= for a resolved pallas/interpret mode. Only ``"interpret"``
    interprets: ``"pallas"`` off-TPU raises instead of quietly running the
    interpreter in place of the device kernel."""
    TRACED_KERNELS.add(kernel)
    if mode == "interpret":
        return True
    if not _on_tpu():
        raise RuntimeError(
            f"backend='pallas' needs a TPU (JAX default backend is "
            f"{jax.default_backend()!r}); use backend='interpret' for the "
            f"Pallas interpreter or 'ref' for the jnp oracle")
    return False


def adc_scan(codes, lut, *, backend: Backend = "auto", block_n: int = 1024):
    """One-query ADC scan: (N, M) codes × (M, K) LUT → (N,) f32."""
    mode = _resolve(backend)
    codes = _codes_i32(codes)
    if mode == "ref":
        return _ref.adc_scan_ref(codes, lut)
    return _adc.adc_scan(codes, lut, block_n=block_n,
                         interpret=_interpret_flag(mode, "adc_scan"))


def adc_scan_batch(codes, luts, *, backend: Backend = "auto",
                   block_n: int = 256, block_q: int = 128):
    """Batched ADC scan: (N, M) codes × (Q, M, K) LUTs → (Q, N) f32."""
    mode = _resolve(backend)
    codes = _codes_i32(codes)
    if mode == "ref":
        return _ref.adc_scan_batch_ref(codes, luts)
    return _adc.adc_scan_batch(codes, luts, block_n=block_n, block_q=block_q,
                               interpret=_interpret_flag(mode,
                                                         "adc_scan_batch"))


def adc_scan_fs(packed, luts_u8, scale, bias, *, backend: Backend = "auto",
                block_n: int = 512, block_q: int = 64):
    """Batched FAST-SCAN ADC: (N, ceil(M/2)) 4-bit-packed codes ×
    (Q, M, 16) uint8 LUTs + per-query (Q,) (scale, bias) → (Q, N) f32.

    The fs4 serving layout (DESIGN.md §8): half the code bytes, a quarter
    of the LUT bytes, exact int32 accumulation, one dequant per output.
    Pack codes with ``repro.pq.pack.pack_codes`` and quantize LUTs with
    ``repro.pq.pack.quantize_luts``.
    """
    mode = _resolve(backend)
    packed = _codes_u8(packed)
    luts_u8 = _codes_u8(luts_u8)
    if mode == "ref":
        return _ref.adc_scan_fs_ref(packed, luts_u8, scale, bias)
    acc = _adcfs.adc_scan_fs(packed, luts_u8, block_n=block_n,
                             block_q=block_q,
                             interpret=_interpret_flag(mode, "adc_scan_fs"))
    return _dequant(acc, scale, bias, luts_u8.shape[1])


def hop_gather(codes, luts, *, backend: Backend = "auto", block_q: int = 8):
    """Per-hop beam ADC on PRE-GATHERED codes: (Q, R, M) × (Q, M, K) →
    (Q, R) f32. Prefer :func:`hop_adc` where the ids are still at hand —
    it fuses the gather too."""
    mode = _resolve(backend)
    codes = _codes_i32(codes)
    if mode == "ref":
        return _ref.hop_gather_ref(codes, luts)
    return _hopg.hop_gather(codes, luts, block_q=block_q,
                            interpret=_interpret_flag(mode, "hop_gather"))


def _hop_codes(codes, k: int) -> jax.Array:
    """Code rows for the fused hop kernels: uint8 bytes at rest (K ≤ 256)."""
    if k > 256:
        raise ValueError(f"the fused hop kernels gather uint8 code rows; "
                         f"K={k} > 256 needs backend='ref'")
    return _codes_u8(codes)


def hop_adc(codes, ids, luts, *, backend: Backend = "auto",
            block_q: int | None = None, m_prefix: int = 0):
    """FUSED per-hop beam ADC: (N, M) codes, (Q, R′) ids, (Q, M, K) LUTs →
    (Q, R′) f32 — gathers the R′ neighbor code rows AND reduces them against
    each query's LUT in one kernel (no (Q, R′, M) HBM round-trip). R′ is the
    beam's frontier width — the graph degree R classically, E·R under
    multi-expansion (beam_search(expand=E), DESIGN.md §9); ``block_q=None``
    lets the kernel pick its query tile from R′. All ids must be valid rows
    in [0, N).

    ``0 < m_prefix < M`` reduces only the FIRST m_prefix subspaces — the
    partial-LUT lower bound of hop pruning (DESIGN.md §11; every LUT entry
    is a squared subdistance ≥ 0, so the prefix sum bounds the full sum
    from below). The Pallas path statically shortens the reduce unroll; the
    oracle slices."""
    mode = _resolve(backend)
    ids = _codes_i32(ids)
    mp = m_prefix if 0 < m_prefix < codes.shape[1] else 0
    if mode == "ref":
        codes = _codes_i32(codes)
        if mp:
            return _ref.hop_adc_ref(codes[:, :mp], ids, luts[:, :mp])
        return _ref.hop_adc_ref(codes, ids, luts)
    return _hop.hop_adc(_hop_codes(codes, luts.shape[2]), ids, luts,
                        block_q=block_q,
                        interpret=_interpret_flag(mode, "hop_adc"),
                        m_prefix=mp)


def hop_adc_fs(packed, ids, luts_u8, scale, bias, *,
               backend: Backend = "auto", block_q: int | None = None,
               m_prefix: int = 0):
    """FUSED per-hop FAST-SCAN ADC: (N, ceil(M/2)) packed codes, (Q, R′)
    ids, (Q, M, 16) uint8 LUTs + (Q,) (scale, bias) → (Q, R′) f32 — the
    packed twin of :func:`hop_adc` (same gather fusion, half the code
    bytes, quarter LUT bytes, int32 accumulation, same frontier-width
    auto-tuning at ``block_q=None``).

    ``m_prefix`` as in :func:`hop_adc`; the dequant then uses
    ``m_prefix · bias`` (bias ≥ 0 — quantize_luts anchors it at the LUT
    minimum), so the partial score lower-bounds the full one in the
    quantized metric too. Odd m_prefix is exact on the oracle as well: the
    paired-LUT table zero-pads the dangling high nibble."""
    mode = _resolve(backend)
    packed = _codes_u8(packed)
    ids = _codes_i32(ids)
    luts_u8 = _codes_u8(luts_u8)
    m = luts_u8.shape[1]
    mp = m_prefix if 0 < m_prefix < m else 0
    if mode == "ref":
        if mp:
            return _ref.hop_adc_fs_ref(packed[:, :(mp + 1) // 2], ids,
                                       luts_u8[:, :mp], scale, bias)
        return _ref.hop_adc_fs_ref(packed, ids, luts_u8, scale, bias)
    acc = _hop.hop_adc_fs(packed, ids, luts_u8, m=m, block_q=block_q,
                          interpret=_interpret_flag(mode, "hop_adc_fs"),
                          m_prefix=mp)
    return _dequant(acc, scale, bias, mp or m)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _pq_pairwise_kernel(x, codebook, block_n: int, interpret: bool):
    return _pqp.pq_pairwise(x, codebook, block_n=block_n, interpret=interpret)


def _pq_pairwise_fwd(x, codebook, block_n, interpret):
    return _pq_pairwise_kernel(x, codebook, block_n, interpret), (x, codebook)


def _pq_pairwise_bwd(block_n, interpret, res, g):
    """Closed-form gradient of d = ‖x‖² − 2x·c + ‖c‖² (a pallas_call has no
    reverse-mode rule of its own): ∂/∂x = 2(x·Σ_k g − g·c),
    ∂/∂c = 2(c·Σ_n g − gᵀ·x), per subspace."""
    x, c = res
    xf, cf = x.astype(jnp.float32), c.astype(jnp.float32)
    dx = 2.0 * (xf * jnp.sum(g, axis=2)[..., None]
                - jnp.einsum("nmk,mkd->nmd", g, cf))
    dc = 2.0 * (cf * jnp.sum(g, axis=0)[..., None]
                - jnp.einsum("nmk,nmd->mkd", g, xf))
    return dx.astype(x.dtype), dc.astype(c.dtype)


_pq_pairwise_kernel.defvjp(_pq_pairwise_fwd, _pq_pairwise_bwd)


def pq_pairwise(x, codebook, *, backend: Backend = "auto", block_n: int = 512):
    """Sub-vector/codeword distance table: (N,M,dsub) × (M,K,dsub) → (N,M,K).

    Differentiable on every backend: the Pallas path carries the closed-form
    backward pass (``_pq_pairwise_bwd``), so the RPQ losses train through
    the kernel on TPU."""
    mode = _resolve(backend)
    if mode == "ref":
        return _ref.pq_pairwise_ref(x, codebook)
    return _pq_pairwise_kernel(x, codebook, block_n,
                               _interpret_flag(mode, "pq_pairwise"))


def kmeans_assign(x, centroids, *, backend: Backend = "auto"):
    """Nearest centroid: (N, D) × (K, D) → (assign (N,) i32, sqdist (N,) f32)."""
    d = pq_pairwise(x[:, None, :], centroids[None, :, :], backend=backend)[:, 0, :]
    idx = jnp.argmin(d, axis=1).astype(jnp.int32)
    best = jnp.take_along_axis(d, idx[:, None], axis=1)[:, 0]
    return idx, best
