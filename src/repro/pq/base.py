"""Serving-side quantizer representation shared by PQ / OPQ / RPQ.

Every trainable quantizer in this repo (classic PQ, OPQ's alternating
optimization, the paper's learned RPQ) exports a :class:`QuantizerModel` —
an orthonormal rotation + codebooks — which is all the serving engine needs:
``encode`` the base vectors once offline, ``build_lut`` per query online,
``adc`` via the Pallas scan kernel.

Catalyst-style nonlinear encoders don't fit this linear form; they provide
the same *protocol* (codes + ``lut_fn``) via their own module.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.pq.pack import quantize_luts

# Rows per encode step: a (16384, 16, 256) f32 distance table is 256 MiB.
ENCODE_BLOCK = 16384


class QuantizerModel(NamedTuple):
    r: jax.Array          # (D, D) orthonormal rotation; identity for PQ
    codebooks: jax.Array  # (M, K, dsub)

    @property
    def dim(self) -> int:
        return self.r.shape[0]

    @property
    def m(self) -> int:
        return self.codebooks.shape[0]

    @property
    def k(self) -> int:
        return self.codebooks.shape[1]

    @property
    def dsub(self) -> int:
        return self.codebooks.shape[2]


def rotate_split(model: QuantizerModel, x: jax.Array) -> jax.Array:
    """(N, D) → (N, M, dsub) rotated sub-vectors."""
    xr = jnp.matmul(x, model.r.T, precision=jax.lax.Precision.HIGHEST)
    return xr.reshape(x.shape[0], model.m, model.dsub)


def encode(model: QuantizerModel, x: jax.Array, *, backend: str = "auto") -> jax.Array:
    """(N, D) → (N, M) hard codes (uint8 when K ≤ 256).

    Encodes ``ENCODE_BLOCK`` rows at a time: the (N, M, K) distance table of
    a whole corpus does not fit a device (16 GB at N=1M, M=16, K=256), the
    (block, M, K) one does."""
    return _encode(model, jnp.asarray(x, jnp.float32), backend=backend)


@functools.partial(jax.jit, static_argnames=("backend",))
def _encode(model: QuantizerModel, x: jax.Array, *, backend: str) -> jax.Array:
    n, d = x.shape
    block = min(ENCODE_BLOCK, n)
    xb = jnp.pad(x, ((0, (-n) % block), (0, 0))).reshape(-1, block, d)

    def one(xs):
        dist = kops.pq_pairwise(rotate_split(model, xs), model.codebooks,
                                backend=backend)
        return jnp.argmin(dist, axis=-1)

    codes = jax.lax.map(one, xb).reshape(-1, model.m)[:n]
    return codes.astype(jnp.uint8 if model.k <= 256 else jnp.int32)


def decode(model: QuantizerModel, codes: jax.Array) -> jax.Array:
    """(N, M) codes → (N, D) reconstruction in the ORIGINAL space (R^T x')."""
    sub = jnp.take_along_axis(
        model.codebooks[None], codes[:, :, None, None].astype(jnp.int32), axis=2
    )[:, :, 0, :]
    return jnp.matmul(sub.reshape(codes.shape[0], -1), model.r,
                      precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("quantize",))
def build_lut(model: QuantizerModel, queries: jax.Array, *,
              quantize: bool = False):
    """(Q, D) → (Q, M, K) per-query ADC lookup tables.

    ``quantize=True`` returns a :class:`repro.pq.pack.QuantizedLUT`
    instead — (Q, M, 16) uint8 tables + per-query (scale, bias) — for the
    fast-scan serving layout (requires K ≤ 16; pair with
    ``pack.pack_codes(encode(model, x))``).

    One compiled program per (query shape, ``quantize``): built op by op,
    the table costs a host dispatch per operation, which is most of a
    small call. On a TPU v5e its bits equal the op-by-op build's
    (``tests/test_lut_jit.py``); under an outer ``jit`` or ``grad`` it
    inlines.
    """
    luts = kops.pq_pairwise(rotate_split(model, jnp.atleast_2d(queries)),
                            model.codebooks, backend="ref")
    return quantize_luts(luts) if quantize else luts


def adc(model: QuantizerModel, codes: jax.Array, queries: jax.Array,
        *, backend: str = "auto") -> jax.Array:
    """(Q, D) × (N, M) → (Q, N) estimated squared distances."""
    return kops.adc_scan_batch(codes, build_lut(model, queries), backend=backend)


def distortion(model: QuantizerModel, x: jax.Array) -> jax.Array:
    """Mean squared reconstruction error (the vertex-oriented PQ objective)."""
    codes = encode(model, x)
    return jnp.mean(jnp.sum((x - decode(model, codes)) ** 2, axis=-1))


def identity_rotation(dim: int) -> jax.Array:
    return jnp.eye(dim, dtype=jnp.float32)
