"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracles,
swept across shapes and dtypes per the deliverable spec."""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels import ref


SHAPES = [
    # (N, M, K, dsub)
    (17, 4, 16, 4),
    (256, 8, 256, 16),
    (1000, 8, 64, 8),
    (2049, 16, 256, 8),
]
CODE_DTYPES = [np.uint8, np.int32]
LUT_DTYPES = [np.float32]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("cdt", CODE_DTYPES)
def test_adc_scan_matches_ref(shape, cdt, rng):
    n, m, k, _ = shape
    if k > np.iinfo(cdt).max + 1:
        pytest.skip("code dtype too narrow")
    codes = rng.integers(0, k, (n, m)).astype(cdt)
    lut = rng.normal(size=(m, k)).astype(np.float32)
    want = ref.adc_scan_ref(codes, lut)
    got = ops.adc_scan(codes, lut, backend="interpret", block_n=256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES[:3])
@pytest.mark.parametrize("q", [1, 3, 8])
def test_adc_scan_batch_matches_ref(shape, q, rng):
    n, m, k, _ = shape
    codes = rng.integers(0, k, (n, m)).astype(np.uint8)
    luts = rng.normal(size=(q, m, k)).astype(np.float32)
    want = ref.adc_scan_batch_ref(codes, luts)
    got = ops.adc_scan_batch(codes, luts, backend="interpret",
                             block_n=128, block_q=4)
    # MXU path casts the LUT to bf16 (DESIGN.md): ~0.5% relative tolerance.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2 * m)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("xdt", [np.float32])
def test_pq_pairwise_matches_ref(shape, xdt, rng):
    n, m, k, dsub = shape
    x = rng.normal(size=(n, m, dsub)).astype(xdt)
    cb = rng.normal(size=(m, k, dsub)).astype(np.float32)
    want = ref.pq_pairwise_ref(x, cb)
    got = ops.pq_pairwise(x, cb, backend="interpret", block_n=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_batch_consistent_with_single(rng):
    n, m, k = 333, 8, 32
    codes = rng.integers(0, k, (n, m)).astype(np.uint8)
    luts = rng.normal(size=(4, m, k)).astype(np.float32)
    batch = ref.adc_scan_batch_ref(codes, luts)
    for i in range(4):
        single = ref.adc_scan_ref(codes, luts[i])
        np.testing.assert_allclose(np.asarray(batch[i]), np.asarray(single),
                                   rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("q,r,m,k", [(5, 8, 4, 16), (16, 32, 8, 256),
                                     (33, 24, 16, 64)])
def test_hop_gather_matches_ref(q, r, m, k, rng):
    codes = rng.integers(0, k, (q, r, m)).astype(np.uint8)
    luts = rng.normal(size=(q, m, k)).astype(np.float32)
    want = ref.hop_gather_ref(codes, luts)
    got = ops.hop_gather(codes, luts, backend="interpret", block_q=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("n,q,r,m,k", [(100, 5, 8, 4, 16),
                                       (257, 16, 32, 8, 256),
                                       (64, 33, 24, 16, 64)])
def test_hop_adc_matches_ref(n, q, r, m, k, rng):
    """Fused gather+reduce kernel (interpret mode) vs the jnp oracle."""
    codes = rng.integers(0, k, (n, m)).astype(np.uint8)
    ids = rng.integers(0, n, (q, r)).astype(np.int32)
    luts = rng.normal(size=(q, m, k)).astype(np.float32)
    want = ref.hop_adc_ref(codes, ids, luts)
    got = ops.hop_adc(codes, ids, luts, backend="interpret", block_q=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-5)


FS_SHAPES = [
    # (N, M, Q, R)
    (100, 4, 3, 8),
    (257, 16, 5, 32),
    (64, 5, 9, 24),    # odd M: last byte's high nibble is padding
    (33, 1, 2, 6),
]


def _fs_inputs(rng, n, m, q):
    from repro.pq import pack

    codes = rng.integers(0, 16, (n, m)).astype(np.uint8)
    packed = pack.pack_codes(jnp.asarray(codes))
    luts = rng.normal(size=(q, m, 16)).astype(np.float32) ** 2
    ql = pack.quantize_luts(jnp.asarray(luts))
    return codes, packed, ql


@pytest.mark.parametrize("shape", FS_SHAPES)
def test_adc_scan_fs_matches_ref_bitexact(shape, rng):
    """Fast-scan bulk kernel (interpret mode) vs the jnp oracle must be
    BIT-exact: integer accumulation + one shared dequant expression."""
    n, m, q, _ = shape
    _, packed, ql = _fs_inputs(rng, n, m, q)
    want = ref.adc_scan_fs_ref(packed, ql.lut, ql.scale, ql.bias)
    got = ops.adc_scan_fs(packed, ql.lut, ql.scale, ql.bias,
                          backend="interpret", block_n=64, block_q=4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", FS_SHAPES)
def test_hop_adc_fs_matches_ref_bitexact(shape, rng):
    """Packed fused gather+reduce kernel (interpret mode) vs its oracle."""
    n, m, q, r = shape
    _, packed, ql = _fs_inputs(rng, n, m, q)
    ids = rng.integers(0, n, (q, r)).astype(np.int32)
    want = ref.hop_adc_fs_ref(packed, jnp.asarray(ids), ql.lut, ql.scale,
                              ql.bias)
    got = ops.hop_adc_fs(packed, ids, ql.lut, ql.scale, ql.bias,
                         backend="interpret", block_q=4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_adc_scan_fs_consistent_with_unpacked_scan(rng):
    """fs4 accumulation == scanning the UNPACKED codes against the uint8
    LUT cast to f32, then the same affine — ties the packed path to the
    classic scan's semantics exactly (all-integer, so equality is exact)."""
    n, m, q = 120, 8, 4
    codes, packed, ql = _fs_inputs(rng, n, m, q)
    fs = np.asarray(ops.adc_scan_fs(packed, ql.lut, ql.scale, ql.bias,
                                    backend="ref"))
    acc = np.asarray(ref.adc_scan_batch_ref(
        jnp.asarray(codes), ql.lut.astype(jnp.float32)))
    want = (np.asarray(ql.scale)[:, None] * acc
            + m * np.asarray(ql.bias)[:, None])
    np.testing.assert_allclose(fs, want, rtol=1e-6, atol=1e-5)


def test_hop_adc_fs_duplicate_and_boundary_ids(rng):
    """Duplicate ids in one hop and rows 0 / N-1 must all resolve."""
    n, m, q = 50, 4, 1
    _, packed, ql = _fs_inputs(rng, n, m, q)
    ids = np.array([[0, 0, n - 1, n - 1, 7, 7, 7, 0]], np.int32)
    got = np.asarray(ops.hop_adc_fs(packed, ids, ql.lut, ql.scale, ql.bias,
                                    backend="interpret"))
    want = np.asarray(ref.hop_adc_fs_ref(packed, jnp.asarray(ids), ql.lut,
                                         ql.scale, ql.bias))
    np.testing.assert_array_equal(got, want)
    assert got[0, 0] == got[0, 1] == got[0, 7]


def test_ops_accept_any_int_dtype(rng):
    """The dispatch boundary canonicalizes code dtypes: uint8 and int32
    callers get identical answers from every op (the one-cast rule)."""
    n, m, k, q, r = 80, 4, 16, 3, 8
    codes = rng.integers(0, k, (n, m))
    lut = rng.normal(size=(m, k)).astype(np.float32)
    luts = rng.normal(size=(q, m, k)).astype(np.float32)
    ids = rng.integers(0, n, (q, r))
    for a, b in [(np.uint8, np.int32), (np.int32, np.uint8)]:
        s1 = ops.adc_scan(codes.astype(a), lut, backend="ref")
        s2 = ops.adc_scan(codes.astype(b), lut, backend="ref")
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        h1 = ops.hop_adc(codes.astype(a), ids.astype(a), luts, backend="ref")
        h2 = ops.hop_adc(codes.astype(b), ids.astype(b), luts, backend="ref")
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))


def test_hop_adc_consistent_with_hop_gather(rng):
    """Fused kernel == pre-gather + hop_gather (the op it replaces)."""
    n, q, r, m, k = 120, 7, 16, 8, 32
    codes = rng.integers(0, k, (n, m)).astype(np.uint8)
    ids = rng.integers(0, n, (q, r)).astype(np.int32)
    luts = rng.normal(size=(q, m, k)).astype(np.float32)
    fused = ops.hop_adc(codes, ids, luts, backend="interpret", block_q=2)
    unfused = ops.hop_gather(codes[ids], luts, backend="ref")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               rtol=1e-6, atol=1e-5)


def test_hop_adc_duplicate_and_boundary_ids(rng):
    """Duplicate ids in one hop and rows 0 / N-1 must all resolve."""
    n, m, k = 50, 4, 16
    codes = rng.integers(0, k, (n, m)).astype(np.uint8)
    ids = np.array([[0, 0, n - 1, n - 1, 7, 7, 7, 0]], np.int32)
    luts = rng.normal(size=(1, m, k)).astype(np.float32)
    got = np.asarray(ops.hop_adc(codes, ids, luts, backend="interpret"))
    want = np.asarray(ref.hop_adc_ref(codes, ids, luts))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)
    assert got[0, 0] == got[0, 1] == got[0, 7]


def test_default_interpret_off_tpu(rng):
    """Interpret mode is never a default: off-TPU only backend="interpret"
    runs the Pallas interpreter, and backend="pallas" raises instead of
    quietly interpreting in place of the device kernel."""
    import jax
    assert jax.default_backend() != "tpu"  # CPU test host
    codes = rng.integers(0, 16, (40, 4)).astype(np.uint8)
    luts = rng.normal(size=(2, 4, 16)).astype(np.float32)
    ids = rng.integers(0, 40, (2, 8)).astype(np.int32)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        ops.hop_adc(codes, ids, luts, backend="pallas")
    got = ops.hop_adc(codes, ids, luts, backend="interpret")
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.hop_adc_ref(codes, ids, luts)))


def test_hop_gather_consistent_with_adc_scan(rng):
    """hop_gather on one query's R codes == adc_scan of those codes."""
    r, m, k = 16, 8, 32
    codes = rng.integers(0, k, (r, m)).astype(np.uint8)
    lut = rng.normal(size=(m, k)).astype(np.float32)
    a = ref.adc_scan_ref(codes, lut)
    b = ref.hop_gather_ref(codes[None], lut[None])[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_kmeans_assign_matches_ref(rng):
    x = rng.normal(size=(500, 24)).astype(np.float32)
    c = rng.normal(size=(32, 24)).astype(np.float32)
    ia, da = ops.kmeans_assign(x, c, backend="ref")
    ib, db = ref.kmeans_assign_ref(x, c)
    assert (np.asarray(ia) == np.asarray(ib)).all()
    np.testing.assert_allclose(np.asarray(da), np.asarray(db), rtol=1e-5, atol=1e-4)


def test_adc_equals_decode_distance(rng):
    """ADC(q, codes) == ||q − decode(codes)||² — the LUT identity."""
    from repro.pq import base, train_pq
    import jax

    x = jnp.asarray(rng.normal(size=(800, 32)).astype(np.float32))
    model = train_pq(jax.random.PRNGKey(0), x, 4, 16, iters=5)
    codes = base.encode(model, x)
    q = x[:6]
    adc = base.adc(model, codes, q, backend="ref")
    dec = base.decode(model, codes)
    exact = jnp.sum((q[:, None, :] - dec[None, :, :]) ** 2, -1)
    np.testing.assert_allclose(np.asarray(adc), np.asarray(exact),
                               rtol=1e-3, atol=1e-2)
