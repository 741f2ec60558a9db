"""The per-call LUT build as one compiled program (``pq.base.build_lut``).

The tables route the beam, and a last-bit difference between two near-tied
PQ distances forks it, so the compiled build is held to the op-by-op build
(the same function under ``jax.disable_jit()``):

* on a TPU v5e, bit for bit at the benchmark cells' shapes (the test at the
  end, skipped without a TPU);
* on the CPU, as far as that backend allows. XLA's CPU backend contracts
  a product and a sum into one fused multiply-add when they are compiled
  together (``|q_j|^2``, ``|c|^2`` over 2-dimensional sub-vectors) and
  blocks a small matmul differently, so float parts move by a few float32
  ulps of the table's magnitude; the u8 layout's table (8-dimensional
  sub-vectors) stays bit-equal.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.pq import base
from repro.pq.base import QuantizerModel
from repro.pq.pack import quantize_luts

EPS = float(np.finfo(np.float32).eps)

# (queries, dim, M, K, quantize): the online fs4 cell, the batch u8 cell
ONLINE_FS4 = (16, 96, 48, 16, True)
BATCH_U8 = (1024, 128, 16, 256, False)


def _op_by_op(model, queries, quantize):
    """The build as it ran before it was compiled: one dispatch per op."""
    luts = kref.pq_pairwise_ref(base.rotate_split(model, queries),
                                model.codebooks)
    return quantize_luts(luts) if quantize else luts


def _problem(seed, q, d, m, k, *, rotation="random"):
    """Unit-norm queries and a quantizer whose codewords are rotated
    sub-vectors of data rows, so tables hold realistic near-ties."""
    kr, kx, kq = jax.random.split(jax.random.PRNGKey(seed), 3)
    r = (jnp.linalg.qr(jax.random.normal(kr, (d, d)))[0]
         if rotation == "random" else jnp.eye(d))
    r = r.astype(jnp.float32)
    rows = jax.random.normal(kx, (k, d), jnp.float32)
    cb = base.rotate_split(QuantizerModel(r, jnp.zeros((m, k, d // m))), rows)
    queries = jax.random.normal(kq, (q, d), jnp.float32)
    queries = queries / jnp.linalg.norm(queries, axis=1, keepdims=True)
    return QuantizerModel(r, cb.transpose(1, 0, 2)), queries


def _leaves(tables):
    return [np.asarray(t) for t in jax.tree.leaves(tables)]


def _table_scale(model, queries):
    """Per entry, (|q| + |c|)^2: it bounds |q_j - c|^2, and the rotation
    rounds each component relative to the whole query's norm, so it is the
    magnitude the table's float32 rounding is relative to."""
    qn = np.linalg.norm(np.asarray(queries, np.float64), axis=1)
    cn = np.linalg.norm(np.asarray(model.codebooks, np.float64), axis=-1)
    return (qn[:, None, None] + cn[None]) ** 2


@pytest.mark.parametrize("quantize", [False, True])
def test_compiles_once_per_shape_and_layout(quantize):
    model, queries = _problem(0, 8, 32, 16, 16)
    base.build_lut.clear_cache()
    for seed in range(3):
        fresh = jax.random.normal(jax.random.PRNGKey(seed), queries.shape)
        jax.block_until_ready(base.build_lut(model, fresh, quantize=quantize))
    assert base.build_lut._cache_size() == 1
    base.build_lut(model, queries[:4], quantize=quantize)
    assert base.build_lut._cache_size() == 2


def test_single_query_is_promoted():
    model, queries = _problem(1, 1, 32, 16, 16)
    one = base.build_lut(model, queries[0])
    assert one.shape == (1, 16, 16)
    np.testing.assert_array_equal(one, base.build_lut(model, queries))


@pytest.mark.parametrize("rotation", ["identity", "random"])
@pytest.mark.parametrize("q,d,m,k", [BATCH_U8[:4], (16, 128, 16, 256)])
def test_u8_table_bit_equal_on_cpu(q, d, m, k, rotation):
    model, queries = _problem(2, q, d, m, k, rotation=rotation)
    with jax.disable_jit():
        want = _op_by_op(model, queries, False)
    np.testing.assert_array_equal(base.build_lut(model, queries), want)


@pytest.mark.parametrize("rotation", ["identity", "random"])
@pytest.mark.parametrize("seed", [3, 4])
def test_fs4_tables_match_on_cpu(seed, rotation):
    """Float parts within 8 float32 ulps of the table's magnitude (the
    fused multiply-add and the matmul's blocking, module docstring; 60
    draws read at most 1.42); scale and bias follow from the table's
    extremes. A uint8 entry may move one step where its value lies within
    that rounding of a step's midpoint (none did in those draws)."""
    q, d, m, k, _ = ONLINE_FS4
    model, queries = _problem(seed, q, d, m, k, rotation=rotation)
    with jax.disable_jit():
        want_f = np.asarray(_op_by_op(model, queries, False))
        want = _op_by_op(model, queries, True)
    got_f = np.asarray(base.build_lut(model, queries))
    got = base.build_lut(model, queries, quantize=True)

    atol = 8 * EPS * _table_scale(model, queries)
    assert np.all(np.abs(got_f - want_f) <= atol)
    per_query = atol.reshape(q, -1).max(axis=1)
    np.testing.assert_array_less(np.abs(np.asarray(got.bias - want.bias)),
                                 per_query + 1e-30)
    np.testing.assert_array_less(np.abs(np.asarray(got.scale - want.scale)),
                                 2 * per_query / 255 + 1e-30)
    step = np.abs(got.lut.astype(np.int32) - want.lut.astype(np.int32))
    assert step.max() <= 1
    assert step.sum() <= max(1, step.size // 1000)


@pytest.mark.parametrize("quantize", [False, True])
def test_under_outer_jit_as_before(quantize):
    model, queries = _problem(5, 8, 32, 16, 16)
    nested = jax.jit(lambda mo, qq: base.build_lut(mo, qq, quantize=quantize))
    before = jax.jit(lambda mo, qq: _op_by_op(mo, qq, quantize))
    for got, want in zip(_leaves(nested(model, queries)),
                         _leaves(before(model, queries))):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("wrt", ["codebooks", "rotation"])
def test_under_grad_as_before(wrt):
    """Differentiable through the compiled build, with the gradient the
    op-by-op build had."""
    model, queries = _problem(6, 8, 32, 8, 16)

    def loss(build, x):
        mo = (model._replace(codebooks=x) if wrt == "codebooks"
              else model._replace(r=x))
        luts = build(mo, queries)
        return jnp.sum(luts * jnp.linspace(0.5, 1.5, luts.size)
                       .reshape(luts.shape))

    x = model.codebooks if wrt == "codebooks" else model.r
    got = np.asarray(jax.grad(lambda x: loss(base.build_lut, x))(x))
    want = np.asarray(jax.grad(
        lambda x: loss(lambda mo, qq: _op_by_op(mo, qq, False), x))(x))
    # the backward pass is compiled as one program too, so on the CPU its
    # sums round as the module docstring says (20 draws: at most 1.97 ulps
    # of the largest entry)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=8 * EPS * np.abs(want).max())


@pytest.fixture(scope="module")
def tpu():
    if jax.devices()[0].platform != "tpu":
        pytest.skip("no TPU attached: the bit-equality holds on the chip")


@pytest.mark.parametrize("rotation", ["identity", "random"])
@pytest.mark.parametrize("cell", [ONLINE_FS4, BATCH_U8],
                         ids=["online16-fs4", "batch1024-u8"])
def test_bit_equal_on_tpu(tpu, cell, rotation):
    """At the benchmark cells' shapes the compiled tables, scale and bias
    are the op-by-op build's bits, under ``disable_jit`` and as plain
    eager dispatch alike."""
    q, d, m, k, quantize = cell
    for seed in range(8):
        model, queries = _problem(100 + seed, q, d, m, k, rotation=rotation)
        got = _leaves(base.build_lut(model, queries, quantize=quantize))
        eager = _leaves(_op_by_op(model, queries, quantize))
        with jax.disable_jit():
            no_jit = _leaves(_op_by_op(model, queries, quantize))
        for g, e, n in zip(got, eager, no_jit):
            np.testing.assert_array_equal(g, n)
            np.testing.assert_array_equal(g, e)
