"""Each corpus generator is deterministic for a seed."""

import numpy as np
import pytest

from harness import BENCH, find_cell, load_module, seed_key

CELLS = ["sift128-hybrid-u8.batch1024", "deep96-hybrid-fs4.online16"]


def _make(cell, seed, n=500, nq=50):
    _, _, conf, _, _, _ = find_cell(cell)
    gen = conf["generator"]
    make = load_module(BENCH / "data" / f"{gen['module']}.py").make
    base, q = make(seed_key(seed), n, nq, gen)
    return np.asarray(base), np.asarray(q), conf


@pytest.mark.parametrize("cell", CELLS)
def test_same_seed_same_corpus(cell):
    a = _make(cell, 2**33 + 7)
    b = _make(cell, 2**33 + 7)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


@pytest.mark.parametrize("cell", CELLS)
def test_other_seed_other_corpus(cell):
    a, b = _make(cell, 1), _make(cell, 2)
    assert not np.array_equal(a[0], b[0])


@pytest.mark.parametrize("cell", CELLS)
def test_shape_and_held_out_queries(cell):
    base, q, conf = _make(cell, 3)
    d = conf["shape"]["dim"]
    assert base.shape == (500, d) and q.shape == (50, d)
    assert base.dtype == np.float32
    # queries are drawn apart from the base: none is a base row
    d2 = ((q[:, None, :] - base[None]) ** 2).sum(-1)
    assert d2.min() > 0


def test_deep_rows_have_unit_norm():
    base, q, _ = _make("deep96-hybrid-fs4.online16", 4)
    np.testing.assert_allclose(np.linalg.norm(base, axis=1), 1.0, rtol=1e-5)
