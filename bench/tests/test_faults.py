"""A run with the timed path broken underneath comes out not correct: the
look for a chip skipped, the rest of a run driven as on the chip."""

import pytest

import faults
import harness
from repro.search import engine as eng


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("cell", ["sift128-hybrid-u8.batch1024",
                                  "deep96-hybrid-fs4.online16"])
def test_fault_is_not_correct(cell, fault, tiny, no_disk_cache, monkeypatch):
    monkeypatch.setattr(eng.HybridEngine, "search",
                        faults.FAULTS[fault](eng.HybridEngine.search))
    args = harness.parser().parse_args(
        ["--workload", cell, "--seed", "5", "--seconds", "1", "--trace", "0"])
    result, checks = harness.run(args, require_chip=False, overrides=tiny)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in checks.values())


@pytest.mark.parametrize("layout", ["u8", "fs4"])
def test_wrong_code_table_is_caught(layout):
    """A code table that encode, packing or row order got wrong reads
    rows wrong; the reference's own table reads none."""
    import jax
    import jax.numpy as jnp

    import reference

    m, k = (4, 16) if layout == "fs4" else (4, 32)
    kx, kc = jax.random.split(jax.random.PRNGKey(0))
    base = jax.random.normal(kx, (300, 16))
    cb = jax.random.normal(kc, (m, k, 4))
    r = jnp.eye(16)
    good = reference.encode(base, r, cb, layout)
    assert reference.code_rows_wrong(base, r, cb, good, layout) == 0.0
    for bad in (jnp.roll(good, 1, axis=0),          # rows shifted by one
                good[:, ::-1],                       # bytes or codes reversed
                good[:-1]):                          # a row left out
        assert reference.code_rows_wrong(base, r, cb, bad, layout) > 0.5
