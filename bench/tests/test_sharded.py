"""The sharded cell: a whole run on four virtual CPU devices (in a
subprocess, ``sharded_cpu.py``; a test process keeps one device), with
its faults and control; and, in this process, the sharded reference, the
placement rule's inputs, and the refusals of configurations the harness
does not serve."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import harness
import reference

CELL = "sift128-sharded4-u8.batch1024"


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable,
                        str(harness.BENCH / "tests" / "sharded_cpu.py")],
                       cwd=harness.REPO, env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sharded_run_is_correct(four_devices):
    result = four_devices["run"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] == 4
    checks = result["checks"]
    assert set(checks) == {"code_rows_wrong", "id_mismatch_share",
                           "dist_rel_gap_max", "shards_misplaced"}
    assert checks["shards_misplaced"]["value"] == 0
    assert all(c["value"] <= c["limit"] for c in checks.values())
    _, _, _, _, e2e, _ = harness.find_cell(CELL)
    assert set(result["metrics"]) == {m["name"] for m in e2e}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_sharded_traced_line(four_devices):
    """On the CPU the four devices' operations share one host plane, and
    no Pallas kernel runs: the busy spread and the roofline are left
    out."""
    result = four_devices["traced"]
    assert result["correct"] is True
    _, _, _, _, _, layer = harness.find_cell(CELL)
    assert set(result["metrics"]) == {m["name"] for m in layer} - {
        "hop_adc_roofline", "device.busy_spread.sharded"}
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert result["breakdown"]["device_ops"]


@pytest.mark.parametrize("fault", ["one_shard_dropped", "altered"])
def test_sharded_fault_is_not_correct(four_devices, fault):
    result = four_devices["faults"][fault]
    assert result["correct"] is False
    over = {k for k, c in result["checks"].items() if c["value"] > c["limit"]}
    assert "id_mismatch_share" in over


def test_misplaced_code_table_is_caught(four_devices):
    got = four_devices["placements"]
    assert got["stacked"] == 0
    assert got["device0"] == 4 and got["everywhere"] == 4


def test_sharded_control_fails_and_program_passes(four_devices):
    out, = four_devices["control"]
    _, _, conf, _, _, _ = harness.find_cell(CELL)
    limits = conf["limits"]
    assert set(limits) <= set(out["program"])
    assert all(out["program"][k] <= v for k, v in limits.items())
    assert any(out["control"][k] > v for k, v in limits.items())
    assert [p["h"] for p in out["recall_h"]][:2] == [16, 24]


def _graph(n=200, r=8, dim=16, seed=0):
    rng = np.random.default_rng(seed)
    neighbors = rng.integers(0, n + 1, (n, r)).astype(np.int32)
    base = rng.standard_normal((n, dim)).astype(np.float32)
    cb = rng.standard_normal((4, 16, dim // 4)).astype(np.float32)
    r_id = np.eye(dim, dtype=np.float32)
    codes = np.asarray(reference.encode(base, r_id, cb, "u8"))
    q = rng.standard_normal((7, dim)).astype(np.float32)
    return neighbors, base, cb, r_id, codes, q


def test_sharded_answer_at_one_shard_is_the_one_graph_answer():
    neighbors, base, cb, r_id, codes, q = _graph()
    kw = dict(layout="u8", h=16, k=5, max_steps=64)
    one = {"neighbors": neighbors, "entry": np.int32(3), "codes": codes,
           "base": base, "r": r_id, "codebooks": cb}
    stacked = dict(one, neighbors=neighbors[None], medoids=np.array([3]),
                   codes=codes[None])
    ids1, d1 = reference.answer([q], one, **kw)
    ids, d = reference.answer_sharded([q], stacked, **kw)
    assert (ids1 < len(base)).all()
    assert np.array_equal(ids, ids1) and np.array_equal(d, d1)


def test_sharded_answer_merges_by_distance():
    """Two shards, each a whole graph over its rows: every merged answer is
    the k nearest of both shards' answers, by exact distance, in global
    ids of the right shard."""
    n = 201
    neighbors, base, cb, r_id, codes, q = _graph(n=n, seed=1)
    half = -(-n // 2)
    rng = np.random.default_rng(2)
    nb = np.full((2, half, 8), half, np.int32)
    for s, (lo, hi) in enumerate(reference.shard_bounds(n, 2)):
        nb[s, : hi - lo] = rng.integers(0, hi - lo, (hi - lo, 8))
    index = {"neighbors": nb, "medoids": np.array([0, 0]), "base": base,
             "codes": reference.stack_rows(codes, 2), "r": r_id,
             "codebooks": cb}
    ids, d = reference.answer_sharded([q], index, layout="u8", h=16, k=5,
                                      max_steps=64)
    assert ((ids >= 0) & (ids < n)).all()
    true = ((base[ids] - q[:, None, :]) ** 2).sum(-1)
    np.testing.assert_allclose(d, true, rtol=1e-5)
    assert (np.diff(d, axis=1) >= 0).all()


def test_stacked_rows_round_trip():
    t = np.arange(10 * 3).reshape(10, 3)
    st = reference.stack_rows(t, 3)
    assert st.shape == (3, 4, 3) and not st[2, 2:].any()
    assert np.array_equal(reference.global_rows(st, 10), t)
    assert reference.global_rows(st[:, :3], 10) is None


def test_failed_counts_sentinels_and_degraded_calls():
    ok = np.array([[0, 1], [2, 3]])
    rec = [harness.SimpleNamespace(ids=ok, degraded=False),
           harness.SimpleNamespace(ids=np.array([[0, -1], [9, 1]]),
                                   degraded=False),
           harness.SimpleNamespace(ids=ok, degraded=True)]
    assert harness.failed_queries(rec, 2, n_base=9) == 4


@pytest.mark.parametrize("index,quantizer,match", [
    ({"engine": "ivf"}, {}, "index.engine 'ivf'"),
    ({}, {"training": "rpq", "fit": {}}, "training 'rpq'")])
def test_unserved_configuration_is_refused_before_the_corpus(
        tiny, index, quantizer, match):
    _, _, conf, traffic, _, _ = harness.find_cell(
        CELL, dict(tiny, index=dict(tiny["index"], **index),
                   quantizer=dict(tiny["quantizer"], **quantizer)))
    phases = {}
    with pytest.raises(ValueError, match=match):
        harness.build(conf, traffic, phases)
    assert phases == {}


def test_shards_must_equal_the_cells_chips(tiny):
    with pytest.raises(ValueError, match="index.shards 2"):
        harness.find_cell(CELL, dict(tiny, index={"shards": 2}))
