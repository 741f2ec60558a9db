"""A whole run at a tiny size on the CPU (the look for a chip skipped):
the shape of the result line; and the refusals of a run without a chip or
without the program."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import harness
import profile_reduce

CELLS = ["sift128-hybrid-u8.batch1024", "deep96-hybrid-fs4.online16"]


def _run(cell, trace, tiny):
    args = harness.parser().parse_args(
        ["--workload", cell, "--seed", str(2**31 + 11), "--seconds", "1.5",
         "--trace", str(trace)])
    return harness.run(args, require_chip=False, overrides=tiny)


@pytest.mark.parametrize("cell", CELLS)
def test_result_line(cell, tiny, no_disk_cache):
    result, checks = _run(cell, 0, tiny)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["correct"] is True and result["failed"] == 0
    _, _, _, _, e2e, _ = harness.find_cell(cell)
    assert set(result["metrics"]) == {m["name"] for m in e2e}
    for m in e2e:
        got = result["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert all(c["value"] <= c["limit"] for c in checks.values())
    json.dumps(result)


def test_traced_line(tiny, no_disk_cache, monkeypatch):
    """--trace 1: per-layer metrics, busy and window, the breakdown. On the
    CPU the operations run on the host's client thread."""
    monkeypatch.setattr(profile_reduce, "DEVICE_PLANE_PREFIX", "/host:CPU")
    monkeypatch.setattr(profile_reduce, "DEVICE_OP_LINE", "tf_XLA")
    cell = "sift128-hybrid-u8.batch1024"
    result, _ = _run(cell, 1, tiny)
    _, _, _, _, _, layer = harness.find_cell(cell)
    # no Pallas kernel runs on the CPU: its roofline is left out
    want = {m["name"] for m in layer} - {"hop_adc_roofline"}
    assert set(result["metrics"]) == want
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    bd = result["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert list(result)[-1] == "checks"


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    p = _cli(harness.REPO)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(harness.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
