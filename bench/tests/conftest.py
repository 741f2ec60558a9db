"""Tests of the benchmark itself (CPU; not in the repository's tier-1
``testpaths``): ``python -m pytest bench/tests`` from the repository root."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import pytest  # noqa: E402

# A cell cut to what a CPU test can hold: every code path of a run, tiny.
TINY = {"shape": {"n_base": 3000},
        "quantizer": {"train_rows": 1500, "kmeans_iters": 4},
        "index": {"R": 16, "L": 24},
        "traffic": {"pool": 300, "check_queries": 64, "trace_seconds": 1},
        "conf": {"operating_h": 24}}


@pytest.fixture
def tiny():
    import copy
    return copy.deepcopy(TINY)


@pytest.fixture
def no_disk_cache(monkeypatch):
    """Keep the runs' persistent compilation cache off, and off disk."""
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "enable", lambda: "off in tests")
