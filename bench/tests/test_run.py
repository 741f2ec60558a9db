"""A whole run at a tiny size on the CPU (the look for a chip skipped):
the shape of the result line; the codebooks each configuration's training
gives; and the refusals of a run without a chip, without the program, or
with a training the harness does not know."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import harness
import profile_reduce

CELLS = ["sift128-hybrid-u8.batch1024", "deep96-hybrid-fs4.online16",
         "deep96-hybrid-fs4.batch1024", "sift128-rpq-u8.batch1024"]


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


@pytest.mark.parametrize("cell", ["sift128-hybrid-u8.batch1024",
                                  "deep96-hybrid-fs4.batch1024"])
def test_traced_line(cell, tiny, no_disk_cache, monkeypatch):
    """--trace 1: per-layer metrics, busy and window, the breakdown. On the
    CPU the operations run on the host's client thread."""
    monkeypatch.setattr(profile_reduce, "DEVICE_PLANE_PREFIX", "/host:CPU")
    monkeypatch.setattr(profile_reduce, "DEVICE_OP_LINE", "tf_XLA")
    result, _ = _run(cell, 1, tiny)
    _, _, _, _, _, layer = harness.find_cell(cell)
    # no Pallas kernel runs on the CPU: its roofline is left out
    want = {m["name"] for m in layer} - {"hop_adc_roofline",
                                         "hop_adc_fs_roofline"}
    assert set(result["metrics"]) == want
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    bd = result["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert list(result)[-1] == "checks"


def _step0(cell, tiny):
    """The cell's configuration, its build, and RPQ's step 0 made directly
    from the configuration's ``data_seed``, as the harness has always
    made k-means codebooks: ``init_rpq`` on the first rows, then
    ``encode``."""
    import jax
    from repro.core import RPQConfig
    from repro.core.trainer import init_rpq, to_model
    from repro.pq import base as pqbase
    from repro.pq import pack

    _, _, conf, traffic, _, _ = harness.find_cell(cell, tiny)
    engine, index, _, _ = harness.build(conf, traffic, {})
    del engine
    shape, gen, qz = conf["shape"], conf["generator"], conf["quantizer"]
    k_data, _, k_pq = jax.random.split(harness.seed_key(gen["data_seed"]), 3)
    make = harness.load_module(harness.BENCH / "data"
                               / f"{gen['module']}.py").make
    base, _ = make(k_data, shape["n_base"], traffic["pool"], gen)
    cfg = RPQConfig(dim=shape["dim"], m=qz["M"], k=qz["K"],
                    learn_rotation=False)
    model = to_model(cfg, init_rpq(k_pq, cfg, base[: qz["train_rows"]],
                                   kmeans_iters=qz["kmeans_iters"]))
    codes = pqbase.encode(model, base)
    if qz["layout"] == "fs4":
        codes = pack.pack_codes(codes)
    return conf, index, model, codes


@pytest.mark.parametrize("cell", ["sift128-hybrid-u8.batch1024",
                                  "deep96-hybrid-fs4.online16"])
def test_kmeans_build_is_step0_bit_for_bit(cell, tiny):
    """A configuration without ``training`` builds the codes, rotation and
    codebooks of a direct ``init_rpq`` and ``encode``, bit for bit."""
    conf, index, model, codes = _step0(cell, tiny)
    assert "training" not in conf["quantizer"]
    for got, want in ((index["r"], model.r),
                      (index["codebooks"], model.codebooks),
                      (index["codes"], codes)):
        got, want = np.asarray(got), np.asarray(want)
        assert got.dtype == want.dtype and np.array_equal(got, want)
    assert np.array_equal(np.asarray(model.r), np.eye(conf["shape"]["dim"]))


def test_rpq_build_serves_what_fit_learned(tiny):
    """The RPQ configuration serves ``fit``'s quantizer: a rotation that is
    not the identity, and codebooks moved from step 0."""
    conf, index, step0, _ = _step0("sift128-rpq-u8.batch1024", tiny)
    assert conf["quantizer"]["training"] == "rpq"
    r = np.asarray(index["r"])
    assert np.abs(r - np.eye(r.shape[0])).max() > 0
    np.testing.assert_allclose(r @ r.T, np.eye(r.shape[0]), atol=1e-5)
    cb, cb0 = np.asarray(index["codebooks"]), np.asarray(step0.codebooks)
    assert cb.shape == cb0.shape and not np.array_equal(cb, cb0)


def test_unknown_training_is_refused_before_the_corpus(tiny):
    _, _, conf, traffic, _, _ = harness.find_cell(
        "sift128-rpq-u8.batch1024", dict(tiny, quantizer={"training": "opq"}))
    phases = {}
    with pytest.raises(ValueError, match="training 'opq'"):
        harness.build(conf, traffic, phases)
    assert phases == {}


def test_overrides_reach_nested_groups(tiny):
    _, _, conf, _, _, _ = harness.find_cell("sift128-rpq-u8.batch1024", tiny)
    qz = conf["quantizer"]
    assert (qz["train_rows"], qz["kmeans_iters"]) == (1500, 4)
    fit = qz["fit"]
    assert fit["steps"] == 4 and fit["lr"] == 1e-3 and fit["k_neg"] == 30


def test_fit_steps_pass_through_and_record(tiny):
    """The recorder hands every step on unchanged: the quantizer trained
    under it is the one trained without it, bit for bit; it keeps the
    first steps' feeds and losses, and puts the step back when done."""
    import jax
    from repro.core import trainer

    _, _, conf, traffic, _, _ = harness.find_cell("sift128-rpq-u8.batch1024",
                                                  tiny)
    parts = harness.step0(conf, traffic, {})
    key = jax.random.fold_in(parts.k_pq, 1)
    make = trainer.make_train_step
    spy = harness.FitSteps()
    recorded = harness.train(parts, conf["quantizer"], key, spy)
    assert trainer.make_train_step is make
    plain = trainer.fit(key, parts.cfg,
                        trainer.TrainConfig(**conf["quantizer"]["fit"]),
                        parts.base, parts.graph, params=parts.params,
                        verbose=False)
    for a, b in zip(recorded.params, plain.params):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert len(spy.feeds) == len(spy.losses) == harness.FIT_STEPS_CHECKED
    assert spy.losses[0] == recorded.history[0]["total"]
    for a, b in zip(spy.params0, parts.params):
        assert np.array_equal(np.asarray(a), np.asarray(b))


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
