"""The benchmark run: one cell of ``BENCHMARK.json``, found by name.

A cell names a configuration (``configs/<config>.json``: corpus shape,
generator, index, quantizer, operating h and the limits of the
comparison) and a traffic mix (``traffic/<mix>.json``: loop, batch, h, k,
expand, entries, pool). Per-layer metrics are read by
``metrics/<metric>.py`` and kernel costs come from ``costs/<kernel>.py``.
Adding a cell, configuration, mix or metric adds files and entries; this
file does not change.

A run:

1. set-up, each phase timed on standard error: the corpus and the held-out
   query pool on the device, from the configuration's ``data_seed``; their exact kNN (the
   benchmark's own brute force); the program's Vamana graph; PQ codebooks
   by k-means (RPQ's step-0 initialisation) on the first half of the base;
   the code table; the ``HybridEngine`` as ``launch/serve.py`` builds it;
   two warm-up calls at the cell's shape;
2. the window: a closed loop of ``engine.search`` calls, each with the
   next ``batch`` queries of the pool in an order drawn from ``--seed``,
   until ``--seconds`` have passed;
   with ``--trace 1`` the profiler records its first ``trace_seconds``;
3. after the window: the device's peak memory; then the served code table
   is held to the codebooks, and a sample of the answered calls, drawn
   from the seed, to ``reference.py``.

The last line of standard output is the result; the numbers compared,
each beside its limit, are the last lines of standard error.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
_T_START = time.perf_counter()


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(name: str, overrides: dict | None = None):
    """(workload, configuration entry, config file, traffic file, end-to-end
    and per-layer metric entries of this cell) for the cell ``name``.
    ``overrides`` updates parts of the configuration ("shape", "index",
    "quantizer", "generator", "conf" itself) or the traffic ("traffic"):
    the tests cut a cell to a size the CPU can hold."""
    bm = load_json(REPO / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf_entry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    conf = load_json(REPO / conf_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    for part, values in (overrides or {}).items():
        target = {"conf": conf, "traffic": traffic}.get(part) or conf[part]
        target.update(values)

    def reports(metric):
        return name in metric.get("workloads", [name])

    e2e = [m for m in bm["end_to_end"] if reports(m)]
    layer = [m for m in bm["per_layer"] if reports(m)]
    return cell, conf_entry, conf, traffic, e2e, layer


def seed_key(seed: int):
    """A JAX key for any whole-number seed, including seeds wider than 32
    bits."""
    import jax
    key = jax.random.PRNGKey(seed % (2 ** 31))
    return jax.random.fold_in(key, seed // (2 ** 31) % (2 ** 31))


def check_chip(chips: int) -> None:
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise NoChip(f"no TPU found (JAX default backend is {backend!r})")
    if len(jax.devices()) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(jax.devices())}")


def _timed(phases: dict, label: str, fn, *args, **kw):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    phases[label] = time.perf_counter() - t0
    log(f"setup {label}: {phases[label]:.3f} s")
    return out


def build(conf: dict, traffic: dict, phases: dict):
    """Corpus, ground truth and the program's index and engine. All of it
    comes from the configuration's ``data_seed``: every run serves the same
    deployment, and ``--seed`` changes only the order of the queries and
    the answers sampled for the check."""
    import jax
    from repro.core import RPQConfig
    from repro.core.trainer import init_rpq, to_model
    from repro.graphs.vamana import build_vamana
    from repro.pq import base as pqbase
    from repro.pq import pack
    from repro.search.engine import HybridEngine

    import reference

    shape, gen, idx, qz = (conf["shape"], conf["generator"], conf["index"],
                           conf["quantizer"])
    key = seed_key(gen["data_seed"])
    k_data, k_graph, k_pq = jax.random.split(key, 3)
    make = load_module(BENCH / "data" / f"{gen['module']}.py").make
    base, pool = _timed(phases, "corpus", make, k_data, shape["n_base"],
                        traffic["pool"], gen)
    gt, _ = _timed(phases, "ground_truth", reference.exact_knn, base, pool,
                   traffic["k"])
    graph = _timed(phases, "graph", build_vamana, k_graph, base,
                   r=idx["R"], l=idx["L"], alpha=idx["alpha"],
                   passes=idx["passes"])
    cfg = RPQConfig(dim=shape["dim"], m=qz["M"], k=qz["K"],
                    learn_rotation=False)
    train = base[: qz["train_rows"]]
    params = _timed(phases, "codebooks", init_rpq, k_pq, cfg, train,
                    kmeans_iters=qz["kmeans_iters"])
    model = to_model(cfg, params)
    codes = _timed(phases, "encode", pqbase.encode, model, base)
    fs4 = qz["layout"] == "fs4"
    if fs4:
        codes = pack.pack_codes(codes)
        lut_fn = lambda q: pqbase.build_lut(model, q, quantize=True)  # noqa: E731
    else:
        lut_fn = lambda q: pqbase.build_lut(model, q)  # noqa: E731
    engine = HybridEngine(graph, codes, lut_fn, vectors=base)
    index = {"neighbors": graph.neighbors, "entry": graph.medoid,
             "r": model.r, "codebooks": model.codebooks, "codes": codes,
             "base": base}
    return engine, index, np.asarray(pool), np.asarray(gt)


def search_kwargs(conf: dict, traffic: dict) -> dict:
    h = conf["operating_h"] if traffic["h"] == "config" else traffic["h"]
    return {"k": traffic["k"], "h": int(h), "expand": traffic["expand"],
            "entries": traffic["entries"], "max_steps": traffic["max_steps"]}


def pool_order(seed: int, pool_size: int) -> np.ndarray:
    """The order, drawn from the seed, in which the calls cycle the pool."""
    return np.random.default_rng(seed).permutation(pool_size)


def batch_rows(i: int, batch: int, order: np.ndarray) -> np.ndarray:
    """Pool rows of the i-th call: the next ``batch`` rows of ``order``,
    cycling."""
    return order[(i * batch + np.arange(batch)) % order.shape[0]]


def closed_loop(engine, pool, order, traffic, skw, seconds, trace_dir=None,
                compiles=None):
    """One call in flight at a time until ``seconds`` have passed. Returns
    the per-call records and, when tracing, how many calls it covered.
    Each call's answers and counters come to the host as it finishes, and
    the garbage collector waits until the window has closed."""
    import jax
    import jax.numpy as jnp

    batch, records = traffic["batch"], []
    traced = None
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        if trace_dir:
            # no Python function tracing: it would slow the host it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_begin = time.perf_counter()
        if compiles is not None:
            compiles["window"] = compiles["count"]
        i = 0
        while True:
            rows = batch_rows(i, batch, order)
            q = pool[rows]
            with jax.profiler.TraceAnnotation("bench.search"):
                ts = time.perf_counter()
                res = engine.search(jnp.asarray(q), **skw)
            with jax.profiler.TraceAnnotation("bench.fetch"):
                ids = np.asarray(res.ids)
                dists = np.asarray(res.dists)
                n_dist = np.asarray(res.n_dist)
                rounds = np.asarray(res.rounds)
                te = time.perf_counter()
            records.append(SimpleNamespace(rows=rows, t0=ts, t1=te, ids=ids,
                                           dists=dists, n_dist=n_dist,
                                           rounds=rounds))
            i += 1
            if trace_dir and traced is None and (
                    te - t_begin >= traffic["trace_seconds"]
                    or te - t_begin >= seconds):
                jax.profiler.stop_trace()
                traced = len(records)
            if te - t_begin >= seconds:
                break
    finally:
        gc.enable()
        gc.unfreeze()
    if compiles is not None:
        compiles["window"] = compiles["count"] - compiles["window"]
    return records, t_begin, traced


def recall(records, gt, k: int) -> float:
    hits = total = 0
    for r in records:
        g = gt[r.rows, :k]
        hits += int((r.ids[:, :k, None] == g[:, None, :]).any(-1).sum())
        total += g.size
    return hits / total


def memory_peak() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def sample_calls(records, traffic, seed) -> np.ndarray:
    """The calls whose answers are compared: ``check_queries`` queries'
    worth, drawn from the seed."""
    rng = np.random.default_rng(seed)
    need = max(1, math.ceil(traffic["check_queries"] / traffic["batch"]))
    return rng.choice(len(records), size=min(need, len(records)),
                      replace=False)


def check(records, index, pool, conf, traffic, skw, seed):
    """The numbers that decide ``correct``: the served code table held to
    the codebooks, and a sample of the answered calls, drawn from the
    seed, against ``reference.answer``."""
    import jax.numpy as jnp

    import reference

    wrong = reference.code_rows_wrong(index["base"], index["r"],
                                      index["codebooks"], index["codes"],
                                      conf["quantizer"]["layout"])
    pick = sample_calls(records, traffic, seed)
    rows = np.concatenate([records[i].rows for i in pick])
    ids = np.concatenate([records[i].ids for i in pick])
    dists = np.concatenate([records[i].dists for i in pick])
    q = pool[rows]
    ref_ids, ref_d = reference.answer(
        [pool[records[i].rows] for i in pick], index,
        layout=conf["quantizer"]["layout"], h=skw["h"], k=skw["k"],
        max_steps=skw["max_steps"])
    true_d = np.asarray(reference.returned_dists(
        jnp.asarray(q), jnp.asarray(ids), index["base"]))
    nums = reference.compare(ids, dists, ref_ids, ref_d, true_d)
    return {"code_rows_wrong": wrong, **nums}, len(rows)


def reduce_trace(trace_dir: str, records, traced: int):
    import profile_reduce as pr

    devices, host, summary = pr.load(pr.find_xplane(trace_dir))
    for plane, lines in summary:
        log(f"trace plane {plane}: lines {lines}")
    spans = [h for h in host if h[0] in ("bench.search", "bench.fetch")]
    if not devices or not spans:
        raise RuntimeError("the trace holds no device operations or no "
                           "benchmark spans")
    spans.sort(key=lambda h: h[1])
    t0 = spans[0][1]
    t1 = max(s + d for _, s, d in spans[: 2 * traced])
    per_dev = {p: pr.clip(ev, t0, t1) for p, ev in devices.items()}
    busy = [pr.busy_ns(ev) for ev in per_dev.values()]
    first = per_dev[sorted(per_dev)[0]]
    return SimpleNamespace(
        events=first, window_s=(t1 - t0) / 1e9,
        busy_s=float(np.mean(busy)) / 1e9,
        device_ops=pr.top_ops(first), idle_gaps=pr.idle_gaps(first, host,
                                                            t0, t1))


def layer_context(records, trace, conf, traffic, peaks, kernels):
    """What a per-layer reader may read (``metrics/<metric>.py``)."""
    import profile_reduce as pr

    qz, idx = conf["quantizer"], conf["index"]
    shape = {"q": traffic["batch"], "r": traffic["expand"] * idx["R"],
             "m": qz["M"], "k": qz["K"]}

    def kernel(name):
        cost = load_module(BENCH / "costs" / f"{name}.py")
        events = (pr.matching(trace.events, cost.TRACE_NAMES)
                  if name in kernels else [])
        return SimpleNamespace(
            events=events, bytes=cost.bytes_per_call(**shape),
            ops=cost.ops_per_call(**shape))

    return SimpleNamespace(records=records, trace=trace, peaks=peaks,
                           kernel=kernel)


def run(args, *, require_chip: bool = True, overrides: dict | None = None):
    """One run; returns (result dict, compared numbers with limits)."""
    import jax

    cell, _, conf, traffic, e2e, layer = find_cell(args.workload, overrides)
    if require_chip:
        check_chip(cell["chips"])
    from repro.kernels import ops
    from repro.launch import compile_cache

    cache = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = {"count": 0}

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["count"] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    dev = jax.devices()[0]
    log(f"cell {cell['name']}: device {dev.platform} {dev.device_kind} "
        f"x{len(jax.devices())}; jax {jax.__version__}; cache {cache}")
    peaks = None
    if require_chip:
        table = load_json(BENCH / "peaks.json")["devices"]
        if dev.device_kind not in table:
            raise KeyError(f"device kind {dev.device_kind!r} is not in "
                           f"peaks.json")
        peaks = table[dev.device_kind]

    phases = {}
    engine, index, pool, gt = build(conf, traffic, phases)
    skw = search_kwargs(conf, traffic)
    order = pool_order(args.seed, pool.shape[0])
    t0 = time.perf_counter()
    for i in range(2):
        rows = batch_rows(i, traffic["batch"], order)
        jax.block_until_ready(engine.search(pool[rows], **skw))
    phases["warm_up"] = time.perf_counter() - t0
    log(f"setup warm_up: {phases['warm_up']:.3f} s")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        setup_s = time.perf_counter() - _T_START
        records, t_begin, traced = closed_loop(
            engine, pool, order, traffic, skw, args.seconds, trace_dir,
            compiles)
        window_s = records[-1].t1 - t_begin
        n_q = sum(len(r.rows) for r in records)
        log(f"window: {len(records)} calls, {n_q} queries in "
            f"{window_s:.3f} s; compiles in the window: {compiles['window']}")
        peak = memory_peak()
        trace = reduce_trace(trace_dir, records, traced) if args.trace else None
        if trace:
            log(f"traced: {traced} calls in {trace.window_s:.3f} s, device "
                f"busy {trace.busy_s:.3f} s")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    kernels = set(ops.TRACED_KERNELS)
    del engine
    jax.clear_caches()

    t0 = time.perf_counter()
    nums, n_checked = check(records, index, pool, conf, traffic, skw,
                            args.seed)
    log(f"reference: {n_checked} queries compared in "
        f"{time.perf_counter() - t0:.3f} s; {nums}")
    limits = {k: v for k, v in conf["limits"].items() if k in nums}
    correct = all(nums[k] <= v for k, v in limits.items())
    failed = int(sum((r.ids[:, : skw["k"]] >= conf["shape"]["n_base"]).any(1)
                     .sum() for r in records))

    metrics = {}
    if args.trace:
        ctx = layer_context(records[:traced], trace, conf, traffic, peaks,
                            kernels)
        for m in layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        lat = np.array([(r.t1 - r.t0) * 1e3 for r in records])
        values = {"qps": n_q / window_s,
                  "recall_at_10": recall(records, gt, 10),
                  "latency_p99_ms": float(np.percentile(lat, 99)),
                  "setup_s": setup_s}
        for m in e2e:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
        log(f"latency ms: p50 {np.percentile(lat, 50):.3f} p99 "
            f"{np.percentile(lat, 99):.3f} max {lat.max():.3f} over "
            f"{len(lat)} calls; {int((lat > 2 * np.median(lat)).sum())} "
            f"calls over twice the median")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": n_q, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": trace.device_ops,
                               "idle_gaps": trace.idle_gaps}
    checks = {k: {"value": nums[k], "limit": v} for k, v in limits.items()}
    result["checks"] = checks
    return result, checks


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap


def main(argv=None) -> int:
    args = parser().parse_args(argv)
    sys.path.insert(0, str(REPO / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the program (src/repro) is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    try:
        result, checks = run(args)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
