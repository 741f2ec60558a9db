"""The benchmark run: one cell of ``BENCHMARK.json``, found by name.

A cell names a configuration (``configs/<config>.json``: corpus shape,
generator, index, quantizer, operating h and the limits of the
comparison) and a traffic mix (``traffic/<mix>.json``: loop, batch, h, k,
expand, entries, pool). Per-layer metrics are read by
``metrics/<metric>.py`` and kernel costs come from ``costs/<kernel>.py``.
Adding a cell, configuration, mix or metric adds files and entries; this
file does not change.

The configuration's ``quantizer.training`` chooses how the codebooks are
made. Both start from RPQ's step 0: k-means on ``quantizer.train_rows``
rows of the base for ``quantizer.kmeans_iters`` iterations, rotation the
identity. Absent or ``"kmeans"``, that is all; ``"rpq"``, the paper's
routing-guided training follows, ``core/trainer.fit`` with
``quantizer.fit`` as its ``TrainConfig``, over the whole base and the
graph, learning the rotation, its first ``FIT_STEPS_CHECKED`` steps
recorded for ``fit_reference.py``. Any other value is refused before the
corpus is built.

The configuration's ``index.engine`` chooses the program's engine. Absent
or ``"hybrid"``: one Vamana graph over the whole base and a
``HybridEngine`` on one chip. ``"sharded_graph"``: the base split into
``index.shards`` contiguous row ranges, one Vamana graph each
(``graphs/partition.build_partitioned_vamana``), served by a
``ShardedGraphEngine`` whose mesh is the first ``index.shards`` devices
on one axis; ``index.shards`` must equal the cell's ``chips``, and
``fit`` is not run over it. Any other value, or ``"sharded_graph"`` with
``"rpq"``, is refused before the corpus is built.

A run:

1. set-up, each phase timed on standard error: the corpus and the held-out
   query pool on the device, from the configuration's ``data_seed``; their exact kNN (the
   benchmark's own brute force); the program's Vamana graph (one per
   shard for ``"sharded_graph"``); PQ codebooks by k-means (RPQ's step-0
   initialisation) on the first half of the base, then, for ``"rpq"``,
   ``fit`` (phase ``rpq_fit``); the code table; the engine as
   ``launch/serve.py`` builds it; two warm-up calls at the cell's shape;
2. the window: a closed loop of ``engine.search`` calls, each with the
   next ``batch`` queries of the pool in an order drawn from ``--seed``,
   until ``--seconds`` have passed;
   with ``--trace 1`` the profiler records its first ``trace_seconds``;
3. after the window: the devices' peak memory; then the served code table
   is held to the codebooks, a sample of the answered calls, drawn from
   the seed, to ``reference.py``, for ``"rpq"`` the first steps of
   ``fit`` to ``fit_reference.py``, and for ``"sharded_graph"`` the
   placement of the engine's resident arrays to the mesh.

The last line of standard output is the result; the numbers compared,
each beside its limit, are the last lines of standard error.
"""

from __future__ import annotations

import argparse
import contextlib
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


# quantizer.training: how a configuration's codebooks are made
TRAINING = ("kmeans", "rpq")
# index.engine: the program's engine a configuration is served by
ENGINES = ("hybrid", "sharded_graph")
# fit's first steps held to fit_reference.py
FIT_STEPS_CHECKED = 3


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


def merge(target: dict, values: dict) -> None:
    """``target`` updated by ``values``, nested groups key by key."""
    for key, value in values.items():
        if isinstance(value, dict) and isinstance(target.get(key), dict):
            merge(target[key], value)
        else:
            target[key] = value


def find_cell(name: str, overrides: dict | None = None):
    """(workload, configuration entry, config file, traffic file, end-to-end
    and per-layer metric entries of this cell) for the cell ``name``.
    ``overrides`` updates parts of the configuration ("shape", "index",
    "quantizer", "generator", "conf" itself) or the traffic ("traffic"),
    nested groups key by key: the tests cut a cell to a size the CPU can
    hold."""
    bm = load_json(REPO / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf_entry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    conf = load_json(REPO / conf_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    for part, values in (overrides or {}).items():
        merge({"conf": conf, "traffic": traffic}.get(part) or conf[part],
              values)
    idx = conf["index"]
    if (idx.get("engine") == "sharded_graph"
            and idx.get("shards") != cell["chips"]):
        raise ValueError(f"index.shards {idx.get('shards')!r} of "
                         f"{conf['name']} is not the cell's chips "
                         f"{cell['chips']}")

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


class StopFit(Exception):
    """Raised by a ``FitSteps`` that stops training once it has recorded."""


class FitSteps:
    """``fit``'s first ``keep`` steps as its jitted step saw them: the
    parameters it was first given, each step's feed, key and loss, Adam's
    state after the first step and the parameters after the last. Installed
    around ``trainer.make_train_step``, it passes every step through
    unchanged; with ``stop`` it ends training once it has recorded
    (``control.py``'s readings)."""

    def __init__(self, keep: int = FIT_STEPS_CHECKED, stop: bool = False):
        self.keep, self.stop = keep, stop
        self.feeds, self.losses = [], []
        self.params0 = self.params = self.first_state = None

    @contextlib.contextmanager
    def installed(self):
        from repro.core import trainer

        make = trainer.make_train_step

        def make_recorded(cfg, tcfg, optimizer):
            step = make(cfg, tcfg, optimizer)

            def recorded(params, opt_state, x, trip, route, key):
                out = step(params, opt_state, x, trip, route, key)
                if len(self.feeds) < self.keep:
                    self.record(params, trip, route, key, out)
                return out
            return recorded

        trainer.make_train_step = make_recorded
        try:
            yield self
        finally:
            trainer.make_train_step = make

    def record(self, params, trip, route, key, out):
        new, state, report, _ = out
        if not self.feeds:
            self.params0, self.first_state = params, state
        self.feeds.append({"trip": trip._asdict(),
                           "route": {"q": route.q, "cand": route.cand,
                                     "valid": route.valid},
                           "key": key})
        self.losses.append(report.total)
        self.params = new
        if self.stop and len(self.feeds) == self.keep:
            raise StopFit

    def program(self) -> dict:
        """What ``fit_reference.numbers`` compares: the first gradient is
        Adam's first moment after one step over ``1 - b1``."""
        import fit_reference as fr

        if len(self.feeds) < self.keep:
            raise RuntimeError(f"fit ran {len(self.feeds)} recorded steps, "
                               f"{self.keep} are checked")
        first_moment = self.first_state.inner[0]
        leaves = lambda p: {k: np.asarray(getattr(p, k)) for k in fr.LEAVES}  # noqa: E731
        return {"params0": leaves(self.params0),
                "params": leaves(self.params),
                "losses": [float(v) for v in self.losses],
                "grad": {k: v / (1 - fr.ADAM_B1)
                         for k, v in leaves(first_moment).items()}}


def log_fit(state, step0) -> None:
    """``fit``'s first and last logged losses, and how far it moved the
    quantizer from step 0, on standard error (the sums tell two runs'
    quantizers apart)."""
    for rec in (state.history[0], state.history[-1]):
        log(f"rpq_fit step {rec['step']}: total {rec['total']:.6f} "
            f"routing {rec['routing']:.6f} neighborhood "
            f"{rec['neighborhood']:.6f} alpha {rec['alpha']:.6f} "
            f"wall {rec['wall']:.3f} s")
    for name in ("theta", "codebooks"):
        new, old = (np.asarray(getattr(p, name)) for p in (state.params,
                                                             step0))
        log(f"rpq_fit {name}: largest change {np.abs(new - old).max():.6g}, "
            f"sum {np.sum(new, dtype=np.float64):.17g}")


def step0(conf: dict, traffic: dict, phases: dict) -> SimpleNamespace:
    """Corpus, ground truth, the program's graph and RPQ's step 0. All of
    it comes from the configuration's ``data_seed``: every run serves the
    same deployment, and ``--seed`` changes only the order of the queries
    and the answers sampled for the check."""
    import jax
    from repro.core import RPQConfig
    from repro.core.trainer import init_rpq
    from repro.graphs.partition import build_partitioned_vamana
    from repro.graphs.vamana import build_vamana

    import reference

    shape, gen, idx, qz = (conf["shape"], conf["generator"], conf["index"],
                           conf["quantizer"])
    training = qz.get("training", "kmeans")
    if training not in TRAINING:
        raise ValueError(f"quantizer.training {training!r} is not one of "
                         f"{TRAINING}")
    engine = idx.get("engine", "hybrid")
    if engine not in ENGINES:
        raise ValueError(f"index.engine {engine!r} is not one of {ENGINES}")
    if engine == "sharded_graph" and training == "rpq":
        raise ValueError("quantizer.training 'rpq' samples over one graph: "
                         "not with index.engine 'sharded_graph'")
    key = seed_key(gen["data_seed"])
    k_data, k_graph, k_pq = jax.random.split(key, 3)
    make = load_module(BENCH / "data" / f"{gen['module']}.py").make
    base, pool = _timed(phases, "corpus", make, k_data, shape["n_base"],
                        traffic["pool"], gen)
    gt, _ = _timed(phases, "ground_truth", reference.exact_knn, base, pool,
                   traffic["k"])
    vamana = dict(r=idx["R"], l=idx["L"], alpha=idx["alpha"],
                  passes=idx["passes"])
    if engine == "hybrid":
        graph = _timed(phases, "graph", build_vamana, k_graph, base, **vamana)
    else:
        graph = _timed(phases, "graph", build_partitioned_vamana, k_graph,
                       base, idx["shards"], **vamana)
    cfg = RPQConfig(dim=shape["dim"], m=qz["M"], k=qz["K"],
                    learn_rotation=training == "rpq")
    params = _timed(phases, "codebooks", init_rpq, k_pq, cfg,
                    base[: qz["train_rows"]], kmeans_iters=qz["kmeans_iters"])
    return SimpleNamespace(base=base, pool=pool, gt=gt, graph=graph, cfg=cfg,
                           params=params, k_pq=k_pq, training=training,
                           engine=engine)


def train(parts, qz: dict, key, spy: FitSteps):
    """``fit`` from step 0 over the whole base (its samplers index the
    graph's rows), its first steps recorded by ``spy``."""
    import jax
    from repro.core.trainer import TrainConfig, fit

    with spy.installed():
        state = fit(key, parts.cfg, TrainConfig(**qz["fit"]), parts.base,
                    parts.graph, params=parts.params, verbose=False)
    # the state is no pytree: its parameters are waited for
    jax.block_until_ready(state.params)
    return state


def build(conf: dict, traffic: dict, phases: dict):
    """The program's index and engine, and what the check needs of it."""
    import jax
    from repro.core.trainer import to_model
    from repro.pq import base as pqbase
    from repro.pq import pack
    from repro.search.engine import HybridEngine

    qz = conf["quantizer"]
    parts = step0(conf, traffic, phases)
    params, spy = parts.params, None
    if parts.training == "rpq":
        spy = FitSteps()
        state = _timed(phases, "rpq_fit", train, parts, qz,
                       jax.random.fold_in(parts.k_pq, 1), spy)
        log_fit(state, parts.params)
        params = state.params
    model = to_model(parts.cfg, params)
    base = parts.base
    codes = _timed(phases, "encode", pqbase.encode, model, base)
    if qz["layout"] == "fs4":
        codes = pack.pack_codes(codes)
        lut_fn = lambda q: pqbase.build_lut(model, q, quantize=True)  # noqa: E731
    else:
        lut_fn = lambda q: pqbase.build_lut(model, q)  # noqa: E731
    if parts.engine == "hybrid":
        engine = HybridEngine(parts.graph, codes, lut_fn, vectors=base)
        index = {"neighbors": parts.graph.neighbors,
                 "entry": parts.graph.medoid, "r": model.r,
                 "codebooks": model.codebooks, "codes": codes, "base": base,
                 "fit": spy}
    else:
        engine = sharded_engine(parts.graph, codes, lut_fn, base,
                                conf["index"]["shards"])
        index = sharded_index(engine, parts.graph, model, base)
    return engine, index, np.asarray(parts.pool), np.asarray(parts.gt)


def sharded_engine(graph, codes, lut_fn, base, shards: int):
    """A ``ShardedGraphEngine`` over the first ``shards`` devices, on one
    mesh axis."""
    import jax
    from jax.sharding import Mesh
    from repro.search.engine import ShardedGraphEngine

    mesh = Mesh(np.array(jax.devices()[:shards]), ("data",))
    return ShardedGraphEngine(graph, codes, lut_fn, vectors=base, mesh=mesh)


def sharded_index(engine, graph, model, base) -> dict:
    """What the check needs of a sharded build: the per-shard graphs, the
    served code table as the engine holds it (shard-stacked, on the
    host), the base, and the engine's resident arrays with the mesh's
    devices, whose placement ``shards_misplaced`` reads. Nothing of the
    program's merge."""
    return {"neighbors": graph.neighbors, "medoids": graph.medoids,
            "r": model.r, "codebooks": model.codebooks,
            "codes": np.asarray(engine.codes), "base": base, "fit": None,
            # the engine keeps its adjacency under a private name only
            "resident": {"codes": engine.codes, "neighbors": engine._nbrs_s,
                         "vectors": engine.vectors},
            "devices": list(engine.mesh.devices.flat)}


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
                                           rounds=rounds,
                                           degraded=bool(res.degraded)))
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
    """The peak on the fullest device; each device's on standard error."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    log(f"peak bytes in use by device: {peaks}; reported {max(peaks)}")
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
    seed, against ``reference.answer`` (``answer_sharded`` for a sharded
    build, whose resident arrays are also held to the mesh)."""
    import jax.numpy as jnp

    import reference

    sharded = "medoids" in index
    n = index["base"].shape[0]
    table = (reference.global_rows(index["codes"], n) if sharded
             else index["codes"])
    wrong = 1.0 if table is None else reference.code_rows_wrong(
        index["base"], index["r"], index["codebooks"], table,
        conf["quantizer"]["layout"])
    pick = sample_calls(records, traffic, seed)
    rows = np.concatenate([records[i].rows for i in pick])
    ids = np.concatenate([records[i].ids for i in pick])
    dists = np.concatenate([records[i].dists for i in pick])
    q = pool[rows]
    answer = reference.answer_sharded if sharded else reference.answer
    ref_ids, ref_d = answer(
        [pool[records[i].rows] for i in pick], index,
        layout=conf["quantizer"]["layout"], h=skw["h"], k=skw["k"],
        max_steps=skw["max_steps"])
    true_d = np.asarray(reference.returned_dists(
        jnp.asarray(q), jnp.asarray(ids), index["base"]))
    nums = reference.compare(ids, dists, ref_ids, ref_d, true_d)
    if sharded:
        nums["shards_misplaced"] = reference.shards_misplaced(
            index["resident"], index["devices"])
    return {"code_rows_wrong": wrong, **nums,
            **check_fit(index, conf)}, len(rows)


def failed_queries(records, k: int, n_base: int) -> int:
    """Queries whose top-k holds a sentinel (an id below 0, as the sharded
    merge pads, or at or above ``n_base``), or whose call came back
    degraded (a shard left out of the merge)."""
    return int(sum((((r.ids[:, :k] < 0) | (r.ids[:, :k] >= n_base)).any(1)
                    | r.degraded).sum() for r in records))


def check_fit(index, conf) -> dict:
    """For a quantizer trained by ``fit``: its first steps against
    ``fit_reference.steps`` from the same step 0 and feeds."""
    import fit_reference

    spy = index.get("fit")
    if spy is None:
        return {}
    program = spy.program()
    ref = fit_reference.steps(program["params0"], index["base"], spy.feeds,
                              conf["quantizer"]["fit"])
    log(f"fit's first steps: losses {program['losses']}, reference "
        f"{[s.loss for s in ref]}")
    return fit_reference.numbers(program, ref)


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
    busy = [pr.busy_ns(per_dev[p]) for p in sorted(per_dev)]
    first = per_dev[sorted(per_dev)[0]]
    return SimpleNamespace(
        events=first, window_s=(t1 - t0) / 1e9,
        busy_s=float(np.mean(busy)) / 1e9,
        device_busy_s=[b / 1e9 for b in busy],
        device_ops=pr.top_ops(first), idle_gaps=pr.idle_gaps(first, host,
                                                            t0, t1))


def layer_context(records, trace, conf, traffic, peaks, kernels, phases):
    """What a per-layer reader may read (``metrics/<metric>.py``): the
    traced calls, the trace, the chip's peaks, the set-up phases, the
    configuration, and each kernel's events, costs and roofline share."""
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

    def roofline(name):
        """Share of its HBM roofline that kernel ``name`` reaches: (calls
        x least time of one call) over the calls' summed device time. The
        least time is the algorithmic bytes of a call over peak HBM
        bandwidth: the hop kernels' lookup-adds are far below any compute
        peak. No event of the kernel in the trace: nothing to read."""
        k = kernel(name)
        if not k.events:
            return None
        least = k.bytes / peaks["hbm_bytes_per_s"]
        spent = sum(d for _, _, d in k.events) / 1e9
        return 100.0 * len(k.events) * least / spent

    return SimpleNamespace(records=records, trace=trace, peaks=peaks,
                           phases=phases, conf=conf, kernel=kernel,
                           roofline=roofline)


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
    failed = failed_queries(records, skw["k"], conf["shape"]["n_base"])

    metrics = {}
    if args.trace:
        ctx = layer_context(records[:traced], trace, conf, traffic, peaks,
                            kernels, phases)
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
