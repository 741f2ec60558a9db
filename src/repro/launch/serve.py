"""ANN serving driver: load a trained RPQ checkpoint and serve queries.

    PYTHONPATH=src python -m repro.launch.serve --ckpt-dir runs/rpq \
        --dataset sift-small \
        [--scenario hybrid|memory|sharded|sharded-graph|streaming|disk] \
        [--codes u8|fs4] [--h 32] [--entries 8] [--prune-eps 0.1] \
        [--cache-mb 4] [--io-threads 4] [--port-stdin]

``--entries S`` / ``--prune-eps ε`` switch on adaptive routing (DESIGN.md
§11) in every scenario: S > 1 seeds each beam from the PQ-hash coarse
index instead of the single medoid, ε > 0 gates each hop's full ADC pass
behind a partial-LUT estimate. Both default OFF (S=1, ε=0 — bit-identical
to the classic beam). The graph-free ``sharded`` scan has no beam and
ignores them.

``--codes fs4`` serves the fast-scan layout (DESIGN.md §8) — 4-bit packed
codes + quantized uint8 LUTs — through ANY scenario; it needs a quantizer
trained with K ≤ 16 sub-codewords (e.g. ``train.py --m 16 --k 16`` for the
same bytes/vector as M=8, K=256).

Loads the latest checkpoint written by launch/train.py, rebuilds the
serving engine (codes are re-encoded from the checkpointed quantizer —
deterministic), and either runs a one-shot evaluation batch or reads
newline-delimited query vectors from stdin (toy request loop; a real
deployment fronts this with an RPC layer). In-process callers build the
arguments with :func:`parser` and call :func:`run`, which returns what it
measured.

Scenarios (search/engine.py, DESIGN.md §5–§6):

* ``memory``        — codes + PG in RAM, single device, ADC-only routing.
* ``hybrid``        — DiskANN-style: ADC routing + exact rerank from "SSD"
                      vectors (default).
* ``sharded``       — graph-free scatter-gather SCAN through ShardedEngine:
                      codes + vectors row-sharded over the local devices per
                      dist/sharding.rpq_rows_spec, per-shard exhaustive scan
                      + local rerank, dist.fault.partial_merge gather — the
                      serve_1m dry-run cell's pattern running for real.
* ``sharded-graph`` — graph-ROUTED scatter-gather through
                      ShardedGraphEngine: one independent Vamana subgraph
                      per device shard (graphs/partition.py, cached next to
                      the checkpoint), the beam search itself runs inside
                      shard_map with local exact rerank — the sharded_graph
                      dry-run cell's pattern running for real.
* ``streaming``     — live serving under CHURN through
                      repro.index.StreamingEngine (DESIGN.md §10): the
                      dataset's tail is held out as an insert stream, then
                      ``--churn-rounds`` rounds of interleaved insert /
                      delete / query batches run against the mutable index
                      (recall scored against the LIVE corpus each round),
                      followed by a consolidation that folds the delta into
                      the next base generation, snapshots it atomically
                      next to the checkpoint, and re-evaluates.
                      ``--refresh-every N`` additionally RETRAINS the
                      quantizer on the live graph every N rounds and at
                      the final consolidation (DESIGN.md §12): each new
                      generation re-encodes against the refreshed
                      codebooks and its snapshot carries them, so a
                      restart restores self-contained.
* ``disk``          — ALL-IN-STORAGE serving (DESIGN.md §14,
                      repro/storage/): the Vamana adjacency + packed codes
                      are written to a per-vertex record segment file and
                      served by DiskEngine — every beam round fetches its
                      candidate records from disk through an async reader
                      with double-buffered frontier prefetch; DRAM holds
                      only the LUTs, the entry points, and an LRU
                      hot-vertex cache (``--cache-mb``). ``--chaos
                      slow_read=5`` models device latency on the real read
                      path; ``--chaos io=0.05`` injects transient read
                      faults (retried); ``--chaos corrupt_record`` flips a
                      record byte silently.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import RPQConfig
from repro.core.quantizer import RPQParams
from repro.core.trainer import to_model
from repro.data import load_dataset
from repro.dist import checkpoint as ckpt
from repro.dist.fault import ChaosPlan, InjectedFailure
from repro.dist.retry import RetryPolicy
from repro.graphs.knn import knn_ids
from repro.graphs.partition import PartitionedGraph, build_partitioned_vamana
from repro.launch import compile_cache
from repro.launch.train import build_or_load_graph
from repro.pq import base as pqbase
from repro.pq import pack
from repro.search.degrade import DegradationPolicy
from repro.search.engine import (HybridEngine, InMemoryEngine, ShardedEngine,
                                 ShardedGraphEngine)
from repro.search.metrics import live_ground_truth, measure_qps, recall_at_k


def build_or_load_partitioned_graph(key, x, cache_path: str, n_shards: int,
                                    r: int, l: int) -> PartitionedGraph:
    """Per-shard Vamana subgraphs, cached next to the checkpoint (the
    partition depends on the shard count and the row count, so a cache
    built for other values is rebuilt)."""
    if cache_path and os.path.exists(cache_path):
        z = np.load(cache_path)
        if int(z["n_shards"]) == n_shards and int(z["n"]) == x.shape[0]:
            return PartitionedGraph(neighbors=jnp.asarray(z["neighbors"]),
                                    medoids=jnp.asarray(z["medoids"]),
                                    n=int(z["n"]))
    pg = build_partitioned_vamana(key, x, n_shards, r=r, l=l)
    if cache_path:
        os.makedirs(os.path.dirname(cache_path) or ".", exist_ok=True)
        np.savez(cache_path, neighbors=np.asarray(pg.neighbors),
                 medoids=np.asarray(pg.medoids), n=pg.n, n_shards=n_shards)
    return pg


def calibrate_max_rounds(engine, queries, deadline_s: float, **kw) -> int:
    """Turn a wall-clock deadline into a per-call round budget: run one
    warmup batch (absorbs compile), time a steady-state batch, divide the
    observed per-round latency into the deadline (DESIGN.md §13). The
    budget is a TRACED argument downstream, so re-calibrating under drift
    never recompiles."""
    res = engine.search(queries, **kw)
    jax.block_until_ready(res.dists)
    t0 = time.perf_counter()
    res = engine.search(queries, **kw)
    jax.block_until_ready(res.dists)
    elapsed = time.perf_counter() - t0
    rounds = 1.0
    if res.rounds is not None:
        rounds = max(float(np.asarray(res.rounds).max()), 1.0)
    per_round = elapsed / rounds
    return max(1, int(deadline_s / per_round))


def run_streaming(args, model, ds, plan: Optional[ChaosPlan] = None) -> None:
    """The churn loop: hold out the dataset tail as an insert stream, then
    interleave insert / delete / query batches through a StreamingEngine
    and consolidate at the end (DESIGN.md §10)."""
    from repro.index import BaseSegment, StreamingEngine
    from repro.index.segment import encode_codes

    n = int(ds.base.shape[0])
    n0 = n - int(n * args.churn)
    base_x = np.asarray(ds.base[:n0])
    stream = np.asarray(ds.base[n0:])
    graph = build_or_load_graph(jax.random.PRNGKey(0), base_x,
                                f"{args.ckpt_dir}/graph_stream{n0}.npz",
                                args.graph_r, args.graph_l)
    seg = BaseSegment(graph=graph,
                      codes=jnp.asarray(encode_codes(model, base_x,
                                                     args.codes)),
                      vectors=jnp.asarray(base_x), layout=args.codes)
    cap = max(len(stream), 1)
    engine = StreamingEngine(seg, model, delta_capacity=cap)
    print(f"[serve] streaming: base {n0} rows (gen 0), insert stream "
          f"{len(stream)}, delta capacity {cap}, layout {args.codes}")

    rng = np.random.default_rng(0)
    # gid → vector row for live-corpus ground truth: written at insert time
    # (consolidation renumbers gids, so a static base+stream concat would
    # go stale after the first mid-stream generation bump)
    all_x = np.zeros((n0 + cap, base_x.shape[1]), np.float32)
    all_x[:n0] = base_x
    live = np.zeros(n0 + cap, bool)
    live[:n0] = True

    policy = DegradationPolicy()
    budget = {"max_rounds": None}

    def evaluate(tag: str) -> None:
        if args.deadline_ms and budget["max_rounds"] is None:
            budget["max_rounds"] = calibrate_max_rounds(
                engine, ds.queries, args.deadline_ms / 1e3, k=args.k,
                h=args.h)
            print(f"[serve] deadline {args.deadline_ms}ms → "
                  f"max_rounds={budget['max_rounds']}")
        skw = policy.apply(engine, args.degrade_level, h=args.h,
                           expand=args.expand, entries=args.entries,
                           prune_eps=args.prune_eps,
                           max_rounds=budget["max_rounds"])
        gt_g = live_ground_truth(all_x, np.flatnonzero(live), ds.queries,
                                 args.k)
        qps, res = measure_qps(
            lambda q: engine.search(q, k=args.k, **skw), ds.queries)
        trunc = (f" truncated={float(np.asarray(res.truncated).mean()):.2f}"
                 if res.truncated is not None else "")
        print(f"[serve] streaming/{tag}: recall@{args.k}="
              f"{recall_at_k(res.ids, gt_g, args.k):.4f} qps={qps:.1f} "
              f"live={engine.n_live} gen={engine.generation} "
              f"resident={engine.memory_bytes()/1e6:.1f}MB{trunc}")

    snap_dir = f"{args.ckpt_dir}/streaming_index"

    def consolidate_now(refresh, chaos=None) -> dict:
        nonlocal live, all_x
        stats = engine.consolidate(ckpt_dir=snap_dir, keep=3,
                                   refresh=refresh, chaos=chaos)
        # consolidation renumbers: translate the live-corpus bookkeeping
        old_live = np.flatnonzero(live)
        live = np.zeros(stats["n"] + cap, bool)
        live[stats["old2new"][old_live]] = True
        all_x = np.concatenate([
            np.asarray(engine.base.vectors),
            np.zeros((cap, base_x.shape[1]), np.float32)])
        extra = ""
        if stats["refreshed"]:
            rep = stats["refresh"]
            extra = (f", codebooks refreshed (live distortion "
                     f"{rep['distortion_before']:.3f} → "
                     f"{rep['distortion_after']:.3f})")
        print(f"[serve] consolidated → generation {stats['generation']}: "
              f"{stats['n']} rows ({stats['dropped']} dropped, "
              f"{stats['folded']} folded in){extra}, snapshot at "
              f"{snap_dir}")
        return stats

    rounds = max(args.churn_rounds, 1)
    per = -(-max(len(stream), 1) // rounds)
    for i in range(rounds):
        batch = stream[i * per:(i + 1) * per]
        if len(batch):
            gids = engine.insert(batch)
            all_x[gids] = batch
            live[gids] = True
        base_rows = engine.base.n
        live_base = np.flatnonzero(live[:base_rows])
        dead = rng.choice(live_base, min(len(batch), len(live_base)),
                          replace=False)
        engine.delete(dead)
        live[dead] = False
        evaluate(f"round{i}")
        # mid-stream refreshed consolidations close the learning loop
        # (DESIGN.md §12) while the stream keeps flowing; the final
        # consolidation below covers the tail
        if (args.refresh_every and (i + 1) % args.refresh_every == 0
                and i + 1 < rounds):
            consolidate_now(refresh=True)
            evaluate(f"refreshed{i}")
    if plan is not None and plan.crash_phase is not None:
        # chaos drill (DESIGN.md §13): crash mid-consolidation, then prove
        # a restart lands on an intact generation — with the newest
        # snapshot corrupted on top when the plan says so. The drill must
        # demonstrate FALLBACK, not data loss: establish a durable intact
        # generation first (two when corruption will also eat the newest
        # one — a pre_snapshot crash writes nothing, so the corruptor
        # would otherwise hit the only snapshot on disk).
        consolidate_now(refresh=False)
        if plan.corrupt_latest_snapshot:
            consolidate_now(refresh=False)
        try:
            consolidate_now(refresh=bool(args.refresh_every),
                            chaos=plan.consolidate_hook())
        except InjectedFailure as e:
            print(f"[serve] chaos: injected crash during consolidation "
                  f"({e}); restarting from {snap_dir}")
        if plan.corrupt_latest_snapshot:
            from repro.dist.fault import corrupt_snapshot
            step = corrupt_snapshot(snap_dir, seed=plan.seed)
            print(f"[serve] chaos: corrupted snapshot generation {step}")
        engine = StreamingEngine.restore(
            snap_dir, delta_capacity=cap, retry=RetryPolicy(),
            on_fallback=lambda g, e: print(
                f"[serve] chaos: generation {g} failed verification "
                f"({type(e).__name__}) — falling back"))
        live = np.zeros(engine.base.n + cap, bool)
        live[:engine.base.n] = True
        all_x = np.concatenate([np.asarray(engine.base.vectors),
                                np.zeros((cap, base_x.shape[1]),
                                         np.float32)])
        print(f"[serve] chaos: restored generation {engine.generation} "
              f"({engine.n_live} live rows)")
        evaluate("restored")
        return
    consolidate_now(refresh=bool(args.refresh_every))
    evaluate("consolidated")


def parser() -> argparse.ArgumentParser:
    """The command line of ``python -m repro.launch.serve``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--dataset", default="sift-small")
    ap.add_argument("--scenario",
                    choices=("hybrid", "memory", "sharded", "sharded-graph",
                             "streaming", "disk"),
                    default="hybrid")
    ap.add_argument("--codes", choices=("u8", "fs4"), default="u8",
                    help="serving layout: u8 = 1 byte/sub-code + f32 LUTs; "
                    "fs4 = fast-scan 4-bit packed codes + quantized uint8 "
                    "LUTs (requires a checkpoint trained with K <= 16)")
    ap.add_argument("--h", type=int, default=32)
    ap.add_argument("--expand", type=int, default=1,
                    help="frontier batch size E (DESIGN.md §9): nodes "
                    "expanded per beam round — each round scores one "
                    "E*R-wide fused hop-ADC call instead of E narrow ones "
                    "(the sharded scenario has no beam and ignores it)")
    ap.add_argument("--entries", type=int, default=1,
                    help="adaptive routing (DESIGN.md §11): seed each beam "
                    "with S entry points from the PQ-hash coarse index "
                    "instead of the single medoid; 1 = classic routing "
                    "(bit-identical). The sharded-graph scenario seeds "
                    "per shard inside shard_map")
    ap.add_argument("--prune-eps", type=float, default=0.0,
                    help="adaptive routing (DESIGN.md §11): probabilistic "
                    "hop pruning margin ε — each hop first scores the "
                    "frontier on a prefix of the subspaces and full-scores "
                    "only lanes whose extrapolated estimate beats the beam "
                    "threshold by ε; 0 = off (bit-identical)")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--graph-r", type=int, default=24)
    ap.add_argument("--graph-l", type=int, default=48)
    ap.add_argument("--churn", type=float, default=0.1,
                    help="streaming scenario: fraction of the dataset held "
                    "out as the insert stream (an equal count of base rows "
                    "is deleted over the churn rounds)")
    ap.add_argument("--churn-rounds", type=int, default=4,
                    help="streaming scenario: interleaved insert/delete/"
                    "query rounds before consolidation")
    ap.add_argument("--refresh-every", type=int, default=0,
                    help="streaming scenario: run a codebook-REFRESHED "
                    "consolidation every N churn rounds (DESIGN.md §12) — "
                    "the quantizer retrains on the live graph and the new "
                    "generation re-encodes against it; the final "
                    "consolidation refreshes too. 0 = codebooks stay "
                    "frozen across generations (the pre-refresh behavior)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-batch serving deadline (DESIGN.md §13): a "
                    "warmup batch calibrates the per-round latency and the "
                    "beam gets the max_rounds budget that fits — capped "
                    "queries return best-so-far with truncated flags set. "
                    "0 = no deadline. For sharded-graph it also sets the "
                    "straggler deadline of the quorum merge")
    ap.add_argument("--degrade-level", type=int, default=0,
                    help="degradation ladder rung (DESIGN.md §13, "
                    "search/degrade.py): 0 = full config, each level sheds "
                    "the next recall-for-compute knob (L1 expand, L2 "
                    "entries, L3 aggressive prune, L4 rerank, L5 delta "
                    "scan)")
    ap.add_argument("--chaos", default="",
                    help="fault-injection plan (DESIGN.md §13), e.g. "
                    "'dead=1,straggler=2,straggler_ms=50,io=0.05,corrupt,"
                    "crash=consolidate,seed=7': kill shards, slow shards, "
                    "inject transient I/O faults, corrupt the newest "
                    "snapshot, crash mid-consolidation — serving must "
                    "degrade, never throw")
    ap.add_argument("--cache-mb", type=float, default=4.0,
                    help="disk scenario: DRAM budget for the hot-vertex "
                    "cache (LRU over per-vertex records, BFS-seeded from "
                    "the medoid)")
    ap.add_argument("--io-threads", type=int, default=4,
                    help="disk scenario: reader thread-pool width — a "
                    "round's record batch is split across this many "
                    "concurrent pread workers")
    ap.add_argument("--port-stdin", action="store_true",
                    help="read whitespace-separated query vectors on stdin")
    return ap


def main():
    args = parser().parse_args()
    compile_cache.enable()
    return run(args)


def run(args) -> dict:
    """Serve one scenario as :func:`main` parses it; returns what it measured
    (``recall``, ``qps``, the ``engine`` and its ``result``, the
    ``queries`` and exact ``gt`` ids) so in-process callers can check it.
    The streaming and stdin-port scenarios print only and return ``{}``."""
    plan = ChaosPlan.parse(args.chaos) if args.chaos else None
    retry = None
    if plan is not None and plan.io_fault_p > 0:
        # every checkpoint read in this process now fails transiently with
        # probability io_fault_p — and retries through the backoff policy
        ckpt.set_io_fault_hook(plan.io_fault())
        retry = RetryPolicy()
        print(f"[serve] chaos: transient I/O fault p={plan.io_fault_p} "
              f"injected on checkpoint reads (retry up to "
              f"{retry.max_attempts} attempts)")

    state = ckpt.restore(args.ckpt_dir, retry=retry)
    extra = state.get("extra") or {}
    ds = load_dataset(extra.get("dataset", args.dataset),
                      scale=extra.get("scale"))
    m, k = extra.get("m", 8), extra.get("k", 64)
    cfg = RPQConfig(dim=ds.dim, m=m, k=k)
    flat = state["params"]
    params = RPQParams(theta=jnp.asarray(flat["theta"]),
                       codebooks=jnp.asarray(flat["codebooks"]),
                       log_alpha=jnp.asarray(flat["log_alpha"]))
    model = to_model(cfg, params)
    print(f"[serve] restored step {state['step']} quantizer "
          f"(M={m}, K={k}) from {args.ckpt_dir}")

    if args.codes == "fs4" and k > 16:
        raise SystemExit(
            f"--codes fs4 needs 4-bit sub-codes (K <= 16); this "
            f"checkpoint was trained with K={k}. Re-train with --k 16 "
            f"(double M to keep the byte budget).")
    if args.scenario == "streaming":  # live mutable index under churn
        if args.port_stdin:
            raise SystemExit(
                "--port-stdin is not available with --scenario streaming: "
                "the scenario runs a fixed churn loop, not a query port")
        run_streaming(args, model, ds, plan)
        return {}

    codes = pqbase.encode(model, ds.base)
    if args.codes == "fs4":
        # fast-scan layout (DESIGN.md §8): nibble-packed codes + uint8 LUTs.
        # Every scenario below accepts it — the engines dispatch on the
        # QuantizedLUT type that build_lut(quantize=True) returns.
        codes = pack.pack_codes(codes)
        lut_fn = lambda q: pqbase.build_lut(model, q, quantize=True)
        print(f"[serve] fast-scan fs4 layout: {codes.shape[1]} packed "
              f"bytes/vector, uint8 LUTs")
    else:
        lut_fn = lambda q: pqbase.build_lut(model, q)
    if args.scenario == "sharded":  # graph-free scatter-gather scan
        engine = ShardedEngine(codes, lut_fn, vectors=ds.base)
        print(f"[serve] sharded over {engine.n_shards} device shard(s)")
    elif args.scenario == "sharded-graph":  # graph-routed scatter-gather
        n_shards = len(jax.devices())
        pg = build_or_load_partitioned_graph(
            jax.random.PRNGKey(0), ds.base,
            f"{args.ckpt_dir}/graph_part{n_shards}.npz", n_shards,
            args.graph_r, args.graph_l)
        engine = ShardedGraphEngine(pg, codes, lut_fn, vectors=ds.base)
        print(f"[serve] graph-routed over {engine.n_shards} device "
              f"shard(s), {pg.n_local} rows/shard, R={pg.degree}")
    elif args.scenario == "disk":  # all-in-storage tier (DESIGN.md §14)
        from repro.index.segment import BaseSegment
        from repro.storage import DiskEngine, write_segment
        from repro.storage import format as segfmt

        graph = build_or_load_graph(jax.random.PRNGKey(0), ds.base,
                                    f"{args.ckpt_dir}/graph_base.npz",
                                    args.graph_r, args.graph_l)
        storage_dir = f"{args.ckpt_dir}/storage"
        seg = BaseSegment(graph=graph, codes=jnp.asarray(codes),
                          vectors=None, layout=args.codes,
                          generation=0, dim_hint=ds.dim)
        seg_path = write_segment(storage_dir, seg, model=model)
        fault_hook, slow_ms = None, 0.0
        if plan is not None:
            slow_ms = plan.slow_read_ms
            if plan.io_fault_p > 0:
                fault_hook = plan.io_fault()
                retry = retry or RetryPolicy()
                print(f"[serve] chaos: transient read fault p="
                      f"{plan.io_fault_p} injected on segment reads")
            if plan.corrupt_record:
                vid = segfmt.corrupt_record(seg_path, seed=plan.seed)
                print(f"[serve] chaos: silently corrupted record {vid} "
                      f"in {seg_path}")
        engine = DiskEngine.open(
            storage_dir, lut_fn=lut_fn, cache_mb=args.cache_mb,
            io_threads=args.io_threads, retry=retry,
            fault_hook=fault_hook, slow_read_ms=slow_ms,
            on_fallback=lambda g, e: print(
                f"[serve] disk: generation {g} failed header verification "
                f"({e}) — falling back"))
        print(f"[serve] disk: gen {engine.generation} segment "
              f"{os.path.getsize(engine.path)/1e6:.1f}MB on storage, "
              f"cache {len(engine.cache)}/{engine.cache.capacity} records "
              f"({args.cache_mb}MB budget), {args.io_threads} io threads")
    else:
        graph = build_or_load_graph(jax.random.PRNGKey(0), ds.base,
                                    f"{args.ckpt_dir}/graph_base.npz",
                                    args.graph_r, args.graph_l)
        if args.scenario == "hybrid":
            engine = HybridEngine(graph, codes, lut_fn, vectors=ds.base)
        else:
            engine = InMemoryEngine(graph, codes, lut_fn)

    if args.port_stdin:
        print(f"[serve] reading {ds.dim}-d queries from stdin "
              f"(one per line; EOF to stop)")
        for line in sys.stdin:
            try:
                vals = np.fromiter(line.split(), dtype=np.float32)
            except ValueError:
                print(f"!! expected {ds.dim} floats, got unparseable input")
                continue
            if vals.size != ds.dim:
                print(f"!! expected {ds.dim} floats, got {vals.size}")
                continue
            t0 = time.perf_counter()
            res = engine.search(jnp.asarray(vals)[None], k=args.k, h=args.h,
                                expand=args.expand, entries=args.entries,
                                prune_eps=args.prune_eps)
            dt = (time.perf_counter() - t0) * 1e3
            ids = np.asarray(res.ids[0]).tolist()
            print(f"ids={ids} dists={np.asarray(res.dists[0]).round(3).tolist()} "
                  f"({dt:.1f} ms, {int(res.hops[0])} hops)")
        return {}

    policy = DegradationPolicy()
    skw = policy.apply(engine, args.degrade_level, h=args.h,
                       expand=args.expand, entries=args.entries,
                       prune_eps=args.prune_eps)
    if args.deadline_ms and not isinstance(engine, ShardedEngine):
        # the graph-free exhaustive scan has no rounds to budget; its
        # deadline story is the quorum merge below
        mr = calibrate_max_rounds(engine, ds.queries,
                                  args.deadline_ms / 1e3, k=args.k, **skw)
        skw["max_rounds"] = mr
        print(f"[serve] deadline {args.deadline_ms}ms → max_rounds={mr}")
    if plan is not None and hasattr(engine, "n_shards"):
        skw["alive"] = list(plan.alive(engine.n_shards))
        dead = engine.n_shards - sum(skw["alive"])
        msg = f"[serve] chaos: {dead}/{engine.n_shards} shard(s) dead"
        if isinstance(engine, ShardedGraphEngine):
            skw["shard_latency_s"] = list(plan.latencies(engine.n_shards))
            if args.deadline_ms:
                skw["deadline_s"] = args.deadline_ms / 1e3
                msg += (f", stragglers {list(plan.straggler_shards)} at "
                        f"{plan.straggler_latency_s*1e3:.0f}ms vs "
                        f"{args.deadline_ms}ms deadline quorum")
        print(msg)

    gt, _ = knn_ids(ds.base, ds.queries, args.k)
    qps, res = measure_qps(lambda q: engine.search(q, k=args.k, **skw),
                           ds.queries)
    rounds = (f"rounds={float(res.rounds.mean()):.1f} "
              if res.rounds is not None else "")
    trunc = (f"truncated={float(np.asarray(res.truncated).mean()):.2f} "
             if res.truncated is not None else "")
    degr = "DEGRADED " if res.degraded else ""
    recall = recall_at_k(res.ids, gt, args.k)
    print(f"[serve] {args.scenario}: recall@{args.k}="
          f"{recall:.4f} qps={qps:.1f} "
          f"hops={float(res.hops.mean()):.1f} {rounds}{trunc}{degr}"
          f"resident={engine.memory_bytes()/1e6:.1f}MB")
    if args.scenario == "disk":
        io = engine.last_io
        print(f"[serve] disk io: cache_hit_rate={io['cache_hit_rate']:.3f} "
              f"bytes_read={io['bytes_read']} n_reads={io['n_reads']} "
              f"io_wait={io['io_wait_s']*1e3:.1f}ms "
              f"retries={io['n_retries']}")
    return {"recall": recall, "qps": qps, "engine": engine, "result": res,
            "search_kwargs": skw, "queries": ds.queries, "gt": gt}


if __name__ == "__main__":
    main()
