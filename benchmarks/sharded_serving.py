"""Sharded serving: graph-routed vs. exhaustive-scan vs. single-device.

The numbers behind DESIGN.md §6's engine choice: at matched recall targets,
how do the three multi-device-capable scenarios trade QPS, recall@10 and
per-query distance work?

* ``memory``        — single-device InMemoryEngine beam (the baseline the
                      acceptance bar is measured against),
* ``sharded-scan``  — ShardedEngine: every shard exhaustively ADC-scans its
                      rows (O(N/S) distances per query per shard),
* ``sharded-graph`` — ShardedGraphEngine: every shard beam-searches its own
                      Vamana subgraph (O(hops·R) distances), with and
                      without DiskANN-style local exact rerank,

plus a dead-shard row showing graceful recall degradation (never an error),
a frontier-batching sweep (E ∈ {1, 2, 4}, DESIGN.md §9) over the beam-routed
engines, an adaptive-routing sweep (S ∈ {1, 4, 8} seeds × ε ∈ {0, 0.1}
prune margin, DESIGN.md §11) whose summary rows record the rounds_cut /
n_dist_cut acceptance bars against the bit-identical S=1/ε=0 baseline, and
the DiskANN-style hybrid scenario whose per-query service time (compute +
per-round batched SSD reads) is where multi-expansion pays end to end on an
IO-modeled host. Every engine row carries ``rounds`` (sequential beam
rounds) and ``n_dist`` (full-LUT-equivalent distances per query) as parsed
derived fields in BENCH_sharded.json.

This is a CPU-only tool: run as a script it forces four host CPU devices
(``XLA_FLAGS``), and its times are CPU-sandbox numbers; the four-chip path
is ``chip_smoke.py --four-chips``. Run as a section of the driver (uses
however many devices exist — 1 in the default CPU sandbox):

    PYTHONPATH=src python -m benchmarks.run --only sharded

or standalone with a forced 4-way host-device split, the honest multi-shard
configuration:

    PYTHONPATH=src python -m benchmarks.sharded_serving
"""

from __future__ import annotations

import os


def run():
    import numpy as np
    import jax

    from benchmarks import common as C
    from repro.graphs.partition import build_partitioned_vamana
    from repro.search.engine import (HybridEngine, InMemoryEngine,
                                     ShardedEngine, ShardedGraphEngine)
    from repro.search.metrics import measure_qps, recall_at_k

    ds, gt, g = C.dataset(), C.ground_truth(), C.vamana_graph()
    codes, lut_fn, _ = C.quantizer("pq")
    n_shards = len(jax.devices())
    pg = build_partitioned_vamana(jax.random.PRNGKey(11), ds.base, n_shards,
                                  r=24, l=48, batch=2048)
    k, h = 10, 32
    rows = []

    def emit(row):
        rows.append(row)

    def bench(tag, engine, repeats=2, **kw):
        qps, res = measure_qps(
            lambda q: engine.search(q, k=k, **kw), ds.queries,
            repeats=repeats)
        rec = recall_at_k(res.ids, gt, k)
        hops = float(np.mean(np.asarray(res.hops)))
        n_dist = float(np.mean(np.asarray(res.n_dist)))
        rounds = (float(np.mean(np.asarray(res.rounds)))
                  if res.rounds is not None else hops)
        # rounds and n_dist ride in EVERY row — the adaptive-routing
        # acceptance bars (rounds_cut, n_dist_cut) are measured on them
        # and CI asserts BENCH_sharded carries them as parsed fields.
        emit((f"sharded/{tag}", 1e6 / max(qps, 1e-9),
              f"recall={rec:.3f};qps={qps:.1f};hops={hops:.1f};"
              f"rounds={rounds:.2f};n_dist={n_dist:.1f};shards={n_shards}"))
        return {"qps": qps, "recall": rec, "hops": hops, "rounds": rounds,
                "n_dist": n_dist}

    mem = InMemoryEngine(g, codes, lut_fn)
    bench("memory/h%d" % h, mem, h=h)

    scan = ShardedEngine(codes, lut_fn)
    bench("scan", scan)

    graph_eng = ShardedGraphEngine(pg, codes, lut_fn)
    bench("graph/h%d" % h, graph_eng, h=h)

    graph_rr = ShardedGraphEngine(pg, codes, lut_fn, vectors=ds.base)
    bench("graph_rerank/h%d" % h, graph_rr, h=h)

    # frontier-batching sweep (DESIGN.md §9): E ∈ {1, 2, 4} on the two
    # beam-routed engines — the QPS-vs-recall@10 frontier of multi-
    # expansion, plus E=4-vs-E=1 speedup rows. On a CPU host the compute
    # rows sit near parity (XLA fuses the per-hop work into the while body,
    # so there is no per-round dispatch to amortize — §9 explains why the
    # TPU picture differs); the regime where frontier batching pays end to
    # end HERE is the IO-round-bound DiskANN scenario below.
    expand_base = {}
    for tag, engine in (("memory", mem), ("graph", graph_eng)):
        sweep = {}
        for e in (1, 2, 4):
            # repeats=6: the speedup row below is a recorded acceptance
            # metric and 2-repeat means swing 2× on a shared CPU host
            sweep[e] = bench(f"{tag}/h{h}/e{e}", engine, repeats=6, h=h,
                             expand=e)
        expand_base[tag] = sweep
        b1, b4 = sweep[1], sweep[4]
        emit((f"sharded/{tag}/expand_speedup", 1e6 / max(b4["qps"], 1e-9),
              f"qps_e4_over_e1={b4['qps'] / max(b1['qps'], 1e-9):.2f};"
              f"recall_delta={b4['recall'] - b1['recall']:+.3f};"
              f"rounds={b4['rounds']:.2f};n_dist={b4['n_dist']:.1f}"))

    # adaptive routing sweep (DESIGN.md §11): PQ-hash multi-entry seeding
    # (S = entries) × probabilistic hop pruning (ε = prune_eps) on the two
    # beam-routed engines. The S=1/ε=0 cell takes the BIT-IDENTICAL classic
    # path — its recall/rounds/n_dist must equal the e1 row above (CI
    # asserts this against the recorded baseline), so it anchors the
    # rounds_cut / n_dist_cut acceptance rows:
    #   * n_dist_cut — best pruned cell vs S=1/ε=0 at the same E=1 (≥30%
    #     fewer full-LUT-equivalent distance evaluations, recall within
    #     1pt),
    #   * rounds_cut — the combined adaptive config (seeding + pruning +
    #     frontier batching E=4) vs the classic SEQUENTIAL beam (S=1/ε=0/
    #     E=1), the "cut sequential rounds" headline (≥2×, recall within
    #     1pt).
    for tag, engine in (("memory", mem), ("graph", graph_eng)):
        grid = {}
        for s in (1, 4, 8):
            for eps in (0.0, 0.1):
                grid[(s, eps)] = bench(f"{tag}/adaptive/S{s}_eps{eps:g}",
                                       engine, h=h, entries=s, prune_eps=eps)
        # tuned deep-prune cell: short prefix + wide seed set + larger ε
        grid[(16, 0.2)] = bench(f"{tag}/adaptive/S16_eps0.2", engine, h=h,
                                entries=16, prune_eps=0.2)
        base = grid[(1, 0.0)]
        e1 = expand_base[tag][1]
        if abs(base["recall"] - e1["recall"]) > 1e-6 or \
           abs(base["rounds"] - e1["rounds"]) > 1e-6:
            raise SystemExit(
                f"adaptive S=1/eps=0 diverged from the classic beam on "
                f"{tag}: {base} vs {e1}")
        ok = [(key, c) for key, c in grid.items()
              if key[1] > 0 and c["recall"] >= base["recall"] - 0.01]
        (ps, peps), pruned = min(ok, key=lambda kc: kc[1]["n_dist"]) \
            if ok else ((0, 0.0), base)
        combo = bench(f"{tag}/adaptive/S8_eps0.1_e4", engine, h=h,
                      entries=8, prune_eps=0.1, expand=4)
        emit((f"sharded/{tag}/adaptive_summary", 0.0,
              f"n_dist_cut={1.0 - pruned['n_dist'] / base['n_dist']:.3f};"
              f"pruned_cfg=S{ps}_eps{peps:g};"
              f"pruned_recall_delta={pruned['recall'] - base['recall']:+.3f};"
              f"rounds_cut={base['rounds'] / max(combo['rounds'], 1e-9):.2f};"
              f"combo_recall_delta={combo['recall'] - base['recall']:+.3f};"
              f"base_rounds={base['rounds']:.2f};"
              f"combo_rounds={combo['rounds']:.2f}"))

    # DiskANN-style hybrid: per-query service time = compute + modeled SSD
    # reads, where a round's ≤E reads are issued concurrently (engine.
    # HybridEngine.io_time) — the per-round batching that motivated
    # DiskANN's beam width, and the e2e acceptance regime on this host.
    hyb = HybridEngine(g, codes, lut_fn, vectors=np.asarray(ds.base))
    service = {}
    for e in (1, 2, 4):
        qps, res = measure_qps(
            lambda q: hyb.search(q, k=k, h=h, expand=e), ds.queries,
            repeats=6)
        rec = recall_at_k(res.ids, gt, k)
        io_s = float(np.mean(np.asarray(hyb.io_time(res, expand=e))))
        sq = 1.0 / (1.0 / max(qps, 1e-9) + io_s)   # compute + serial IO
        service[e] = (sq, rec)
        emit((f"sharded/hybrid/h{h}/e{e}", 1e6 / max(sq, 1e-9),
              f"recall={rec:.3f};service_qps={sq:.1f};compute_qps={qps:.1f};"
              f"io_ms={io_s * 1e3:.2f};"
              f"rounds={float(np.mean(np.asarray(res.rounds))):.2f};"
              f"n_dist={float(np.mean(np.asarray(res.n_dist))):.1f};"
              f"hops={float(np.mean(np.asarray(res.hops))):.1f}"))
    s1, r1 = service[1]
    s4, r4 = service[4]
    emit(("sharded/hybrid/expand_speedup", 1e6 / max(s4, 1e-9),
          f"service_qps_e4_over_e1={s4 / max(s1, 1e-9):.2f};"
          f"recall_delta={r4 - r1:+.3f}"))

    # fault drill: kill shard 0, recall degrades, the query still answers.
    # Needs survivors — on a 1-device host (benchmarks/run.py default)
    # every shard would be dead and partial_merge rightly raises.
    if n_shards >= 2:
        alive = [s != 0 for s in range(n_shards)]
        res = graph_eng.search(ds.queries, k=k, h=h, alive=alive)
        emit(("sharded/graph/dead_shard0", 0.0,
              f"recall={recall_at_k(res.ids, gt, k):.3f};"
              f"rounds={float(np.mean(np.asarray(res.rounds))):.2f};"
              f"n_dist={float(np.mean(np.asarray(res.n_dist))):.1f};"
              f"alive={sum(alive)}/{n_shards}"))
    else:
        emit(("sharded/graph/dead_shard0", 0.0,
              "skipped=single_shard_host"))
    return rows


def main():
    rows = run()
    for r in rows:
        print(f"{r[0]},{r[1]:.2f},{r[2]}", flush=True)
    bad = [r for r in rows if "recall=" in r[2]
           and float(r[2].split("recall=")[1].split(";")[0]) <= 0]
    if bad:
        raise SystemExit(f"degenerate benchmark rows: {bad}")


if __name__ == "__main__":
    # the honest multi-shard configuration on a CPU host: 4 forced devices
    # (must be set before jax initializes its backend)
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")
    print("name,us_per_call,derived")
    main()
