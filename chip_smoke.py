#!/usr/bin/env python3
"""Chip smoke test: the RPQ pipeline end to end on one TPU, at SIFT1M width.

    python chip_smoke.py                # one chip (the default)
    python chip_smoke.py --four-chips   # the graph-routed sharded path

One process drives the chip (a child process could not take a chip this
one holds), through the code behind ``python -m repro.launch.train`` and
``python -m repro.launch.serve``:

1. data: the ``sift`` synthetic corpus, 128-d, with a training subset of
   half the base and 1000 queries, made from a seed. N is cut from
   SIFT1M's 1M rows to 200k (``ONE_CHIP_N``): on one v5e the Vamana build
   took 597 s for the 500k training subset alone, so the two builds of a
   1M run do not fit the 1200 s a smoke may take;
2. graphs: Vamana over the training subset and over the base, degree 64;
3. training: RPQ with M=16, K=256 for 20 steps, routing features
   refreshed every 10;
4. serving: the ``hybrid`` and ``memory`` scenarios (u8 codes,
   ``backend="auto"``, so the compiled Pallas kernels on the chip), each
   checked against the same engine on ``backend="ref"``;
5. fs4: a K=16, M=32 PQ served through ``InMemoryEngine`` in the packed
   layout, checked the same way.

``--four-chips`` instead runs ``ShardedGraphEngine`` over four chips and
``InMemoryEngine`` on chip 0 over the same corpus (``sift``, 20k rows) and
queries, and checks that their recall@10 are within 0.05 and that every
shard holds its rows on its own chip and contributes answers.

The script exits non-zero, printing no result, when JAX finds no TPU, when
the repository's ``src/`` is not next to it, or when any phase or check
fails. Otherwise the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times printed on the way are host wall clock and informational.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, "runs", "chip_smoke")
FULL_N = 1_000_000          # SIFT1M rows; scale 10 of the 100k sift spec
ONE_CHIP_N = 200_000        # the cut: two Vamana builds fit 1200 s
FOUR_CHIP_N = 20_000        # four chips cost 4x: the engines, not the build
R, L = 64, 64               # graph degree (paper) and build list size
M, K = 16, 256              # RPQ subspaces × codewords (paper, SIFT1M)
FS4_M = 32                  # fs4: K=16 nibble codes at the same 16 B/vector
TOPK = 10
RTOL, ATOL = 1e-5, 1e-6     # f32 agreement of auto vs ref distances


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def timed(label: str, fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    log(f"time {label}: {time.perf_counter() - t0:.1f} s "
        f"(host wall clock, informational)")
    return out


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)
    log(f"check ok: {msg}")


def agreement(a, b):
    """Per-query agreement of two SearchResults of the same engine and
    queries: distances equal rank by rank within RTOL/ATOL, and ids equal
    up to tied distances (an id in one answer but not the other must sit
    at the k-th distance; equal ids may swap ranks only among ties)."""
    import numpy as np

    a_ids, b_ids = np.asarray(a.ids), np.asarray(b.ids)
    a_d, b_d = np.asarray(a.dists), np.asarray(b.dists)
    tol = ATOL + RTOL * np.abs(b_d)
    ok = (np.abs(a_d - b_d) <= tol).all(axis=1)
    for q in np.flatnonzero(ok):
        for ids, d, other in ((a_ids[q], a_d[q], b_ids[q]),
                              (b_ids[q], b_d[q], a_ids[q])):
            lone = ~np.isin(ids, other)
            if (np.abs(d[lone] - d[-1]) > tol[q, -1]).any():
                ok[q] = False
    return ok


def compare_backends(name: str, engine, res_auto, queries, gt, **skw):
    """Search again with the same engine on backend="ref" and hold the
    device result to it. Returns (recall auto, recall ref)."""
    from repro.search.metrics import recall_at_k

    ref_engine = dataclasses.replace(engine, backend="ref")
    res_ref = ref_engine.search(queries, k=TOPK, **skw)
    ok = agreement(res_auto, res_ref)
    rec_auto = recall_at_k(res_auto.ids, gt, TOPK)
    rec_ref = recall_at_k(res_ref.ids, gt, TOPK)
    log(f"{name}: recall@{TOPK} auto={rec_auto:.4f} ref={rec_ref:.4f}; "
        f"auto==ref on {int(ok.sum())}/{ok.size} queries "
        f"(distances within rtol={RTOL}, atol={ATOL}; ids up to ties)")
    check(bool(ok.all()), f"{name}: backend=auto agrees with backend=ref")
    return rec_auto, rec_ref


def one_chip(scale: float) -> None:
    import jax
    import numpy as np

    from repro.data import load_dataset
    from repro.kernels import ops
    from repro.launch import serve, train
    from repro.launch.train import build_or_load_graph
    from repro.pq import base as pqbase
    from repro.pq import pack, train_pq_fs4
    from repro.search.engine import InMemoryEngine
    from repro.search.metrics import recall_at_k

    n = int(100_000 * scale)
    cut = "" if n == FULL_N else (
        f" (cut from SIFT1M's {FULL_N}: two Vamana builds of 1M rows do "
        f"not fit the smoke's 1200 s)")
    shutil.rmtree(WORK, ignore_errors=True)
    ckpt_dir = os.path.join(WORK, "ckpt")

    ds = timed("data synth", load_dataset, "sift", scale=scale)
    check(ds.base.shape == (n, 128), f"base is {n} x 128")
    log(f"N={ds.base.shape[0]}{cut} d={ds.dim} train={ds.train.shape[0]} "
        f"queries={ds.queries.shape[0]} M={M} K={K} R={R} L={L}")

    # graphs through the trainer's own cache, so train.run loads them
    kg, _ = jax.random.split(jax.random.PRNGKey(0))
    timed("graph build (training subset)", build_or_load_graph, kg, ds.train,
          os.path.join(ckpt_dir, "graph.npz"), R, L)
    graph = timed("graph build (base)", build_or_load_graph, kg, ds.base,
                  os.path.join(ckpt_dir, "graph_base.npz"), R, L)

    targs = train.parser().parse_args([
        "--dataset", "sift", "--scale", str(scale), "--steps", "20",
        "--m", str(M), "--k", str(K), "--refresh-every", "10",
        "--graph-r", str(R), "--graph-l", str(L), "--log-every", "5",
        "--checkpoint-every", "10", "--ckpt-dir", ckpt_dir])
    out = timed("train (20 steps + hybrid eval)", train.run, targs)
    losses = [h["total"] for h in out["history"]]
    log(f"train loss by logged step: "
        + ", ".join(f"{h['step']}:{h['total']:.4f}" for h in out["history"]))
    check(len(losses) > 0 and bool(np.all(np.isfinite(losses))),
          f"training loss is finite after {targs.steps} steps "
          f"(last {losses[-1] if losses else None})")

    for scenario in ("hybrid", "memory"):
        sargs = serve.parser().parse_args([
            "--ckpt-dir", ckpt_dir, "--scenario", scenario, "--codes", "u8",
            "--graph-r", str(R), "--graph-l", str(L), "--k", str(TOPK)])
        t0 = time.perf_counter()
        res = serve.run(sargs)
        total = time.perf_counter() - t0
        batch = res["queries"].shape[0] / res["qps"]
        log(f"time serve {scenario}: {total:.1f} s = set-up (load, encode, "
            f"compile) {total - 4 * batch:.1f} s + 4 batches of "
            f"{res['queries'].shape[0]} queries at {batch * 1e3:.1f} ms "
            f"steady state (host wall clock, informational)")
        rec_auto, rec_ref = compare_backends(
            scenario, res["engine"], res["result"], res["queries"],
            res["gt"], **res["search_kwargs"])
        if scenario == "hybrid":
            check(rec_auto >= rec_ref,
                  f"hybrid recall@{TOPK} {rec_auto:.4f} >= ref recall "
                  f"{rec_ref:.4f}")
        gt = res.pop("gt")
        del res

    # fs4: K=16 PQ, packed codes + uint8 LUTs, same corpus and graph
    model = timed("fs4 PQ train (M=32, K=16)", train_pq_fs4,
                  jax.random.PRNGKey(1), ds.train, FS4_M)
    codes = pack.pack_codes(pqbase.encode(model, ds.base))
    engine = InMemoryEngine(
        graph, codes, lambda q: pqbase.build_lut(model, q, quantize=True))
    for label in ("compile + 1 batch", "steady-state batch"):
        res = timed(f"serve fs4 memory ({label})", lambda: (
            jax.block_until_ready(engine.search(ds.queries, k=TOPK))))
    compare_backends("fs4 memory", engine, res, ds.queries, gt)
    log(f"fs4: {codes.shape[1]} packed bytes/vector, recall@{TOPK}="
        f"{recall_at_k(res.ids, gt, TOPK):.4f}")

    ran = sorted(ops.TRACED_KERNELS)
    log(f"Pallas kernels traced on the device path: {', '.join(ran)}")
    need = {"pq_pairwise", "hop_adc", "hop_adc_fs"}
    check(need <= set(ran), f"compiled kernels ran: {sorted(need)}")


def four_chips(scale: float) -> None:
    import jax
    import numpy as np

    from repro.data import load_dataset
    from repro.graphs.knn import knn_ids
    from repro.launch.serve import build_or_load_partitioned_graph
    from repro.launch.train import build_or_load_graph
    from repro.pq import base as pqbase
    from repro.pq import train_pq
    from repro.search.engine import InMemoryEngine, ShardedGraphEngine
    from repro.search.metrics import recall_at_k

    devices = jax.devices()
    check(len(devices) == 4, f"four devices visible ({len(devices)})")
    shutil.rmtree(WORK, ignore_errors=True)
    ds = timed("data synth", load_dataset, "sift", scale=scale)
    log(f"N={ds.base.shape[0]} d={ds.dim} queries={ds.queries.shape[0]} "
        f"M={M} K={K} R={R} L={L} shards=4")
    model = timed("PQ train", train_pq, jax.random.PRNGKey(0), ds.train,
                  M, K)
    codes = pqbase.encode(model, ds.base)
    lut_fn = lambda q: pqbase.build_lut(model, q)   # noqa: E731
    gt = np.asarray(knn_ids(ds.base, ds.queries, TOPK)[0])
    key = jax.random.PRNGKey(0)
    pg = timed("partitioned graph build (4 shards)",
               build_or_load_partitioned_graph, key, ds.base,
               os.path.join(WORK, "graph_part4.npz"), 4, R, L)
    graph = timed("graph build (one chip)", build_or_load_graph, key,
                  ds.base, os.path.join(WORK, "graph_base.npz"), R, L)

    sharded = ShardedGraphEngine(pg, codes, lut_fn)
    res_s = timed("sharded-graph search (compile + 1 batch)",
                  sharded.search, ds.queries, k=TOPK)
    single = InMemoryEngine(graph, jax.device_put(codes, devices[0]),
                            lut_fn)
    res_1 = timed("in-memory search on chip 0 (compile + 1 batch)",
                  single.search, ds.queries, k=TOPK)
    jax.block_until_ready(res_1.dists)
    rec_s = recall_at_k(res_s.ids, gt, TOPK)
    rec_1 = recall_at_k(res_1.ids, gt, TOPK)
    log(f"recall@{TOPK}: sharded-graph (4 chips)={rec_s:.4f} "
        f"in-memory (chip 0)={rec_1:.4f}")
    check(abs(rec_s - rec_1) <= 0.05,
          f"sharded-graph recall within 0.05 of one chip "
          f"({rec_s - rec_1:+.4f})")

    placed = {s.device.id: s.data.shape[0]
              for s in sharded.codes.addressable_shards}
    log(f"code shards by device id: {placed}")
    check(sorted(placed) == sorted(d.id for d in devices),
          "every chip holds its own shard of the codes")
    ids = np.asarray(res_s.ids)
    per_shard = np.bincount(ids[ids >= 0] // pg.n_local, minlength=4)
    log(f"answers (top-{TOPK} ids) contributed per shard: "
        f"{per_shard.tolist()}; mean hops summed over shards "
        f"{float(np.asarray(res_s.hops).mean()):.1f}")
    check(bool((per_shard > 0).all()), "every shard contributes answers")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-graph phase on four chips "
                    "and the one-chip engine it is compared with")
    ap.add_argument("--scale", type=float, default=None,
                    help="sift corpus scale (10 = SIFT1M's 1M rows); default "
                    f"{ONE_CHIP_N} rows on one chip, {FOUR_CHIP_N} with "
                    "--four-chips. Only N changes")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU found (JAX default backend is "
              f"{backend!r})", file=sys.stderr)
        return 2
    cache_dir = compile_cache.enable()
    dev = jax.devices()[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    try:
        if args.four_chips:
            four_chips(args.scale or FOUR_CHIP_N / 100_000)
        else:
            one_chip(args.scale or ONE_CHIP_N / 100_000)
    except Exception as e:  # noqa: BLE001 - every phase failure fails
        import traceback
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
