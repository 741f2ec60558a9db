"""Resilience under injected faults: recall/QPS/p99 with honest degradation.

The numbers behind DESIGN.md §13's claim that every failure mode degrades
into a cheaper-but-honest answer instead of an error:

* ``resilience/fault_free/*``   — the healthy baseline (recall@10, QPS,
  p99 batch latency) every faulted row is compared against.
* ``resilience/deadline/r*``    — the compute-budget sweep: the beam hard-
  capped at B rounds returns best-so-far with per-query ``truncated``
  flags; recall falls monotonically with B, rounds never exceed it.
* ``resilience/degrade/L*``     — the degradation ladder (search/degrade
  .py): each rung sheds the next recall-for-compute knob; n_dist falls
  with the level.
* ``resilience/io_retry``       — transient-read faults on checkpoint
  restore, retried with exponential backoff + jitter (dist/retry.py):
  the restore succeeds, the row records observed injected faults and the
  closed-form expected retry time.
* ``resilience/snapshot_fallback`` — the newest snapshot's bytes are
  silently flipped (zip-consistent — only the manifest CRC32 can catch
  it); restore() falls back to the newest INTACT generation.
* ``resilience/crash_consolidate`` — an injected crash between the atomic
  snapshot and the in-memory swap; a restart restores the just-written
  generation.
* ``resilience/sharded/*``      — the seeded chaos acceptance drill on a
  forced 4-device host split (subprocess): the ISSUE plan {1 dead shard +
  1 straggler charged dead by the quorum deadline} at the same round
  budget as fault-free. Faulted recall is scored against the REACHABLE
  corpus (rows of the merged shards) — a dead shard's rows are gone by
  construction, and the honest claim is that the surviving shards still
  find their part.
* ``resilience/summary``        — the SLO row CI asserts on:
  ``recall_drop`` (faulted vs fault-free, equal deadline) must stay
  within 5 points.

Run as a section of the driver (emits BENCH_resilience.json):

    PYTHONPATH=src python -m benchmarks.run --only resilience

This is a CPU-only tool: the sharded drill runs in a child process forced
onto the CPU (``JAX_PLATFORMS=cpu`` with four host devices), because a
child cannot take a chip its parent holds. Its times are CPU-sandbox
numbers; the chip path is ``chip_smoke.py``.
"""

from __future__ import annotations

import os
import subprocess
import sys

# the chaos acceptance drill needs real shards to kill; forced 4-way host
# split in a subprocess, same pattern as tests/test_sharded_graph.py
_SUBPROC_CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.graphs.partition import build_partitioned_vamana, shard_bounds
from repro.pq.pq import train_pq
from repro.pq import base as pqbase
from repro.dist.fault import ChaosPlan, resolve_quorum
from repro.graphs.knn import knn_ids
from repro.search.engine import ShardedGraphEngine
from repro.search.metrics import live_ground_truth, recall_at_k

N, D, Q, K, TOPK, H, BUDGET = 2048, 32, 100, 16, 10, 32, 48
r = np.random.default_rng(7)
centers = r.normal(size=(16, D)) * 2.5
x = (centers[r.integers(0, 16, N)] + r.normal(size=(N, D))).astype(np.float32)
q = (centers[r.integers(0, 16, Q)] + r.normal(size=(Q, D))).astype(np.float32)
x, q = jnp.asarray(x), jnp.asarray(q)
model = train_pq(jax.random.PRNGKey(0), x, 8, K, iters=8)
codes = pqbase.encode(model, x)
lut_fn = lambda qq: pqbase.build_lut(model, qq)
pg = build_partitioned_vamana(jax.random.PRNGKey(1), x, 4, r=16, l=32)
eng = ShardedGraphEngine(pg, codes, lut_fn, vectors=x)
gt, _ = knn_ids(x, q, TOPK)
gt = np.asarray(gt)

free = eng.search(q, k=TOPK, h=H, max_rounds=BUDGET)
rec_free = recall_at_k(free.ids, gt, TOPK)
print(f"ROW sharded/fault_free recall={rec_free:.3f};"
      f"rounds={float(np.asarray(free.rounds).mean()):.2f};"
      f"truncated={float(np.asarray(free.truncated).mean()):.2f};"
      f"degraded={int(free.degraded)}")

plan = ChaosPlan(seed=7, dead_shards=(0,), straggler_shards=(1,),
                 straggler_latency_s=0.050, shard_latency_s=0.002)
deadline = 0.010                      # straggler (50ms) misses it
fault = eng.search(q, k=TOPK, h=H, max_rounds=BUDGET,
                   alive=plan.alive(4), deadline_s=deadline,
                   shard_latency_s=list(plan.latencies(4)))
dec = resolve_quorum(plan.alive(4), list(plan.latencies(4)), deadline, None)
bounds = shard_bounds(N, 4)
reach = np.concatenate([np.arange(lo, hi)
                        for s, (lo, hi) in enumerate(bounds) if dec.alive[s]])
gt_reach = live_ground_truth(np.asarray(x), reach, q, TOPK)
rec_fault = recall_at_k(fault.ids, gt_reach, TOPK)
assert fault.degraded, "dead+straggler must mark the answer degraded"
assert not np.isin(np.asarray(fault.ids),
                   np.setdiff1d(np.arange(N), reach)).any(), \
    "a merged answer leaked rows from a dead/straggler shard"
print(f"ROW sharded/chaos_dead0_straggler1 recall={rec_fault:.3f};"
      f"gt=reachable;merged={sum(dec.alive)}/4;deadline_ms=10;"
      f"rounds={float(np.asarray(fault.rounds).mean()):.2f};"
      f"degraded={int(fault.degraded)}")
print(f"SUMMARY recall_free={rec_free:.4f} recall_fault={rec_fault:.4f}")
"""


def _chaos_subprocess_rows():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))), "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    proc = subprocess.run([sys.executable, "-c", _SUBPROC_CODE],
                          capture_output=True, text=True, timeout=1200,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"chaos subprocess failed:\n{proc.stdout}\n"
                           f"{proc.stderr}")
    rows, summary = [], {}
    for line in proc.stdout.splitlines():
        if line.startswith("ROW "):
            _, name, derived = line.split(" ", 2)
            rows.append((f"resilience/{name}", 0.0, derived))
        elif line.startswith("SUMMARY "):
            for tok in line.split()[1:]:
                key, val = tok.split("=")
                summary[key] = float(val)
    return rows, summary


def run():
    import tempfile
    import time

    import numpy as np
    import jax
    import jax.numpy as jnp

    from benchmarks import common as C
    from repro.dist import checkpoint as ckpt
    from repro.dist.fault import (ChaosPlan, InjectedFailure,
                                  corrupt_snapshot)
    from repro.dist.retry import RetryPolicy, expected_retry_time_s
    from repro.index import BaseSegment, StreamingEngine
    from repro.index.segment import encode_codes
    from repro.graphs import build_vamana
    from repro.pq.pq import train_pq
    from repro.search.degrade import MAX_LEVEL, DegradationPolicy
    from repro.search.engine import HybridEngine, InMemoryEngine
    from repro.search.metrics import recall_at_k

    ds, gt, g = C.dataset(), C.ground_truth(), C.vamana_graph()
    codes, lut_fn, _ = C.quantizer("pq")
    k, h = 10, 32
    rows = []

    def timed(engine, repeats=3, chunk=64, **kw):
        """Chunked serving loop → (recall, qps, p99 batch ms, result)."""
        q = np.asarray(ds.queries)
        res = engine.search(jnp.asarray(q[:chunk]), k=k, **kw)  # warmup
        jax.block_until_ready(res.dists)
        lats, ids = [], []
        for _ in range(repeats):
            ids = []
            for s in range(0, len(q), chunk):
                t0 = time.perf_counter()
                res = engine.search(jnp.asarray(q[s:s + chunk]), k=k, **kw)
                jax.block_until_ready(res.dists)
                lats.append(time.perf_counter() - t0)
                ids.append(np.asarray(res.ids))
        rec = recall_at_k(np.concatenate(ids), gt, k)
        qps = chunk / max(float(np.mean(lats)), 1e-12)
        p99 = float(np.percentile(lats, 99)) * 1e3
        return rec, qps, p99, res

    # ---- fault-free baseline --------------------------------------------
    mem = InMemoryEngine(g, codes, lut_fn)
    rec0, qps0, p99_0, res0 = timed(mem, h=h)
    rounds0 = float(np.asarray(res0.rounds).mean())
    rows.append((f"resilience/fault_free/h{h}", 1e6 / max(qps0, 1e-9),
                 f"recall={rec0:.3f};qps={qps0:.1f};p99_ms={p99_0:.2f};"
                 f"rounds={rounds0:.2f}"))

    # ---- deadline sweep: hard round budgets, honest truncation ----------
    for budget in (2, 4, 8, 16):
        rec, qps, p99, res = timed(mem, h=h, max_rounds=budget)
        rmax = int(np.asarray(res.rounds).max())
        if rmax > budget:
            raise SystemExit(f"budget violated: rounds {rmax} > {budget}")
        rows.append((f"resilience/deadline/r{budget}",
                     1e6 / max(qps, 1e-9),
                     f"recall={rec:.3f};qps={qps:.1f};p99_ms={p99:.2f};"
                     f"budget={budget};rounds_max={rmax};"
                     f"truncated="
                     f"{float(np.asarray(res.truncated).mean()):.2f}"))

    # ---- degradation ladder ---------------------------------------------
    hyb = HybridEngine(g, codes, lut_fn, vectors=np.asarray(ds.base))
    policy = DegradationPolicy()
    for lvl in range(MAX_LEVEL + 1):
        kw = policy.apply(hyb, lvl, h=h, expand=4, entries=8,
                          prune_eps=0.1)
        rec, qps, p99, res = timed(hyb, **kw)
        rows.append((f"resilience/degrade/L{lvl}", 1e6 / max(qps, 1e-9),
                     f"recall={rec:.3f};qps={qps:.1f};p99_ms={p99:.2f};"
                     f"n_dist={float(np.asarray(res.n_dist).mean()):.1f}"))

    # ---- snapshot drills: a tiny self-contained streaming sandbox -------
    r = np.random.default_rng(2)
    xs = r.normal(size=(600, 16)).astype(np.float32)
    sm = train_pq(jax.random.PRNGKey(3), jnp.asarray(xs), 4, 16, iters=6)
    sg = build_vamana(jax.random.PRNGKey(4), jnp.asarray(xs), r=8, l=24)
    seg = BaseSegment(graph=sg,
                      codes=jnp.asarray(encode_codes(sm, xs, "u8")),
                      vectors=jnp.asarray(xs), layout="u8")

    with tempfile.TemporaryDirectory() as d:
        # transient-I/O retry: every read flaky at p=0.3, restore retried
        eng = StreamingEngine(seg, sm, delta_capacity=64)
        eng.insert(r.normal(size=(16, 16)).astype(np.float32))
        eng.consolidate(ckpt_dir=d)
        faults = {"n": 0}
        base_hook = ChaosPlan(seed=11, io_fault_p=0.3).io_fault()

        def counting_hook(path):
            try:
                base_hook(path)
            except Exception:
                faults["n"] += 1
                raise
        pol = RetryPolicy(max_attempts=6, base_delay_s=1e-4,
                          max_delay_s=1e-3)
        ckpt.set_io_fault_hook(counting_hook)
        try:
            t0 = time.perf_counter()
            eng2 = StreamingEngine.restore(d, delta_capacity=64, retry=pol)
            wall = time.perf_counter() - t0
        finally:
            ckpt.set_io_fault_hook(None)
        exp = expected_retry_time_s(pol, 0.0, 0.3)
        rows.append(("resilience/io_retry", wall * 1e6,
                     f"io_fault_p=0.3;injected={faults['n']};"
                     f"restored_gen={eng2.generation};"
                     f"expected_retry_s={exp:.4f}"))

        # silent corruption: newest generation flips a byte, restore falls
        # back to the newest intact one
        eng.insert(r.normal(size=(8, 16)).astype(np.float32))
        eng.consolidate(ckpt_dir=d)               # gen 2, intact
        newest = corrupt_snapshot(d, seed=5)
        falls = []
        t0 = time.perf_counter()
        eng3 = StreamingEngine.restore(
            d, delta_capacity=64,
            on_fallback=lambda gen, e: falls.append(gen))
        wall = time.perf_counter() - t0
        if eng3.generation >= newest:
            raise SystemExit("restore served a corrupted generation")
        rows.append(("resilience/snapshot_fallback", wall * 1e6,
                     f"corrupted_gen={newest};landed_gen={eng3.generation};"
                     f"fallbacks={len(falls)}"))

    with tempfile.TemporaryDirectory() as d:
        # crash between snapshot and swap: restart restores the NEW gen
        eng = StreamingEngine(seg, sm, delta_capacity=64)
        eng.insert(r.normal(size=(16, 16)).astype(np.float32))
        plan = ChaosPlan(seed=0, crash_phase="consolidate")
        try:
            eng.consolidate(ckpt_dir=d, chaos=plan.consolidate_hook())
            raise SystemExit("chaos crash did not fire")
        except InjectedFailure:
            pass
        eng4 = StreamingEngine.restore(d, delta_capacity=64)
        rows.append(("resilience/crash_consolidate", 0.0,
                     f"restored_gen={eng4.generation};"
                     f"live={eng4.n_live};crash=post_snapshot"))

    # ---- all-in-storage fallback drill (DESIGN.md §14) ------------------
    # the storage tier's answer to snapshot_fallback: gen 1's segment
    # header is corrupted on disk, DiskEngine.open falls back to the
    # newest INTACT generation and keeps serving — through flaky reads
    # (io_fault_p=0.2, retried per worker chunk) on top
    import dataclasses as _dc

    from repro.storage import (DiskEngine, corrupt_header, segment_path,
                               write_segment)

    with tempfile.TemporaryDirectory() as d:
        write_segment(d, seg, model=sm)                       # gen 0, intact
        write_segment(d, _dc.replace(seg, generation=1), model=sm)
        corrupt_header(segment_path(d, 1), seed=5)
        falls = []
        plan = ChaosPlan(seed=9, io_fault_p=0.2)
        pol = RetryPolicy(max_attempts=6, base_delay_s=1e-4,
                          max_delay_s=1e-3)
        t0 = time.perf_counter()
        with DiskEngine.open(d, cache_records=256, retry=pol,
                             fault_hook=plan.io_fault(),
                             on_fallback=lambda gen, e: falls.append(gen)
                             ) as deng:
            res = deng.search(jnp.asarray(xs[:32]), k=5, h=16)
            wall = time.perf_counter() - t0
            if deng.generation != 0:
                raise SystemExit("disk fallback served the corrupted "
                                 "generation")
            if falls != [1]:
                raise SystemExit(f"disk fallback skipped {falls}, "
                                 f"expected [1]")
            ids = np.asarray(res.ids)
            if ids.max() >= seg.n or not np.isfinite(
                    np.asarray(res.dists)).all():
                raise SystemExit("disk fallback returned invalid answers")
            self_top1 = float((ids[:, 0] == np.arange(32)).mean())
            io = deng.last_io
        rows.append(("resilience/disk_fallback", wall * 1e6,
                     f"corrupted_gen=1;landed_gen=0;fallbacks={len(falls)};"
                     f"self_top1={self_top1:.2f};io_fault_p=0.2;"
                     f"retries={io['n_retries']};"
                     f"cache_hit_rate={io['cache_hit_rate']:.2f}"))

    # ---- the seeded 4-shard chaos acceptance drill ----------------------
    sub_rows, summary = _chaos_subprocess_rows()
    rows.extend(sub_rows)
    drop = summary["recall_free"] - summary["recall_fault"]
    rows.append(("resilience/summary", 0.0,
                 f"recall_free={summary['recall_free']:.4f};"
                 f"recall_fault={summary['recall_fault']:.4f};"
                 f"recall_drop={drop:.4f};slo_drop_max=0.05;"
                 f"p99_free_ms={p99_0:.2f}"))
    return rows


def main():
    print("name,us_per_call,derived")
    for row in run():
        print(f"{row[0]},{row[1]:.2f},{row[2]}", flush=True)


if __name__ == "__main__":
    main()
