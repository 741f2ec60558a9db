"""Fault-tolerant end-to-end RPQ training driver (the paper's pipeline).

    PYTHONPATH=src python -m repro.launch.train \
        --dataset sift-small --steps 400 --ckpt-dir runs/rpq \
        --checkpoint-every 50 [--fail-at-step 120] [--resume]

Builds (or loads) the dataset + Vamana PG, then runs the multi-feature
joint training with atomic checkpointing; on restart (--resume or the
supervise() wrapper after an injected failure) it continues from the
latest checkpoint — the restart is bit-identical (tests/test_dist.py).
In-process callers build the arguments with :func:`parser` and call
:func:`run`.
"""

from __future__ import annotations

import argparse
import os

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import RPQConfig, TrainConfig
from repro.core import trainer as T
from repro.data import load_dataset
from repro.dist import checkpoint as ckpt
from repro.dist.fault import FailureInjector, supervise
from repro.graphs import build_vamana
from repro.launch import compile_cache
from repro.pq import base as pqbase
from repro.search.engine import HybridEngine
from repro.search.metrics import recall_at_k
from repro.graphs.knn import knn_ids


def build_or_load_graph(key, x, cache_path: str, r: int, l: int):
    """Vamana graph over x, cached at ``cache_path``. A cached graph over a
    different row count or degree is rebuilt, never reused."""
    if cache_path and os.path.exists(cache_path):
        z = np.load(cache_path)
        if z["neighbors"].shape == (x.shape[0], r):
            from repro.graphs.adjacency import Graph
            return Graph(neighbors=jnp.asarray(z["neighbors"]),
                         medoid=jnp.asarray(z["medoid"]))
    g = build_vamana(key, x, r=r, l=l)
    if cache_path:
        os.makedirs(os.path.dirname(cache_path) or ".", exist_ok=True)
        np.savez(cache_path, neighbors=np.asarray(g.neighbors),
                 medoid=np.asarray(g.medoid))
    return g


def run(args) -> dict:
    key = jax.random.PRNGKey(args.seed)
    ds = load_dataset(args.dataset, scale=args.scale)
    x = ds.train
    kg, kt = jax.random.split(key)
    graph = build_or_load_graph(
        kg, x, os.path.join(args.ckpt_dir, "graph.npz"), args.graph_r,
        args.graph_l)

    cfg = RPQConfig(dim=x.shape[1], m=args.m, k=args.k)
    tcfg = TrainConfig(steps=args.steps, refresh_every=args.refresh_every,
                       triplet_batch=args.batch, routing_batch=args.batch,
                       routing_pool_queries=args.routing_queries,
                       log_every=args.log_every)

    params = None
    opt_state = None
    start_step = 0
    if args.resume or ckpt.latest_step(args.ckpt_dir) is not None:
        step = ckpt.latest_step(args.ckpt_dir)
        if step is not None:
            params_t = T.init_rpq(jax.random.PRNGKey(0), cfg, x[:512],
                                  kmeans_iters=1)  # template only
            from repro.common import adam, one_cycle
            opt_t = adam(one_cycle(tcfg.lr, tcfg.steps)).init(params_t)
            state = ckpt.restore(args.ckpt_dir, step,
                                 like={"params": params_t, "opt": opt_t})
            params, opt_state, start_step = (state["params"], state["opt"],
                                             state["step"] + 1)
            print(f"[train] resumed from step {state['step']}")

    injector = FailureInjector(fail_at_step=args.fail_at_step)
    args.fail_at_step = None  # one-shot: a restarted (replaced) node must
    #                           not re-crash at the same step

    def checkpoint_cb(step, p, o):
        injector.maybe_fail(step)
        if step % args.checkpoint_every == 0 and step > 0:
            ckpt.save(args.ckpt_dir, step, keep=args.keep, params=p, opt=o,
                      extra={"dataset": args.dataset, "scale": args.scale,
                             "m": args.m, "k": args.k})

    state = T.fit(kt, cfg, tcfg, x, graph, params=params,
                  opt_state=opt_state, start_step=start_step,
                  checkpoint_cb=checkpoint_cb, verbose=not args.quiet)
    ckpt.save(args.ckpt_dir, tcfg.steps, keep=args.keep, params=state.params,
              opt=state.opt_state,
              extra={"final": True, "dataset": args.dataset,
                     "scale": args.scale, "m": args.m, "k": args.k})

    # final evaluation: hybrid (DiskANN) serving on the base set
    model = T.to_model(cfg, state.params)
    codes = pqbase.encode(model, ds.base)
    engine = HybridEngine(graph if ds.base.shape[0] == x.shape[0] else
                          build_or_load_graph(kg, ds.base,
                                              os.path.join(args.ckpt_dir, "graph_base.npz"),
                                              args.graph_r, args.graph_l),
                          codes, lambda q: pqbase.build_lut(model, q),
                          vectors=ds.base)
    gt, _ = knn_ids(ds.base, ds.queries, 10)
    res = engine.search(ds.queries, k=10, h=args.beam)
    rec = recall_at_k(res.ids, gt, 10)
    print(f"[train] final recall@10={rec:.4f} mean hops={float(res.hops.mean()):.1f}")
    return {"recall": rec, "history": state.history}


def parser() -> argparse.ArgumentParser:
    """The command line of ``python -m repro.launch.train``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="sift-small")
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--m", type=int, default=8)
    ap.add_argument("--k", type=int, default=64)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--routing-queries", type=int, default=64)
    ap.add_argument("--refresh-every", type=int, default=100)
    ap.add_argument("--graph-r", type=int, default=24)
    ap.add_argument("--graph-l", type=int, default=48)
    ap.add_argument("--beam", type=int, default=48)
    ap.add_argument("--ckpt-dir", default="runs/rpq")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--log-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at-step", type=int, default=None,
                    help="inject a crash at this step (fault-tolerance demo)")
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("--quiet", action="store_true")
    return ap


def main():
    args = parser().parse_args()
    compile_cache.enable()

    def attempt():
        return run(args)

    result, restarts = supervise(
        attempt, max_restarts=args.max_restarts,
        on_restart=lambda n, e: print(f"[supervise] restart {n} after: {e}"))
    if restarts:
        print(f"[supervise] completed after {restarts} restart(s)")
    return result


if __name__ == "__main__":
    main()
