"""Multi-feature joint training module (paper §6 + Fig. 2 pipeline).

The training loop alternates:
  (1) feature extraction with the CURRENT quantizer — triplets are cheap and
      re-sampled every step; routing features require fresh compact codes +
      beam searches, so they are re-extracted every `refresh_every` steps
      (the pipeline loop in the paper's Fig. 2);
  (2) jitted joint-loss Adam steps (one-cycle LR, lr=1e-3 — paper §6).

Distribution: `data_parallel=True` wraps the step in shard_map over the
`data` axis — triplet/routing examples are sharded, gradients all-reduced
(optionally int8-compressed, dist/compression.py). The quantizer itself is
tiny (≤ a few MB) and stays replicated, exactly like the serving layout.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.common import adam, one_cycle, clip_by_global_norm
from repro.core import features as F
from repro.core import losses as L
from repro.core import quantizer as Q
from repro.dist import compression as comp
from repro.dist import sharding as shd
from repro.graphs.adjacency import Graph
from repro.pq import base as pqbase
from repro.pq.pq import train_pq


@dataclasses.dataclass
class TrainConfig:
    steps: int = 1000
    lr: float = 1e-3                # paper §6
    triplet_batch: int = 512
    routing_batch: int = 512
    routing_pool_queries: int = 256  # queries per routing-feature refresh
    refresh_every: int = 100
    beam_h: int = 16                # h candidates per decision (Def. 6)
    n_hops: int = 2                 # Alg. 1 propagation depth
    k_pos: int = 10
    k_neg: int = 30
    margin: float = 1.0
    fixed_alpha: Optional[float] = None
    grad_clip: float = 1.0
    use_routing: bool = True        # ablations: RPQ w/ N only
    use_neighborhood: bool = True   # ablations: RPQ w/ R only
    log_every: int = 50
    # distribution (dist/sharding + optional dist/compression):
    data_parallel: bool = False     # shard_map the step over the data axis
    compress_grads: bool = False    # int8 + error feedback before all-reduce


@dataclasses.dataclass
class TrainState:
    params: Q.RPQParams
    opt_state: object
    step: int
    history: list


def init_rpq(key: jax.Array, cfg: Q.RPQConfig, x: jax.Array,
             kmeans_iters: int = 15) -> Q.RPQParams:
    """K-means-initialized RPQ (R = I start ⇒ classic PQ as the origin)."""
    model = train_pq(key, x, cfg.m, cfg.k, iters=kmeans_iters)
    return Q.init_params(cfg, model.codebooks)


def _make_loss_fn(cfg: Q.RPQConfig, tcfg: TrainConfig):
    def loss_fn(params, x, trip, route, key):
        kt, kr = jax.random.split(key)
        zero = jnp.zeros((), jnp.float32)
        ln = (L.neighborhood_loss(cfg, params, x, trip, kt, margin=tcfg.margin)
              if tcfg.use_neighborhood else zero)
        lr_ = (L.routing_loss(cfg, params, x, route, kr)
               if tcfg.use_routing else zero)
        if tcfg.fixed_alpha is not None or not (tcfg.use_routing and tcfg.use_neighborhood):
            alpha = jnp.asarray(
                1.0 if tcfg.fixed_alpha is None else tcfg.fixed_alpha, jnp.float32)
            total = lr_ + alpha * ln
        else:
            s = params.log_alpha
            alpha = jnp.exp(-s)
            total = lr_ + alpha * ln + s
        return total, L.LossReport(total, lr_, ln, alpha)

    return loss_fn


def make_train_step(cfg: Q.RPQConfig, tcfg: TrainConfig, optimizer):
    """Returns the jitted (params, opt_state, x, trip, route, key) step."""
    loss_fn = _make_loss_fn(cfg, tcfg)

    @jax.jit
    def step(params, opt_state, x, trip, route, key):
        (_, report), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, x, trip, route, key)
        if not cfg.learn_rotation:
            grads = grads._replace(theta=jnp.zeros_like(grads.theta))
        grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, report, gnorm

    return step


def _dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names) \
        or tuple(mesh.axis_names)


def default_dp_mesh():
    """1-D data mesh over every local device (the serving row layout's
    training twin); built inline so pure-library users never touch launch/."""
    return jax.make_mesh((len(jax.devices()),), ("data",))


def init_dp_comp_state(params, n_dp: int):
    """Per-device error-feedback residuals: leading (n_dp,) axis, sharded
    over the data axis by the dp step (each replica keeps its OWN residual —
    error feedback is local by construction)."""
    return jax.tree.map(
        lambda p: jnp.zeros((n_dp,) + jnp.shape(p), jnp.float32), params)


def make_dp_train_step(cfg: Q.RPQConfig, tcfg: TrainConfig, optimizer, mesh,
                       compress: bool = False):
    """Data-parallel step (the docstring's `data_parallel=True` path).

    shard_map over the data axes: triplet/routing batches are row-sharded,
    the base set x and the (tiny) quantizer params stay replicated, local
    gradients are optionally int8-compressed with error feedback
    (dist/compression) and then mean-all-reduced — after which the update
    is replica-identical, exactly like the serving layout.

    Signature: (params, opt_state, comp_state, x, trip, route, key) →
    (params, opt_state, comp_state, report, gnorm). ``comp_state`` is the
    (n_dp, ...) error-feedback pytree from :func:`init_dp_comp_state`
    (pass ``{}`` when ``compress=False``).
    """
    loss_fn = _make_loss_fn(cfg, tcfg)
    dp = _dp_axes(mesh)

    def local_step(params, opt_state, comp_state, x, trip, route, key):
        # decorrelate per-shard Gumbel noise; one global key per step
        key = jax.random.fold_in(key, shd.flat_shard_index(mesh, dp))
        (_, report), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, x, trip, route, key)
        if not cfg.learn_rotation:
            grads = grads._replace(theta=jnp.zeros_like(grads.theta))
        if compress:
            local_state = jax.tree.map(lambda e: e[0], comp_state)
            (q, s), local_state = comp.compress_tree(grads, local_state)
            grads = comp.decompress_tree((q, s))   # ≙ wire format int8+scale
            comp_state = jax.tree.map(lambda e: e[None], local_state)
        grads = jax.lax.pmean(grads, dp)
        report = jax.tree.map(lambda v: jax.lax.pmean(v, dp), report)
        grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, comp_state, report, gnorm

    pb = P(dp)
    step = jax.shard_map(local_step, mesh=mesh,
                     in_specs=(P(), P(), pb, P(), pb, pb, P()),
                     out_specs=(P(), P(), pb, P(), P()))
    return jax.jit(step)


def fit(key: jax.Array, cfg: Q.RPQConfig, tcfg: TrainConfig, x: jax.Array,
        graph: Graph, *, params: Optional[Q.RPQParams] = None,
        checkpoint_cb: Optional[Callable] = None,
        start_step: int = 0, opt_state=None, mesh=None,
        tombstones=None, verbose: bool = True) -> TrainState:
    """End-to-end RPQ training (paper Fig. 2). Returns the final TrainState.

    checkpoint_cb(step, params, opt_state) — wired to dist/checkpoint.py by
    launch/train.py; pure library users can ignore it. With
    ``tcfg.data_parallel`` the jitted step runs under shard_map on ``mesh``
    (default: every local device) — see :func:`make_dp_train_step`.

    ``tombstones`` (optional uint32 deleted-id bitset words over [0, n),
    the streaming index's Tombstones layout) makes the whole feature loop
    churn-aware — this is the codebook-refresh path (DESIGN.md §12):
    triplet anchors and routing queries are drawn from LIVE vertices only,
    and the bitset threads into both samplers so no dead id reaches any
    loss term. Warm-start via ``params=`` to refine the serving quantizer
    instead of training from the k-means origin.
    """
    n = x.shape[0]
    key, kinit = jax.random.split(key)
    live_ids, ts_dev = None, None
    if tombstones is not None:
        words = np.asarray(tombstones, np.uint32)
        ids = np.arange(n, dtype=np.int64)
        dead = ((words[ids >> 5] >> (ids & 31).astype(np.uint32)) & 1
                ).astype(bool)
        live_np = np.flatnonzero(~dead)
        if live_np.size == 0:
            raise ValueError("fit: every vertex is tombstoned — nothing "
                             "live to sample features from")
        live_ids = jnp.asarray(live_np, jnp.int32)
        ts_dev = jnp.asarray(words)
    if params is None:
        params = init_rpq(kinit, cfg, x)
    optimizer = adam(one_cycle(tcfg.lr, tcfg.steps))
    if opt_state is None:
        opt_state = optimizer.init(params)
    comp_state = {}
    n_dp = 1
    if tcfg.data_parallel:
        mesh = mesh if mesh is not None else default_dp_mesh()
        for a in _dp_axes(mesh):
            n_dp *= mesh.shape[a]
        if tcfg.triplet_batch % n_dp or tcfg.routing_batch % n_dp:
            raise ValueError(
                f"data_parallel: triplet_batch={tcfg.triplet_batch} and "
                f"routing_batch={tcfg.routing_batch} must divide the "
                f"{n_dp}-way data axis")
        step_fn = make_dp_train_step(cfg, tcfg, optimizer, mesh,
                                     compress=tcfg.compress_grads)
        if tcfg.compress_grads:
            comp_state = init_dp_comp_state(params, n_dp)
    else:
        step_fn = make_train_step(cfg, tcfg, optimizer)

    routing_pool: Optional[F.RoutingBatch] = None
    history = []
    t0 = time.time()
    for step in range(start_step, tcfg.steps):
        # fold_in (not sequential splits): a resumed run re-derives the SAME
        # per-step keys as the uninterrupted run (fault-tolerance semantics)
        k1, k2, k3, k4, k5 = jax.random.split(
            jax.random.fold_in(key, step), 5)
        # ---- feature extraction (paper Fig. 2 outer loop) ----
        if tcfg.use_routing and (routing_pool is None
                                 or step % tcfg.refresh_every == 0):
            model = to_model(cfg, params)
            codes = pqbase.encode(model, x)
            if live_ids is None:
                qidx = jax.random.choice(k1, n, (tcfg.routing_pool_queries,),
                                         replace=False)
            else:  # churn-aware: query AT live vertices only
                qidx = live_ids[jax.random.choice(
                    k1, live_ids.shape[0], (tcfg.routing_pool_queries,),
                    replace=live_ids.shape[0] < tcfg.routing_pool_queries)]
            routing_pool = F.sample_routing(
                graph, x, x[qidx], codes,
                lut_fn=lambda q: pqbase.build_lut(model, q), h=tcfg.beam_h,
                tombstones=ts_dev)
        if live_ids is None:
            anchors = jax.random.randint(k2, (tcfg.triplet_batch,), 0, n)
        else:
            anchors = live_ids[jax.random.randint(
                k2, (tcfg.triplet_batch,), 0, live_ids.shape[0])]
        trip = F.sample_triplets(k3, graph, x, anchors, n_hops=tcfg.n_hops,
                                 k_pos=tcfg.k_pos, k_neg=tcfg.k_neg,
                                 tombstones=ts_dev)
        if tcfg.use_routing:
            route = F.subsample_routing(k4, routing_pool, tcfg.routing_batch)
        else:  # placeholder batch (masked out by use_routing=False);
            #    one row PER REPLICA so it shards under data_parallel
            route = F.RoutingBatch(
                q=jnp.zeros((n_dp, x.shape[1]), jnp.float32),
                cand=jnp.zeros((n_dp, tcfg.beam_h), jnp.int32),
                label=jnp.zeros((n_dp,), jnp.int32),
                valid=jnp.zeros((n_dp,), bool))
        # ---- jitted joint step ----
        if tcfg.data_parallel:
            params, opt_state, comp_state, report, gnorm = step_fn(
                params, opt_state, comp_state, x, trip, route, k5)
        else:
            params, opt_state, report, gnorm = step_fn(
                params, opt_state, x, trip, route, k5)
        if step % tcfg.log_every == 0:
            rec = {k: float(v) for k, v in report._asdict().items()}
            rec.update(step=step, gnorm=float(gnorm), wall=time.time() - t0)
            history.append(rec)
            if verbose:
                print(f"[rpq] step {step:5d} total {rec['total']:.4f} "
                      f"routing {rec['routing']:.4f} "
                      f"nbr {rec['neighborhood']:.4f} α {rec['alpha']:.3f}")
        if checkpoint_cb is not None:
            checkpoint_cb(step, params, opt_state)
    return TrainState(params=params, opt_state=opt_state, step=tcfg.steps,
                      history=history)


def to_model(cfg: Q.RPQConfig, params: Q.RPQParams) -> pqbase.QuantizerModel:
    """Export the learned quantizer for the serving engines."""
    r = Q.rotation_matrix(cfg, params)
    return pqbase.QuantizerModel(r=r, codebooks=params.codebooks)
