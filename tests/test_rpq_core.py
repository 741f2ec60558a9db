"""End-to-end RPQ core: feature extraction, losses, training loop."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import RPQConfig, TrainConfig, train_rpq
from repro.core import features as F
from repro.core import losses as L
from repro.core import quantizer as Q
from repro.core.trainer import init_rpq, to_model
from repro.pq import base


@pytest.fixture(scope="module")
def rpq_setup(clustered_data, small_graph):
    x, q, gt = clustered_data
    cfg = RPQConfig(dim=x.shape[1], m=4, k=32)
    params = init_rpq(jax.random.PRNGKey(0), cfg, x, kmeans_iters=5)
    return x, small_graph, cfg, params


def test_sample_triplets_shapes_and_validity(rpq_setup):
    x, g, cfg, params = rpq_setup
    anchors = jnp.arange(64, dtype=jnp.int32)
    t = F.sample_triplets(jax.random.PRNGKey(1), g, x, anchors,
                          n_hops=2, k_pos=5, k_neg=15)
    assert t.v.shape == t.vpos.shape == t.vneg.shape == (64,)
    v, vp, vn = np.asarray(t.v), np.asarray(t.vpos), np.asarray(t.vneg)
    ok = np.asarray(t.valid)
    assert ok.mean() > 0.9
    # positive is closer to anchor than negative (by construction via ranking)
    xa, xp_, xn = np.asarray(x)[v], np.asarray(x)[vp], np.asarray(x)[vn]
    dp = np.sum((xa - xp_) ** 2, -1)
    dn = np.sum((xa - xn) ** 2, -1)
    assert (dp[ok] <= dn[ok] + 1e-5).all()
    assert (vp[ok] != v[ok]).all() and (vn[ok] != v[ok]).all()
    assert (vp[ok] != vn[ok]).all()


def test_sample_routing_labels_are_exact_argmin(rpq_setup):
    x, g, cfg, params = rpq_setup
    model = to_model(cfg, params)
    codes = base.encode(model, x)
    rb = F.sample_routing(g, x, x[:16], codes,
                          lut_fn=lambda q: base.build_lut(model, q),
                          h=8, trace_len=16)
    ok = np.asarray(rb.valid)
    assert ok.sum() > 0
    cand = np.asarray(rb.cand)[ok]
    label = np.asarray(rb.label)[ok]
    qv = np.asarray(rb.q)[ok]
    xp = np.concatenate([np.asarray(x), np.zeros((1, x.shape[1]), np.float32)])
    d = np.sum((xp[cand] - qv[:, None]) ** 2, -1)
    d[cand == x.shape[0]] = np.inf
    assert (d.argmin(1) == label).all()


def test_losses_finite_and_positive(rpq_setup):
    x, g, cfg, params = rpq_setup
    anchors = jnp.arange(32, dtype=jnp.int32)
    trip = F.sample_triplets(jax.random.PRNGKey(2), g, x, anchors)
    model = to_model(cfg, params)
    codes = base.encode(model, x)
    rb = F.sample_routing(g, x, x[:8], codes,
                          lut_fn=lambda q: base.build_lut(model, q),
                          h=8, trace_len=8)
    key = jax.random.PRNGKey(3)
    ln = L.neighborhood_loss(cfg, params, x, trip, key)
    lr = L.routing_loss(cfg, params, x, rb, key)
    total, rep = L.joint_loss(cfg, params, x, trip, rb, key)
    for v in (ln, lr, total):
        assert np.isfinite(float(v))
    assert float(lr) >= 0
    assert float(ln) >= 0


def test_joint_loss_gradients_reach_all_params(rpq_setup):
    x, g, cfg, params = rpq_setup
    anchors = jnp.arange(32, dtype=jnp.int32)
    trip = F.sample_triplets(jax.random.PRNGKey(2), g, x, anchors)
    model = to_model(cfg, params)
    codes = base.encode(model, x)
    rb = F.sample_routing(g, x, x[:8], codes,
                          lut_fn=lambda q: base.build_lut(model, q),
                          h=8, trace_len=8)

    def f(p):
        return L.joint_loss(cfg, p, x, trip, rb, jax.random.PRNGKey(4))[0]

    grads = jax.grad(f)(params)
    assert float(jnp.abs(grads.codebooks).max()) > 0
    assert float(jnp.abs(grads.theta).max()) > 0
    assert float(jnp.abs(grads.log_alpha)) > 0


def test_short_training_improves_joint_loss(clustered_data, small_graph):
    x, _, _ = clustered_data
    cfg = RPQConfig(dim=x.shape[1], m=4, k=32)
    tcfg = TrainConfig(steps=60, refresh_every=30, triplet_batch=128,
                       routing_batch=128, routing_pool_queries=32,
                       log_every=10)
    rpq = train_rpq(jax.random.PRNGKey(0), x, small_graph, cfg=cfg, tcfg=tcfg,
                    verbose=False)
    hist = rpq.history
    assert len(hist) >= 3
    first = np.mean([h["total"] for h in hist[:2]])
    last = np.mean([h["total"] for h in hist[-2:]])
    # stability bound: 60 tiny steps with a fresh Kendall α won't always
    # decrease the *joint* objective — recall improvement is asserted in the
    # integration benchmark; here we require it not to diverge
    assert np.isfinite(last) and last < first * 1.5
    # exported model is orthonormal
    r = np.asarray(rpq.model.r)
    np.testing.assert_allclose(r @ r.T, np.eye(r.shape[0]), atol=1e-4)


def test_ablation_flags(clustered_data, small_graph):
    x, _, _ = clustered_data
    cfg = RPQConfig(dim=x.shape[1], m=4, k=32)
    for kwargs in ({"use_routing": False}, {"use_neighborhood": False}):
        tcfg = TrainConfig(steps=5, refresh_every=5, triplet_batch=64,
                           routing_batch=64, routing_pool_queries=16,
                           log_every=5, **kwargs)
        rpq = train_rpq(jax.random.PRNGKey(0), x, small_graph, cfg=cfg,
                        tcfg=tcfg, verbose=False)
        assert np.isfinite(rpq.history[-1]["total"])


def test_soft_assign_grad_through_kernel_matches_ref(rpq_setup):
    """jax.grad of the soft-assignment loss runs through the Pallas
    pq_pairwise kernel (interpret mode; a pallas_call has no reverse-mode
    rule, ops.pq_pairwise carries a closed-form VJP) and matches the
    gradient through the jnp oracle."""
    from repro.core import rotation as rot

    x, _, cfg, params = rpq_setup
    params = params._replace(theta=params.theta + 0.01)  # R ≠ I
    xb = x[:96]

    def loss(p, backend):
        probs = Q.soft_assign(cfg, p, xb, backend=backend)
        xr = rot.rotate(xb, Q.rotation_matrix(cfg, p))
        return jnp.mean((xr - Q.decode_soft(cfg, p, probs)) ** 2)

    g_kernel = jax.grad(loss)(params, "interpret")
    g_ref = jax.grad(loss)(params, "ref")
    for a, b in zip(jax.tree.leaves(g_kernel), jax.tree.leaves(g_ref)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4 * scale)
    assert float(jnp.max(jnp.abs(g_kernel.codebooks))) > 0
    assert float(jnp.max(jnp.abs(g_kernel.theta))) > 0
