"""Plain reference of the first steps of RPQ's routing-guided training
(``core/trainer.fit``, paper §4-§6).

Written for the benchmark and independent of the program: it imports
nothing of ``repro``. It takes the quantizer that training starts from
(the rotation's parameters, the codebooks and the log weight of the
neighbourhood term, as step 0 left them), the base, and what training fed
each of its first steps: the triplets (anchor, positive and negative ids,
and whether each is valid), the routing decisions (query vector, ranked
candidate ids with the sentinel N for an empty slot, and whether the hop
is valid) and the step's random key. Each decision's label, the candidate
nearest the query, it works out itself. From there it computes the steps
in straightforward ``jax.numpy`` at float32 on the host's CPU
(``dtype=jnp.bfloat16`` gives the control), following its own
parameters:

1. rotation ``R = expm(A)``, A skew-symmetric with ``theta`` as its strict
   upper triangle, row by row;
2. a row's straight-through Gumbel code, per subspace of ``x @ R.T``: the
   squared distances to the codewords, over their batch's mean nearest
   distance (held constant) and the assignment temperature; log-softmax;
   Gumbel noise drawn from the key; softmax at the Gumbel temperature. The
   forward value is the one-hot of its largest entry, the gradient that of
   the softmax; the quantized row is the code times the codebooks;
3. neighbourhood loss (Eq. 8): the mean over valid triplets of
   ``max(0, margin + (|a'-p'|^2 - |a'-n'|^2) / mean |a'-p'|^2)``, the mean
   over the whole batch and held constant, all three legs quantized with
   keys split three ways from the first half of the step's key;
4. routing loss (Eq. 9-10): the mean over valid decisions of the negative
   log-softmax, at the label, of ``-|c' - R q|^2 / (spread * tau)`` over
   the decision's candidates, ``c'`` quantized with the second half of the
   step's key (an empty slot quantizes row 0 and takes no part in the
   softmax), spread the mean gap of its candidates above the nearest, held
   constant;
5. total ``routing + exp(-s) * neighbourhood + s``;
6. the gradient (``jax.grad``) scaled to a global norm of at most
   ``grad_clip``, then Adam with the one-cycle rate of the configuration's
   ``lr`` and ``steps``.

``numbers`` compares a run's first steps with it: the loss of each step,
the first gradient as the optimizer got it (read from Adam's first moment
after one step), and the parameters' change over the steps, each leaf's
norm held to the reference's.
"""

from __future__ import annotations

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.linalg import expm

HIGHEST = jax.lax.Precision.HIGHEST

# The training settings the program states for RPQ (its quantizer's and
# optimizer's defaults, the paper's §4 and §6) that a configuration file
# does not list.
ASSIGN_TEMP = 1.0
GUMBEL_TAU = 1.0
ROUTING_TAU = 1.0
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
CYCLE_START, CYCLE_DIV, CYCLE_FINAL_DIV = 0.3, 25.0, 1e4
LEAVES = ("theta", "codebooks", "log_alpha")
# A leaf whose reference gradient lies under this share of the median
# leaf's moves by round-off alone: it is left out of the change compared.
STILL_LEAF = 1e-3
# A number that is not finite reads as this.
NOT_FINITE = 1e9


def _dot(spec, a, b):
    prec = HIGHEST if a.dtype == jnp.float32 else None
    return jnp.einsum(spec, a, b, precision=prec)


def rotation(theta, dim: int):
    """``expm(A)`` for the skew-symmetric A with ``theta`` above its
    diagonal; the exponential is taken in float32 and rounded to
    ``theta``'s type."""
    iu = np.triu_indices(dim, 1)
    a = jnp.zeros((dim, dim), jnp.float32).at[iu].set(
        theta.astype(jnp.float32))
    return expm(a - a.T).astype(theta.dtype)


def quantize(x, r, codebooks, key):
    """(B, D) rows → (B, D) straight-through Gumbel-quantized rows in the
    rotated space."""
    m, k, dsub = codebooks.shape
    xs = _dot("nd,ed->ne", x, r).reshape(-1, m, dsub)
    d = (jnp.sum(xs * xs, -1)[..., None]
         - 2 * _dot("nmd,mkd->nmk", xs, codebooks)
         + jnp.sum(codebooks * codebooks, -1)[None])
    scale = jax.lax.stop_gradient(jnp.mean(jnp.min(d, -1)) + 1e-12)
    logp = jax.nn.log_softmax(-d / (scale * ASSIGN_TEMP), -1)
    g = jax.random.gumbel(key, logp.shape, jnp.float32).astype(x.dtype)
    y = jax.nn.softmax((logp + g) / GUMBEL_TAU, -1)
    y = (jax.nn.one_hot(jnp.argmax(y, -1), k, dtype=y.dtype)
         + (y - jax.lax.stop_gradient(y)))
    return _dot("nmk,mkd->nmd", y, codebooks).reshape(x.shape[0], -1)


def _mean_valid(per, valid):
    w = valid.astype(per.dtype)
    return jnp.sum(jnp.where(valid, per, 0)) / jnp.maximum(jnp.sum(w), 1)


def neighbourhood_loss(r, codebooks, x, trip, key, margin):
    ka, kp, kn = jax.random.split(key, 3)
    a = quantize(x[trip["v"]], r, codebooks, ka)
    p = quantize(x[trip["vpos"]], r, codebooks, kp)
    n = quantize(x[trip["vneg"]], r, codebooks, kn)
    dp = jnp.sum((a - p) ** 2, -1)
    dn = jnp.sum((a - n) ** 2, -1)
    scale = jax.lax.stop_gradient(jnp.mean(dp) + 1e-9)
    return _mean_valid(jnp.maximum(0, margin + (dp - dn) / scale),
                       trip["valid"])


def labels(x, route):
    """Each decision's candidate nearest its query, the squared
    differences summed; empty slots never win."""
    n = x.shape[0]
    empty = route["cand"] == n
    cv = x[jnp.where(empty, 0, route["cand"])]
    d = jnp.sum((cv - route["q"][:, None, :]) ** 2, -1)
    return jnp.argmin(jnp.where(empty, jnp.inf, d), 1)


def routing_loss(r, codebooks, x, route, label, key):
    n = x.shape[0]
    b, h = route["cand"].shape
    empty = route["cand"] == n
    cv = x[jnp.where(empty, 0, route["cand"])].reshape(b * h, -1)
    cq = quantize(cv, r, codebooks, key).reshape(b, h, -1)
    q = _dot("nd,ed->ne", route["q"], r)
    d = jnp.sum((cq - q[:, None, :]) ** 2, -1)
    dmin = jnp.min(jnp.where(empty, jnp.inf, d), 1, keepdims=True)
    spread = jnp.mean(jnp.where(empty, 0, d - dmin), 1, keepdims=True) + 1e-9
    logits = jnp.where(empty, -jnp.inf,
                       -d / (jax.lax.stop_gradient(spread) * ROUTING_TAU))
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               label[:, None], 1)[:, 0]
    return _mean_valid(nll, route["valid"])


def loss(params, x, feed, margin):
    kt, kr = jax.random.split(feed["key"])
    r = rotation(params["theta"], x.shape[1])
    ln = neighbourhood_loss(r, params["codebooks"], x, feed["trip"], kt,
                            margin)
    lr = routing_loss(r, params["codebooks"], x, feed["route"],
                      feed["label"], kr)
    s = params["log_alpha"]
    return lr + jnp.exp(-s) * ln + s


def one_cycle(step: int, lr: float, steps: int) -> float:
    up = max(int(steps * CYCLE_START), 1)
    down = max(steps - up, 1)
    lo0, lo1 = lr / CYCLE_DIV, lr / CYCLE_FINAL_DIV
    if step < up:
        return lo0 + (lr - lo0) * step / up
    t = min((step - up) / down, 1.0)
    return lo1 + (lr - lo1) * 0.5 * (1 + math.cos(math.pi * t))


@jax.jit
def _step(params, m, v, t, rate, margin, clip, x, feed):
    value, g = jax.value_and_grad(loss)(params, x, feed, margin)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g[k].astype(jnp.float32)))
                        for k in LEAVES))
    scale = jnp.minimum(1.0, clip / (norm + 1e-12))
    g = {k: g[k] * scale.astype(g[k].dtype) for k in LEAVES}
    new, m, v = dict(params), dict(m), dict(v)
    for k in LEAVES:
        m[k] = ADAM_B1 * m[k] + (1 - ADAM_B1) * g[k]
        v[k] = ADAM_B2 * v[k] + (1 - ADAM_B2) * g[k] * g[k]
        mh = m[k] / (1 - ADAM_B1 ** t).astype(m[k].dtype)
        vh = v[k] / (1 - ADAM_B2 ** t).astype(v[k].dtype)
        new[k] = params[k] - (rate * mh / (jnp.sqrt(vh) + ADAM_EPS)
                              ).astype(params[k].dtype)
    return new, m, v, value, g


def steps(params0: dict, x, feeds: list, fit: dict, dtype=jnp.float32):
    """Follows training from ``params0`` through one step per feed.
    Returns one namespace a step: its loss, its clipped gradient and the
    parameters after it (host float64 arrays)."""
    cpu = jax.devices("cpu")[0]
    put = lambda a: jax.device_put(jnp.asarray(a), cpu)  # noqa: E731
    x = put(x).astype(dtype)
    params = {k: put(params0[k]).astype(dtype) for k in LEAVES}
    m = {k: jnp.zeros_like(params[k]) for k in LEAVES}
    v = {k: jnp.zeros_like(params[k]) for k in LEAVES}
    out = []
    with jax.default_device(cpu):
        for t, f in enumerate(feeds, start=1):
            route = {k: put(f["route"][k]) for k in ("q", "cand", "valid")}
            route["q"] = route["q"].astype(dtype)
            feed = {"trip": {k: put(f["trip"][k])
                             for k in ("v", "vpos", "vneg", "valid")},
                    "route": route, "label": labels(x, route),
                    "key": put(f["key"])}
            rate = one_cycle(t - 1, fit["lr"], fit["steps"])
            params, m, v, value, g = _step(
                params, m, v, jnp.float32(t), jnp.float32(rate),
                jnp.float32(fit["margin"]), jnp.float32(fit["grad_clip"]),
                x, feed)
            out.append(SimpleNamespace(
                loss=float(value),
                grad={k: np.asarray(g[k], np.float64) for k in LEAVES},
                params={k: np.asarray(params[k], np.float64)
                        for k in LEAVES}))
    return out


def _finite(v: float) -> float:
    return float(v) if math.isfinite(v) else NOT_FINITE


def _leaf_gap(got: dict, want: dict, keep) -> float:
    """Worst leaf of ``|‖got‖ − ‖want‖|`` over the larger of ``‖want‖`` and
    the median leaf's ``‖want‖``, over the leaves in ``keep``."""
    norms = {k: float(np.linalg.norm(want[k])) for k in LEAVES}
    med = float(np.median(list(norms.values())))
    return max(_finite(abs(float(np.linalg.norm(got[k])) - norms[k])
                       / max(norms[k], med, 1e-30)) for k in keep)


def numbers(program, ref) -> dict:
    """The numbers compared for a run's first steps. ``program`` holds
    ``params0`` and ``params`` (the parameters before the first step and
    after the last), ``losses`` (each step's) and ``grad`` (the first
    gradient as the optimizer got it); ``ref`` is ``steps``' output from
    the same ``params0``.

    * ``fit_loss_rel_gap``: the widest relative gap of a step's loss;
    * ``fit_grad_gap``: the first gradient, by the worst leaf;
    * ``fit_change_gap``: the change of the parameters over the steps, by
      the worst leaf, leaving out leaves whose reference gradient lies
      under ``STILL_LEAF`` of the median leaf's."""
    losses = [_finite(abs(a - b.loss) / max(abs(b.loss), 1e-30))
              for a, b in zip(program["losses"], ref)]
    g0 = ref[0].grad
    gnorm = {k: float(np.linalg.norm(g0[k])) for k in LEAVES}
    med = float(np.median(list(gnorm.values())))
    moving = [k for k in LEAVES if gnorm[k] >= STILL_LEAF * med]
    p0 = {k: np.asarray(program["params0"][k], np.float64) for k in LEAVES}
    change = {k: np.asarray(program["params"][k], np.float64) - p0[k]
              for k in LEAVES}
    ref_change = {k: ref[-1].params[k] - p0[k] for k in LEAVES}
    return {"fit_loss_rel_gap": max(losses),
            "fit_grad_gap": _leaf_gap(program["grad"], g0, LEAVES),
            "fit_change_gap": _leaf_gap(change, ref_change, moving)}


def control(params0: dict, x, feeds: list, fit: dict, ref) -> dict:
    """``numbers`` with the reference in bfloat16 put in the program's
    place."""
    low = steps(params0, x, feeds, fit, dtype=jnp.bfloat16)
    p0 = {k: np.asarray(jnp.asarray(params0[k]).astype(jnp.bfloat16),
                        np.float64) for k in LEAVES}
    base = {k: np.asarray(params0[k], np.float64) for k in LEAVES}
    return numbers({"params0": base,
                    "params": {k: base[k] + low[-1].params[k] - p0[k]
                               for k in LEAVES},
                    "losses": [s.loss for s in low], "grad": low[0].grad},
                   ref)
