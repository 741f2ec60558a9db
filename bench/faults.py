"""Faults planted under the timed path, for the tests and for
``control.py``: each of ``FAULTS`` wraps an engine's ``search``
(``HybridEngine`` or ``ShardedGraphEngine``) and breaks what a served call
returns; each of ``SHARD_FAULTS`` does so for ``ShardedGraphEngine``
alone; each of ``FIT_FAULTS`` breaks the training step of a quantizer
trained by ``fit``. A sound run never uses them."""

import contextlib

import jax.numpy as jnp


def altered(search):
    """One answer of every call altered where it is produced: the first
    query's nearest id replaced by another row, its distance kept."""
    def broken(self, queries, **kw):
        res = search(self, queries, **kw)
        n = self.graph.n
        ids = res.ids.at[0, 0].set((res.ids[0, 0] + n // 2) % n)
        return res._replace(ids=ids)
    return broken


def half_left_out(search):
    """Half of each batch left out: only the first half is searched, and
    its answers stand in for the rest."""
    def broken(self, queries, **kw):
        half = queries.shape[0] // 2
        res = search(self, queries[:half], **kw)
        fill = lambda a: jnp.concatenate([a, a])[: queries.shape[0]]  # noqa: E731
        return res._replace(ids=fill(res.ids), dists=fill(res.dists))
    return broken


def one_lane_capped(search, rounds: int = 3):
    """One lane of every call stops its beam after ``rounds`` rounds: the
    first query's answer comes from a beam cut short, the rest are sound."""
    def broken(self, queries, **kw):
        res = search(self, queries, **kw)
        one = search(self, queries[:1], **dict(kw, max_rounds=jnp.int32(rounds)))
        return res._replace(ids=res.ids.at[0].set(one.ids[0]),
                            dists=res.dists.at[0].set(one.dists[0]))
    return broken


FAULTS = {"altered": altered, "half_left_out": half_left_out,
          "one_lane_capped": one_lane_capped}


def one_shard_dropped(search):
    """The last shard marked dead in every call, and the answer not marked
    degraded: its rows drop out of every merge unannounced."""
    def broken(self, queries, **kw):
        alive = [True] * (self.n_shards - 1) + [False]
        res = search(self, queries, alive=alive, **kw)
        return res._replace(degraded=False)
    return broken


SHARD_FAULTS = {"one_shard_dropped": one_shard_dropped}


# Faults of RPQ training, planted in ``fit``'s jitted step: each wraps
# ``trainer.make_train_step``.

def fit_state_unchanged(make):
    """Every step returns the parameters and Adam's state it was given."""
    def broken_make(cfg, tcfg, optimizer):
        step = make(cfg, tcfg, optimizer)

        def broken(params, opt_state, x, trip, route, key):
            _, _, report, gnorm = step(params, opt_state, x, trip, route, key)
            return params, opt_state, report, gnorm
        return broken
    return broken_make


def fit_half_batch(make):
    """Half of every step's triplets and routing decisions left out: the
    loss is the mean over the rest."""
    def broken_make(cfg, tcfg, optimizer):
        step = make(cfg, tcfg, optimizer)

        def broken(params, opt_state, x, trip, route, key):
            half = lambda b: type(b)(*(a[: a.shape[0] // 2] for a in b))  # noqa: E731
            return step(params, opt_state, x, half(trip), half(route), key)
        return broken
    return broken_make


FIT_FAULTS = {"fit_state_unchanged": fit_state_unchanged,
              "fit_half_batch": fit_half_batch}


@contextlib.contextmanager
def planted_fit(name: str):
    """``fit``'s step broken by the fault ``name`` while the block runs."""
    from repro.core import trainer

    make = trainer.make_train_step
    trainer.make_train_step = FIT_FAULTS[name](make)
    try:
        yield
    finally:
        trainer.make_train_step = make
