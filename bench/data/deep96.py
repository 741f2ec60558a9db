"""Deep1B-shaped corpus (96-d float32, L2, unit norm): the ``_mixture``
model, each point then scaled to unit L2 norm as Deep1B's descriptors are.
Parameters and why: ``configs/deep96-hybrid-fs4.json``."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from data._mixture import points


def make(key, n_base: int, n_query: int, p: dict):
    """((n_base, 96) base, (n_query, 96) held-out queries), float32."""
    x = points(key, n=n_base + n_query, dim=p["dim"],
               clusters=p["clusters"], intrinsic_dim=p["intrinsic_dim"],
               centre_var_share=p["centre_var_share"],
               noise_var_share=p["noise_var_share"])
    x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    return jax.block_until_ready((x[:n_base], x[n_base:]))
