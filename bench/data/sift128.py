"""SIFT-shaped corpus (128-d float32, L2): see ``_mixture`` for the model
and ``configs/sift128-hybrid-u8.json`` for its parameters and why."""

from __future__ import annotations

import jax

from data._mixture import points


def make(key, n_base: int, n_query: int, p: dict):
    """((n_base, 128) base, (n_query, 128) held-out queries), float32."""
    x = points(key, n=n_base + n_query, dim=p["dim"],
               clusters=p["clusters"], intrinsic_dim=p["intrinsic_dim"],
               centre_var_share=p["centre_var_share"],
               noise_var_share=p["noise_var_share"])
    return jax.block_until_ready((x[:n_base], x[n_base:]))
