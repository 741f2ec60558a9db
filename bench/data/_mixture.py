"""Clusters of points on low-dimensional subspaces, made on the device.

Each point is drawn as ``x = mu_c + U_c z + e``: a cluster ``c`` chosen
uniformly among ``clusters``; the cluster's centre ``mu_c ~ N(0, s_mu^2 I)``;
an orthonormal (dim, intrinsic_dim) basis ``U_c`` of the cluster's own
random subspace; a latent ``z ~ N(0, I)`` of ``intrinsic_dim`` components;
and isotropic noise ``e ~ N(0, s_e^2 I)``. The variances are stated as
shares of the within-cluster variance ``intrinsic_dim``:
``centre_var_share`` (dim * s_mu^2) and ``noise_var_share`` (dim * s_e^2).

Base rows and queries are drawn alike from one stream of iid points; the
queries are the last ``n_query`` of them, held out of the base.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=(
    "n", "dim", "clusters", "intrinsic_dim", "centre_var_share",
    "noise_var_share"))
def points(key, *, n: int, dim: int, clusters: int, intrinsic_dim: int,
           centre_var_share: float, noise_var_share: float):
    """(n, dim) float32 iid points of the mixture."""
    kc, ku, ka, kz, ke = jax.random.split(key, 5)
    within = float(intrinsic_dim)
    centres = (jax.random.normal(kc, (clusters, dim))
               * jnp.sqrt(centre_var_share * within / dim))
    basis, _ = jnp.linalg.qr(
        jax.random.normal(ku, (clusters, dim, intrinsic_dim)))
    c = jax.random.randint(ka, (n,), 0, clusters)
    z = jax.random.normal(kz, (n, intrinsic_dim))
    x = centres[c] + jnp.einsum("ndk,nk->nd", basis[c], z,
                                precision=jax.lax.Precision.HIGHEST)
    x = x + (jax.random.normal(ke, (n, dim))
             * jnp.sqrt(noise_var_share * within / dim))
    return x.astype(jnp.float32)
