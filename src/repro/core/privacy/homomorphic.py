"""Graph-homomorphic perturbations (eq. 24-25; Vlaski & Sayed, ICASSP 2021).

Each server ``m`` samples ONE Laplace vector ``g_m ~ Lap(0, sigma_g/sqrt 2)``
per iteration and perturbs the update it sends to neighbour ``p`` with::

    g_{mp} =  g_m                          if m != p
    g_{mp} = -(1 - a_mm)/a_mm * g_m        if m == p

which satisfies the null-space condition (eq. 25)

    (1/P) sum_p sum_m a_mp g_{mp} = 0

for any doubly-stochastic A, so the *network centroid* sees zero injected
noise and the O(mu^{-1}) utility penalty of Theorem 1 disappears.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.privacy.noise import get_sampler

# The mix runs at full f32 precision: a TPU's default matmul rounds its
# inputs to bf16, and A^T round(g) - g then leaves ~2^-9 of the noise in
# the centroid that eq. 25 promises to keep noise-free.
HIGHEST = jax.lax.Precision.HIGHEST


def _mix(A: jax.Array, x: jax.Array) -> jax.Array:
    """A^T x over the server dim: [P, P], [P, dim] -> [P, dim]."""
    return jnp.matmul(A.T.astype(x.dtype), x, precision=HIGHEST)


def homomorphic_noise_matrix(key: jax.Array, A: jax.Array, dim: int,
                             sigma: float, dtype=jnp.float32,
                             distribution: str = "laplace") -> jax.Array:
    """Materialize g_{mp} as a [P, P, dim] tensor (reference path).

    Row m is the noise server m adds to the update it sends to p (column p).
    The null-space identity (eq. 25) holds for ANY additive noise, so the
    distribution is a parameter (Laplace is the paper's choice; Gaussian is
    the Gauthier et al. 2023 variant).
    """
    P = A.shape[0]
    g = get_sampler(distribution)(key, (P, dim), sigma, dtype)  # g_m
    diag = jnp.diagonal(A)                                     # a_mm
    self_coef = -(1.0 - diag) / diag                           # eq. (24)
    out = jnp.broadcast_to(g[:, None, :], (P, P, dim))
    eye = jnp.eye(P, dtype=dtype)[:, :, None]
    return out * (1.0 - eye) + (self_coef[:, None] * g)[:, None, :] * eye


def homomorphic_combine_noise(key: jax.Array, A: jax.Array, psi: jax.Array,
                              sigma: float, distribution: str = "laplace"
                              ) -> jax.Array:
    """Server combination (8) with homomorphic noise, WITHOUT materializing
    the P x P noise tensor:

        w_p = sum_m a_mp (psi_m + g_{mp})
            = sum_m a_mp psi_m + sum_{m} a_mp g_m - g_p   [using eq. 24]

    since ``a_pp * (-(1-a_pp)/a_pp) g_p = -(1-a_pp) g_p`` merges with the
    ``m != p`` terms into ``(A^T g)_p - g_p``.

    psi: [P, dim] -> returns [P, dim].
    """
    P, dim = psi.shape
    g = get_sampler(distribution)(key, (P, dim), sigma, psi.dtype)
    mixed = _mix(A, psi)
    noise = _mix(A, g) - g
    return mixed + noise


def iid_noise_combine(key: jax.Array, A: jax.Array, psi: jax.Array,
                      sigma: float, distribution: str = "laplace"
                      ) -> jax.Array:
    """Baseline 'standard DP' scheme: independent noise per edge."""
    P, dim = psi.shape
    g = get_sampler(distribution)(key, (P, P, dim), sigma, psi.dtype)
    return _mix(A, psi) + jnp.einsum(
        "mp,mpd->pd", A.astype(psi.dtype), g, precision=HIGHEST)


def combine_nonprivate(A: jax.Array, psi: jax.Array) -> jax.Array:
    """Noise-free server combination (8)."""
    return _mix(A, psi)
