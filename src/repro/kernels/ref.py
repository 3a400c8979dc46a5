"""Pure-jnp oracles for every Pallas kernel (bit-compatible semantics).

These are also the ``backend="ref"`` implementations of the dispatch layer
in :mod:`repro.kernels.ops`: the same one-pass *algorithms* expressed as a
single fused jnp computation, so the CPU path gets the fusion win from XLA
while the Pallas path realizes it explicitly on TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.secure_agg import pair_stream


def graph_combine_ref(a_t: jax.Array, psi: jax.Array, g: jax.Array
                      ) -> jax.Array:
    """out = A^T (psi + g) - g  (eq. 8 with eq. 24 noise structure)."""
    mixed = jnp.matmul(a_t.astype(jnp.float32),
                       (psi + g).astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    return (mixed - g.astype(jnp.float32)).astype(psi.dtype)


def secure_agg_mean_ref(updates: jax.Array, seed: jax.Array,
                        scale: float = 1.0) -> jax.Array:
    """Masked client mean with the same integer-hash pairwise streams."""
    L, D = updates.shape
    acc = jnp.sum(updates.astype(jnp.float32), axis=0)
    idx = jnp.arange(D, dtype=jnp.uint32)
    pid = 0
    for a in range(L):
        for b in range(a + 1, L):
            s = pair_stream(jnp.uint32(pid), idx, seed[0], scale)
            acc = acc + s - s
            pid += 1
    return (acc / L).astype(updates.dtype)


def laplace_transform_ref(u: jax.Array, sigma: float) -> jax.Array:
    b = sigma / (2.0 ** 0.5)
    uf = u.astype(jnp.float32)
    return (-b * jnp.sign(uf) * jnp.log1p(-2.0 * jnp.abs(uf))).astype(u.dtype)


def clip_accum_ref(grads: jax.Array, bound: float) -> jax.Array:
    g = grads.astype(jnp.float32)
    nrm = jnp.linalg.norm(g, axis=1, keepdims=True)
    coef = jnp.minimum(1.0, bound / jnp.maximum(nrm, 1e-12))
    return jnp.mean(g * coef, axis=0).astype(grads.dtype)


def hash_net_mask_fold(seed: jax.Array, noise_w: jax.Array, D: int,
                       scale: float) -> jax.Array:
    """One server's folded net pairwise hash-stream masks
    ``sum_k noise_w[k] * mask_k`` -> [D].

    Same counter-hash streams, pair enumeration and O(L) per-owner
    accumulation as the in-kernel path
    (:func:`~repro.kernels.secure_agg.net_mask_stream` inside a
    ``fori_loop``): peak memory is one [L, D] stream block, never the
    [L, L, D] pair tensor.  Because each alive pair's stream enters two
    owners' nets with opposite signs and the same (survivor-uniform)
    weight, the fold term cancels exactly in real arithmetic (eq. 23).
    """
    from repro.kernels.secure_agg import net_mask_stream
    L = noise_w.shape[0]
    idx = jnp.arange(D, dtype=jnp.uint32)[None, :]            # [1, D]
    alive = noise_w > 0

    def fold_owner(k, acc):
        m = net_mask_stream(k, idx, seed, scale, L, alive)    # [1, D]
        return acc + noise_w[k] * m[0]

    return jax.lax.fori_loop(0, L, fold_owner,
                             jnp.zeros((D,), jnp.float32))


def round_fold_ref(w: jax.Array, grads: jax.Array, *, mu: float,
                   bound: float, pre_w: jax.Array, fold_w: jax.Array,
                   noise_w: jax.Array, mode: str = "none",
                   sigma: float = 0.0, seeds: jax.Array | None = None,
                   noise: jax.Array | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """Fused round-fold oracle: clip -> update -> privatize -> fold.

    w: [P, D] or [P, L, D]; grads: [P, L, D]; pre_w / fold_w / noise_w:
    [P, L].  Returns (psi [P, D], sq [P, L] raw squared grad norms) — the
    same contract as :func:`repro.kernels.ops.round_fold`.
    """
    P, L, D = grads.shape
    g32 = grads.astype(jnp.float32)
    sq = jnp.sum(g32 * g32, axis=-1)                          # [P, L]
    pre = pre_w.astype(jnp.float32)
    nrm = pre * jnp.sqrt(sq)
    if bound > 0:
        coef = jnp.minimum(1.0, bound / jnp.maximum(nrm, 1e-12))
    else:
        coef = jnp.ones_like(nrm)
    ss = mu * coef * pre                                      # [P, L]
    wb = w.astype(jnp.float32)
    if w.ndim == 2:
        wb = wb[:, None, :]
    upd = wb - ss[..., None] * g32                            # [P, L, D]
    fw = fold_w.astype(jnp.float32)
    fwn = fw / jnp.maximum(fw.sum(axis=1, keepdims=True), 1e-12)
    psi = jnp.sum(fwn[..., None] * upd, axis=1)               # [P, D]
    nw = noise_w.astype(jnp.float32)
    if mode == "laplace":
        psi = psi + jnp.sum(nw[..., None] * noise.astype(jnp.float32),
                            axis=1)
    elif mode == "mask":
        psi = psi + jax.vmap(
            lambda sd, nw_p: hash_net_mask_fold(sd, nw_p, D, sigma)
        )(seeds, nw)                                          # [P, D]
    else:
        assert mode == "none", mode
    return psi.astype(w.dtype), sq


def swa_decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                             nvalid: jax.Array) -> jax.Array:
    """Naive masked decode attention. q: [B,H,Dh]; k,v: [B,C,H,Dh]."""
    Dh = q.shape[-1]
    C = k.shape[1]
    s = jnp.einsum("bhd,bchd->bhc", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (Dh ** 0.5)
    s = jnp.where(jnp.arange(C)[None, None, :] < nvalid[0], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhc,bchd->bhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)
