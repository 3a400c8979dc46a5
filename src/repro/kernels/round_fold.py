"""Pallas TPU kernel: fused GFL round fold — clip -> update -> privatize -> fold.

The per-iteration client work of the protocol (eqs. 6-7, with eq. 23 masks or
iid noise) is a pure streaming pass over the whole ``[P, L, D]`` gradient
tensor, yet the reference chain runs it as 4-6 separate XLA ops that re-read
the tensor from HBM each time (norms, scale, update, noise add, fold).  This
kernel computes, per server p and model-dim tile,

    coef_k = min(1, B / max(pre_w_k * ||grad_k||, eps))          (clip, eq. 14)
    upd_k  = w_[p|p,k] - mu * coef_k * pre_w_k * grad_k          (update, eq. 6)
    psi_p  = sum_k fold_wn_k * upd_k  +  noise term              (fold, eq. 7)

in TWO HBM passes over the gradients: a norms pass and a scale/noise/fold
pass (the tiny ``[P, L]`` clip/weight math in between runs on host-shaped
arrays).  The composed weight vector — PR 3's ``1/(K pi)`` importance
weights (``pre_w``, applied BEFORE the sensitivity clip), PR 4's
``1/(1+age)^alpha`` staleness weights and alive masks (``fold_wn``,
normalized fold weights) — makes the same kernel serve the dense
``_client_updates``, ``run_gfl_population``'s weighted executor and the
event engine's buffered ``weighted_fold``.

Noise modes (the mechanism's client level):
  ``none``     plain weighted fold;
  ``mask``     in-kernel counter-hash pairwise secure-agg streams
               (:func:`~repro.kernels.secure_agg.net_mask_stream`),
               restricted to alive pairs, entering with the survivor-mean
               weight ``noise_w`` — exact cancellation in the fold;
  ``laplace``  a pre-drawn ``[P, L, D]`` noise tensor streamed once and
               folded with ``noise_w`` (the iid_dp path keeps the reference
               sampler's draws bit-for-bit, so backend parity is tight).

Per-client base models (``w`` of shape [P, L, D], the event engine's stale
snapshots) are supported: their block is [L, bd] where a shared base's is
[1, bd], and the update broadcasts either.

Use :func:`repro.kernels.ops.round_fold` — it handles tile padding, block
autotuning and the ref-jnp backend; this module is the raw kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.secure_agg import net_mask_stream


def _norms_kernel(g_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = g_ref[0].astype(jnp.float32)                      # [L, bd]
    out_ref[0] += jnp.sum(g * g, axis=1, keepdims=True)   # [L, 1]


def fold_norms(grads: jax.Array, *, block_d: int = 512,
               interpret: bool = False) -> jax.Array:
    """Phase 1: per-(server, client) squared gradient norms.

    grads: [P, L, D] -> [P, L] float32 (one HBM read of the gradients;
    the grid revisits each server's [L, 1] output column across model-dim
    tiles).
    """
    P, L, D = grads.shape
    assert D % block_d == 0, (D, block_d)
    out = pl.pallas_call(
        _norms_kernel,
        grid=(P, D // block_d),
        in_specs=[pl.BlockSpec((1, L, block_d), lambda p, j: (p, 0, j))],
        out_specs=pl.BlockSpec((1, L, 1), lambda p, j: (p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((P, L, 1), jnp.float32),
        interpret=interpret,
    )(grads)
    return out[..., 0]


def _fold_kernel(*refs, mode: str, sigma: float, L: int, block_d: int):
    w_ref, g_ref, ss_ref, fw_ref, nw_ref = refs[:5]
    out_ref = refs[-1]
    g = g_ref[0].astype(jnp.float32)                      # [L, bd]
    ss = ss_ref[0].astype(jnp.float32)                    # [L, 1]
    fw = fw_ref[0].astype(jnp.float32)                    # [L, 1]
    nw = nw_ref[0].astype(jnp.float32)                    # [L, 1]
    wb = w_ref[0].astype(jnp.float32)     # [L, bd], or [1, bd] broadcasts
    upd = wb - ss * g                                     # [L, bd]
    acc = jnp.sum(fw * upd, axis=0, keepdims=True)        # [1, bd]
    if mode == "laplace":
        nz = refs[5][0].astype(jnp.float32)               # [L, bd]
        acc = acc + jnp.sum(nw * nz, axis=0, keepdims=True)
    elif mode == "mask":
        # all P seeds sit whole in SMEM (a scalar block would break the
        # TPU's tiling rule); this program reads its own server's
        seed_ref = refs[5]
        j = pl.program_id(1)
        seed = seed_ref[pl.program_id(0)]
        idx = (j * block_d
               + jax.lax.broadcasted_iota(jnp.uint32, (1, block_d), 1))
        alive = nw > 0
        rows = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)
        # each alive pair's stream enters the fold twice with opposite signs
        # and the same survivor-mean weight -> exact cancellation (eq. 23);
        # O(L) fori_loop, body vectorized over peers (compile-flat in L).
        # Client k's weight is picked from the column by a masked sum, not
        # a dynamic vector index, which Mosaic does not lower.
        def fold_client(k, a):
            m = net_mask_stream(k, idx, seed, sigma, L, alive)
            nw_k = jnp.sum(jnp.where(rows == k, nw, 0.0), axis=0,
                           keepdims=True)                 # [1, 1]
            return a + nw_k * m

        acc = jax.lax.fori_loop(0, L, fold_client, acc)
    out_ref[0] = acc.astype(out_ref.dtype)


def fold_apply(w: jax.Array, grads: jax.Array, stepscale: jax.Array,
               fold_wn: jax.Array, noise_w: jax.Array, *,
               mode: str = "none", sigma: float = 0.0,
               seeds: jax.Array | None = None,
               noise: jax.Array | None = None,
               block_d: int = 512, interpret: bool = False) -> jax.Array:
    """Phase 2: fused scale/update/privatize/fold.

    w: [P, D] (shared base) or [P, L, D] (per-client stale bases);
    grads: [P, L, D]; stepscale = mu * clip_coef * pre_w, fold_wn =
    normalized fold weights, noise_w = per-client noise/mask fold weight
    (all [P, L]).  mode "mask" needs ``seeds`` [P] uint32; mode "laplace"
    needs ``noise`` [P, L, D].  Returns psi [P, D] in w.dtype.

    The TPU tiles the last two dims of every block by (8, 128) unless a
    block spans them whole, so [P, D] rows travel as [P, 1, D] and [P, L]
    weights as [P, L, 1] columns: both reshapes are free.
    """
    P, L, D = grads.shape
    assert D % block_d == 0, (D, block_d)
    if w.ndim == 3:                     # per-client stale bases
        w_spec = pl.BlockSpec((1, L, block_d), lambda p, j: (p, 0, j))
    else:
        w = w.reshape(P, 1, D)
        w_spec = pl.BlockSpec((1, 1, block_d), lambda p, j: (p, 0, j))
    col = pl.BlockSpec((1, L, 1), lambda p, j: (p, 0, 0))
    in_specs = [
        w_spec,
        pl.BlockSpec((1, L, block_d), lambda p, j: (p, 0, j)),
        col, col, col,
    ]
    args = [w, grads] + [v.reshape(P, L, 1)
                         for v in (stepscale, fold_wn, noise_w)]
    if mode == "mask":
        assert seeds is not None, "mask mode needs per-server seeds [P]"
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seeds.astype(jnp.uint32).reshape(P))
    elif mode == "laplace":
        assert noise is not None, "laplace mode needs pre-drawn noise [P,L,D]"
        in_specs.append(pl.BlockSpec((1, L, block_d), lambda p, j: (p, 0, j)))
        args.append(noise)
    else:
        assert mode == "none", mode
    kern = functools.partial(_fold_kernel, mode=mode, sigma=float(sigma),
                             L=L, block_d=block_d)
    out = pl.pallas_call(
        kern,
        grid=(P, D // block_d),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_d), lambda p, j: (p, 0, j)),
        out_shape=jax.ShapeDtypeStruct((P, 1, D), w.dtype),
        interpret=interpret,
    )(*args)
    return out[:, 0]
