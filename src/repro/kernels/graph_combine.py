"""Pallas TPU kernel: fused graph-homomorphic server combination (eq. 8 + 24).

Computes, for every server p and model-dim block:

    out[p, :] = sum_m A[m, p] * (psi_eff[m, :] + g_hom[m, p, :])
              = (A^T (psi_eff + g))[p, :] - g[p, :]

using the eq.-(24) identity so the [P, P, D] noise tensor is never
materialized: only the per-server Laplace draws ``g`` [P, D] stream through
VMEM alongside ``psi``, and the P x P mixing runs on the MXU per block.

``A`` (transposed) is a runtime operand: per-round effective matrices from
the resilience ``TopologyProcess`` reuse the one compiled program, so
combines inside ``lax.scan`` bodies stay fused.  Optional extensions:

  ``g=None``       noise-free combine (A^T psi) — the ``none`` mechanism;
  ``gate/cache``   the event engine's cached-psi re-announce: per server,
                   ``psi_eff = gate * psi + (1 - gate) * cache`` is computed
                   in-VMEM, so non-flushing servers re-announce their cached
                   psi without a separate [P, D] select pass over HBM.

HBM traffic: 2*P*D reads + P*D writes (vs 3x that for the unfused
psi-gather -> noise-add -> matmul chain), which matters because this pass
streams the ENTIRE parameter space every GFL iteration.

Grid: one program per model-dim tile of size ``block_d``.  P is padded to
the 8-sublane boundary outside the kernel (ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _combine_kernel(*refs, has_g: bool, has_gate: bool):
    """a_t: [P, P] (=A^T), psi/g/cache/out blocks: [P, block_d],
    gate: [P, 1]."""
    it = iter(refs)
    a_t_ref = next(it)
    psi_ref = next(it)
    g_ref = next(it) if has_g else None
    cache_ref = next(it) if has_gate else None
    gate_ref = next(it) if has_gate else None
    out_ref = next(it)
    a_t = a_t_ref[...]
    psi = psi_ref[...].astype(jnp.float32)
    if has_gate:
        gate = gate_ref[...].astype(jnp.float32)          # [P, 1]
        psi = gate * psi + (1.0 - gate) * cache_ref[...].astype(jnp.float32)
    # full f32 passes on the MXU: the eq.-24 noise only cancels to f32
    # rounding if the mix is f32 (a bf16 pass would leak ~2^-9 of g into
    # the centroid); P is tiny, so the extra passes cost nothing here
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    if has_g:
        g = g_ref[...].astype(jnp.float32)
        out_ref[...] = (dot(a_t, psi + g) - g).astype(out_ref.dtype)
    else:
        out_ref[...] = dot(a_t, psi).astype(out_ref.dtype)


def graph_combine(a_t: jax.Array, psi: jax.Array,
                  g: jax.Array | None = None, *,
                  cache: jax.Array | None = None,
                  gate: jax.Array | None = None,
                  block_d: int = 512, interpret: bool = False
                  ) -> jax.Array:
    """psi, g, cache: [P, D]; a_t: [P, P] (transposed combination matrix);
    gate: [P, 1] float (1 = announce psi, 0 = re-announce cache)."""
    P, D = psi.shape
    assert D % block_d == 0, (D, block_d)
    has_g = g is not None
    has_gate = gate is not None
    if has_gate:
        assert cache is not None, "gate needs a psi cache"
    grid = (D // block_d,)
    in_specs = [
        pl.BlockSpec((P, P), lambda j: (0, 0)),           # A^T resident
        pl.BlockSpec((P, block_d), lambda j: (0, j)),
    ]
    args = [a_t, psi]
    if has_g:
        in_specs.append(pl.BlockSpec((P, block_d), lambda j: (0, j)))
        args.append(g)
    if has_gate:
        in_specs.append(pl.BlockSpec((P, block_d), lambda j: (0, j)))
        in_specs.append(pl.BlockSpec((P, 1), lambda j: (0, 0)))
        args.extend([cache, gate])
    kern = functools.partial(_combine_kernel, has_g=has_g, has_gate=has_gate)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((P, block_d), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((P, D), psi.dtype),
        interpret=interpret,
    )(*args)
