"""Backend-dispatch layer over the Pallas kernels (ref-jnp vs Pallas).

This is the ONE place the engines touch the kernel layer: ``core/gfl.py``,
``core/population/engine.py``, ``core/events/engine.py`` and
``launch/steps.py`` all call these wrappers, so ``GFLConfig.use_kernels``
is a whole-run switch (the engines route through here when it is set)
instead of a mechanism-internal detail.  Every op takes

  ``backend``   "pallas" (default) or "ref" — the pure-jnp oracle from
                :mod:`repro.kernels.ref`, same contract, same one-pass
                algorithm, used for parity tests and CPU-side fusion;
  ``interpret`` None (auto: interpret mode on CPU so the same call sites
                work in tests, the simulator and on real TPUs) or explicit.

Padding: inputs are padded UP to the model-dim tile boundary and sliced
back — the old ``_block_for`` heuristic shrank the block until it divided D,
which collapsed to pathological 1-wide grids for odd/prime D; now the block
is always a 128-multiple and D pads to it (regression-tested on D=509).

Block autotuning: ``block_d`` candidates {128, 256, 512, 1024} that tile
the padded model dim are timed once per (op, shape, dtype) on the device
and the winner is cached for the process (``choose_block``); set
``REPRO_KERNEL_AUTOTUNE=0`` to skip timing and take the largest candidate.
Timing runs eagerly on dummy zeros at trace time (in an eval context,
out of the caller's trace), so jitted callers autotune once per shape.
Interpret mode is never timed: its timings say nothing about a chip.
"""
from __future__ import annotations

import os
import time
import warnings
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import clip_accum as _clip
from repro.kernels import graph_combine as _combine
from repro.kernels import laplace as _laplace
from repro.kernels import ref as _ref
from repro.kernels import round_fold as _rf
from repro.kernels import secure_agg as _sagg

BACKENDS = ("pallas", "ref")
_BLOCK_CANDIDATES = (128, 256, 512, 1024)
_AUTOTUNE_CACHE: dict = {}


def _emit_kernel(**values) -> None:
    """Host-side dispatch record onto the ``kernel`` telemetry stream.

    Runs at TRACE time (dispatch decisions are host logic), so nothing is
    ever inserted into the kernels' process-lifetime jit caches — a
    record fires once per newly-traced (op, shape), only while a
    telemetry session is active."""
    from repro.telemetry import current_session, emit
    sess = current_session()
    if sess is None:
        return
    emit("kernel", {"seq": sess.next_seq(), **values})


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _resolve(backend: str | None, interpret: bool | None):
    # default backend: "pallas" (interpret mode on CPU keeps the kernels
    # exercised by tier-1); REPRO_KERNEL_BACKEND=ref flips whole-run CPU
    # jobs onto the fused jnp oracles — same one-pass pipeline, XLA-fused,
    # much faster than interpreting Pallas on the host
    backend = backend or os.environ.get("REPRO_KERNEL_BACKEND", "pallas")
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    if interpret is None:
        interpret = _on_cpu()
    return backend, interpret


def _pad_last(x: jax.Array, mult: int) -> tuple[jax.Array, int]:
    d = x.shape[-1]
    pad = (-d) % mult
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, d


def _pad_first(x: jax.Array, mult: int) -> tuple[jax.Array, int]:
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x, n


def _pad_axis(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def block_candidates(d: int) -> tuple[list[int], int]:
    """(candidate block_d list, padded model dim) for a last dim of d.

    The padded dim is the 128-tile round-up; candidates are the standard
    tile multiples that divide it, so the grid is never pathological
    (the old ``_block_for`` returned block_d=1 for odd D > 512)."""
    d_pad = max(d, 1) + (-max(d, 1)) % 128
    cands = [c for c in _BLOCK_CANDIDATES if c <= d_pad and d_pad % c == 0]
    return (cands or [d_pad]), d_pad


def clear_autotune_cache() -> None:
    _AUTOTUNE_CACHE.clear()


def apply_gate(psi: jax.Array, gate: jax.Array | None,
               cache: jax.Array | None) -> jax.Array:
    """The cached-psi re-announce select (gated-off servers contribute
    ``cache``) — the jnp realization of what the gated combine kernel does
    in VMEM.  ``gate=None`` is the ungated identity."""
    if gate is None:
        return psi
    return jnp.where(jnp.asarray(gate).astype(bool)[:, None], psi, cache)


def choose_block(op: str, d: int, *, shape_key: tuple = (),
                 make_timed=None, interpret: bool = False
                 ) -> tuple[int, int]:
    """Pick (block_d, padded D) for op on a last dim of d.

    When more than one candidate tiles the padded dim and ``make_timed``
    is given (``make_timed(block_d, d_pad) -> zero-arg callable`` running
    the kernel on dummy data), each candidate is compiled and timed once
    on the device — warmup call then one measured call — and the winner
    is cached per ``(op, d_pad, interpret, *shape_key)`` for the process
    lifetime.  A candidate that fails is reported as a warning; if none
    runs, the error is raised."""
    from repro.telemetry import trace_span
    cands, d_pad = block_candidates(d)
    key = (op, d_pad, interpret) + tuple(shape_key)
    if key in _AUTOTUNE_CACHE:
        return _AUTOTUNE_CACHE[key], d_pad
    autotuned = False
    if (len(cands) == 1 or make_timed is None or interpret
            or os.environ.get("REPRO_KERNEL_AUTOTUNE", "1") == "0"):
        block = cands[-1]
    else:
        autotuned = True
        times, errors = {}, {}
        # callers are jitted: outside an eval context the candidates would
        # only be traced into the caller's program, never run.  (Not
        # ensure_compile_time_eval: it folds the kernel body's constants,
        # which pallas_call refuses.)
        with trace_span(f"autotune:{op}", d_pad=d_pad,
                        candidates=len(cands)), jax.core.eval_context():
            for c in cands:
                try:
                    fn = make_timed(c, d_pad)
                    jax.block_until_ready(fn())      # compile + warmup
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn())
                    times[c] = time.perf_counter() - t0
                except Exception as e:  # noqa: BLE001 - reported below
                    errors[c] = e
                    warnings.warn(f"{op}: block_d={c} (d_pad={d_pad}) "
                                  f"failed: {type(e).__name__}: {e}")
        if not times:
            raise RuntimeError(
                f"{op}: no block_d candidate in {cands} runs at "
                f"d_pad={d_pad}") from errors[cands[-1]]
        block = min(times, key=times.get)
    _AUTOTUNE_CACHE[key] = block
    _emit_kernel(op=op, backend="pallas", block_d=block, d_pad=d_pad,
                 interpret=int(interpret), autotuned=int(autotuned))
    return block, d_pad


# ---------------------------------------------------------------------------
# fused round fold (clip -> update -> privatize -> fold), eqs. 6-7 + 23
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("mu", "bound", "mode", "sigma",
                                   "backend", "interpret"))
def round_fold(w: jax.Array, grads: jax.Array, *, mu: float, bound: float,
               pre_w: jax.Array | None = None,
               fold_w: jax.Array | None = None,
               noise_w: jax.Array | None = None,
               mode: str = "none", sigma: float = 0.0,
               seeds: jax.Array | None = None,
               noise: jax.Array | None = None,
               backend: str | None = None,
               interpret: bool | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """Fused client-side round: [P, L, D] grads -> (psi [P, D], sq [P, L]).

    ``w`` is the per-server base model [P, D], or per-client stale bases
    [P, L, D] (the event engine).  ``pre_w`` scales gradients BEFORE the
    sensitivity clip (importance weights); ``fold_w`` are unnormalized fold
    weights (staleness x alive; the fold is weight-normalized with a 1e-12
    guard, zero total weight folds to zero); ``noise_w`` weights the
    noise/mask term per client (defaults to the uniform 1/L mean).  ``sq``
    is the raw squared gradient norm per (server, client) — callers derive
    clipped-norm feedback as ``min(bound, sqrt(sq))``.
    """
    backend, interpret = _resolve(backend, interpret)
    P, L, D = grads.shape
    from repro.telemetry import telemetry_active
    if telemetry_active():
        # shapes are concrete at trace time: record the round's analytic
        # HBM traffic (launch/roofline.py) once per newly-traced shape
        from repro.launch.roofline import round_pipeline_traffic
        itemsize = jnp.dtype(grads.dtype).itemsize
        fused_t = round_pipeline_traffic(P, L, D, itemsize=itemsize,
                                         mode=mode, fused=True)
        ref_t = round_pipeline_traffic(P, L, D, itemsize=itemsize,
                                       mode=mode, fused=False)
        _emit_kernel(op="round_fold.traffic", backend=backend, mode=mode,
                     hbm_bytes=float(fused_t["total"]),
                     hbm_bytes_ref=float(ref_t["total"]),
                     pld_passes=int(fused_t["pld_passes"]))
    ones = jnp.ones((P, L), jnp.float32)
    pre_w = ones if pre_w is None else pre_w.astype(jnp.float32)
    fold_w = ones if fold_w is None else fold_w.astype(jnp.float32)
    noise_w = ones / L if noise_w is None else noise_w.astype(jnp.float32)

    if backend == "ref":
        return _ref.round_fold_ref(w, grads, mu=mu, bound=bound,
                                   pre_w=pre_w, fold_w=fold_w,
                                   noise_w=noise_w, mode=mode, sigma=sigma,
                                   seeds=seeds, noise=noise)

    l_mult = 16 if grads.dtype == jnp.bfloat16 else 8

    def timed(block, d_pad):
        # mode-faithful proxy: mask mode's per-block cost is dominated by
        # the in-kernel stream generation, so candidates must be timed on
        # the mode they will serve
        L_p = L + (-L) % l_mult
        g0 = jnp.zeros((P, L_p, d_pad), grads.dtype)
        w0 = jnp.zeros((P, d_pad), w.dtype)
        s0 = jnp.zeros((P, L_p), jnp.float32)
        sd0 = jnp.zeros((P,), jnp.uint32) if mode == "mask" else None
        n0 = (jnp.zeros((P, L_p, d_pad), grads.dtype)
              if mode == "laplace" else None)
        return lambda: _rf.fold_apply(w0, g0, s0, s0, s0, mode=mode,
                                      sigma=sigma, seeds=sd0, noise=n0,
                                      block_d=block, interpret=interpret)

    block, d_pad = choose_block(
        "round_fold", D, shape_key=(P, L, str(grads.dtype), mode),
        make_timed=timed, interpret=interpret)

    g_p = _pad_axis(_pad_last(grads, d_pad)[0], 1, l_mult)
    w_p = _pad_last(w, d_pad)[0]
    if w.ndim == 3:
        w_p = _pad_axis(w_p, 1, l_mult)
    pre_p = _pad_last(pre_w, l_mult)[0]
    fold_p = _pad_last(fold_w, l_mult)[0]
    nw_p = _pad_last(noise_w, l_mult)[0]

    sq = _rf.fold_norms(g_p, block_d=block, interpret=interpret)  # [P, L_p]
    # tiny [P, L] clip/weight math between the two streaming passes
    nrm = pre_p * jnp.sqrt(sq)
    if bound > 0:
        coef = jnp.minimum(1.0, bound / jnp.maximum(nrm, 1e-12))
    else:
        coef = jnp.ones_like(nrm)
    stepscale = mu * coef * pre_p
    fsum = fold_p.sum(axis=1, keepdims=True)
    fold_n = fold_p / jnp.maximum(fsum, 1e-12)
    noise_p = (None if noise is None
               else _pad_axis(_pad_last(noise, d_pad)[0], 1, l_mult))
    psi = _rf.fold_apply(w_p, g_p, stepscale, fold_n, nw_p, mode=mode,
                         sigma=sigma, seeds=seeds, noise=noise_p,
                         block_d=block, interpret=interpret)
    return psi[:, :D], sq[:, :L]


# ---------------------------------------------------------------------------
# fused server combination (eq. 8 + 24)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("backend", "interpret"))
def graph_combine(A: jax.Array, psi: jax.Array, g: jax.Array | None = None,
                  *, cache: jax.Array | None = None,
                  gate: jax.Array | None = None,
                  backend: str | None = None,
                  interpret: bool | None = None) -> jax.Array:
    """Fused server combination: [P,D], [P,D] -> [P,D].

    ``A`` is a runtime argument, so per-round effective matrices from the
    resilience ``TopologyProcess`` slot straight in (one compilation serves
    every round, including inside ``lax.scan`` bodies).  ``g=None`` is the
    noise-free combine (A^T psi).  ``gate``/``cache`` ([P] mask, [P, D])
    implement the event engine's cached-psi re-announce IN the kernel:
    servers with gate off contribute their cached psi to the mix instead of
    the (unflushed) fold — no separate select pass over the parameters.
    """
    backend, interpret = _resolve(backend, interpret)
    if backend == "ref":
        psi = apply_gate(psi, gate, cache)
        if g is None:
            mixed = jnp.matmul(jnp.asarray(A).T.astype(jnp.float32),
                               psi.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST)
            return mixed.astype(psi.dtype)
        return _ref.graph_combine_ref(jnp.asarray(A).T, psi, g)

    a_t = jnp.asarray(A).T

    def timed(block, d_pad):
        # variant-faithful: the gated kernel reads two extra operands per
        # block, so time exactly the (g, gate) combination being served
        P8 = psi.shape[0] + (-psi.shape[0]) % 8
        z = jnp.zeros((P8, d_pad), psi.dtype)
        a0 = jnp.zeros((P8, P8), a_t.dtype)
        g0 = None if g is None else z
        c0 = None if gate is None else z
        gt0 = None if gate is None else jnp.zeros((P8, 1), jnp.float32)
        return lambda: _combine.graph_combine(a0, z, g0, cache=c0,
                                              gate=gt0, block_d=block,
                                              interpret=interpret)

    block, d_pad = choose_block(
        "graph_combine", psi.shape[-1],
        shape_key=(psi.shape[0], str(psi.dtype), g is None, gate is None),
        make_timed=timed, interpret=interpret)

    psi_p, D = _pad_last(psi, d_pad)
    psi_p, P = _pad_first(psi_p, 8)
    g_p = None
    if g is not None:
        g_p = _pad_first(_pad_last(g, d_pad)[0], 8)[0]
    cache_p = gate_p = None
    if gate is not None:
        cache_p = _pad_first(_pad_last(cache, d_pad)[0], 8)[0]
        gate_p = _pad_first(jnp.asarray(gate).astype(jnp.float32)[:, None],
                            8)[0]
    a_pad = jnp.zeros((psi_p.shape[0], psi_p.shape[0]), a_t.dtype)
    a_pad = a_pad.at[:P, :P].set(a_t)
    # padded servers get psi=g=0 rows already; diag term subtracts their own 0
    out = _combine.graph_combine(a_pad, psi_p, g_p, cache=cache_p,
                                 gate=gate_p, block_d=block,
                                 interpret=interpret)
    return out[:P, :D]


# ---------------------------------------------------------------------------
# single-server kernels
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("scale", "backend", "interpret"))
def secure_agg_mean(updates: jax.Array, seed: jax.Array, scale: float = 1.0,
                    backend: str | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Masked client mean: [L,D] -> [D]."""
    backend, interpret = _resolve(backend, interpret)
    if backend == "ref":
        return _ref.secure_agg_mean_ref(updates, jnp.atleast_1d(seed),
                                        scale)

    def timed(block, d_pad):
        z = jnp.zeros((updates.shape[0], d_pad), updates.dtype)
        s0 = jnp.zeros((1,), jnp.uint32)
        return lambda: _sagg.secure_agg_mean(z, s0, scale=scale,
                                             block_d=block,
                                             interpret=interpret)

    block, d_pad = choose_block(
        "secure_agg", updates.shape[-1],
        shape_key=(updates.shape[0], str(updates.dtype)),
        make_timed=timed, interpret=interpret)
    upd, D = _pad_last(updates, d_pad)
    out = _sagg.secure_agg_mean(upd, jnp.atleast_1d(seed).astype(jnp.uint32),
                                scale=scale, block_d=block,
                                interpret=interpret)
    return out[:D]


@partial(jax.jit, static_argnames=("sigma", "backend", "interpret"))
def laplace_transform(u: jax.Array, sigma: float,
                      backend: str | None = None,
                      interpret: bool | None = None) -> jax.Array:
    """Uniform (-1/2,1/2) -> Lap(0, sigma/sqrt 2): [P,D] -> [P,D]."""
    backend, interpret = _resolve(backend, interpret)
    if backend == "ref":
        return _ref.laplace_transform_ref(u, sigma)

    def timed(block, d_pad):
        P8 = u.shape[0] + (-u.shape[0]) % 8
        z = jnp.zeros((P8, d_pad), u.dtype)
        return lambda: _laplace.laplace_transform(z, sigma, block_d=block,
                                                  interpret=interpret)

    block, d_pad = choose_block(
        "laplace", u.shape[-1], shape_key=(u.shape[0], str(u.dtype)),
        make_timed=timed, interpret=interpret)
    up, D = _pad_last(u, d_pad)
    up, P = _pad_first(up, 8)
    out = _laplace.laplace_transform(up, sigma, block_d=block,
                                     interpret=interpret)
    return out[:P, :D]


@partial(jax.jit, static_argnames=("bound", "backend", "interpret"))
def clip_accum(grads: jax.Array, bound: float,
               backend: str | None = None,
               interpret: bool | None = None) -> jax.Array:
    """Per-client clip to B + mean: [L,D] -> [D]."""
    backend, interpret = _resolve(backend, interpret)
    if backend == "ref":
        return _ref.clip_accum_ref(grads, bound)

    def timed(block, d_pad):
        z = jnp.zeros((grads.shape[0], d_pad), grads.dtype)
        return lambda: _clip.clip_accum(z, bound, block_d=block,
                                        interpret=interpret)

    block, d_pad = choose_block(
        "clip_accum", grads.shape[-1],
        shape_key=(grads.shape[0], str(grads.dtype)),
        make_timed=timed, interpret=interpret)
    g, D = _pad_last(grads, d_pad)
    out = _clip.clip_accum(g, bound, block_d=block, interpret=interpret)
    return out[:D]


@partial(jax.jit, static_argnames=("interpret",))
def swa_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         nvalid: jax.Array,
                         interpret: bool | None = None) -> jax.Array:
    """Flash-style decode attention vs a (ring) KV cache.

    q: [B,H,Dh]; k,v: [B,C,KVH,Dh] (KV heads repeated to H by the caller or
    here when KVH divides H); nvalid: [1] int32 valid-slot count."""
    from repro.kernels import swa_decode as _swa
    interpret = _on_cpu() if interpret is None else interpret
    B, H, Dh = q.shape
    kvh = k.shape[2]
    if kvh != H:
        rep = H // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return _swa.swa_decode_attention(q, k, v,
                                     jnp.atleast_1d(nvalid).astype(jnp.int32),
                                     interpret=interpret)
