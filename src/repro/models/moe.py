"""Mixture-of-Experts layer: a top-k router over all routed experts, and
one of three dispatches (``MoEConfig.dispatch``).

``dropless`` (DeepSeek-V2): no capacity and no token dropped.  The layer
holds ``experts_held`` of the routed experts, ids ``[expert_offset,
expert_offset + held)`` (expert parallelism: the rest live on other
chips), routes every token over all of them, and computes only its own
experts' part of the result.  Assignments to held experts are sorted by
expert into a buffer of static worst-case size, ``T * min(top_k, held)``
rows, and run through a grouped matmul over the held experts (JAX's Pallas
TPU megablox ``gmm``, with its ``tgmm`` backward: each group's rows times
its expert's weights; the tiles of the buffer's empty tail are not
visited; off a TPU the same kernel runs in interpret mode).  Each result
is scattered back to its token, weighted by its gate.  Assignments to
experts held elsewhere contribute nothing here; no stand-in is computed
for them.

``global`` / ``row`` (capacity dispatch, MegaBlocks-style adapted to static
TPU shapes): token->expert assignments are stably sorted by expert id and
scattered into a dense [E, C, D] buffer of capacity
``C = capacity_factor * T * top_k / E``; assignments past C are dropped.
``global`` pools the whole [B, S] batch, ``row`` dispatches each batch row
on its own so the scatter stays local to a data shard.  Every expert is
held.

Shared experts (DeepSeek-style) run densely on every token.  The balance
loss is Switch-style over the token pool, or DeepSeek-V2's per-sequence
expert-level loss (``balance="sequence"``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.layers import he_init, swiglu, swiglu_init

# The grouped matmul's (rows, contraction, output) tile, capped at each
# call's own sizes; the rows of the dispatch buffer are a multiple of the
# first.  On a TPU v5e, DeepSeek-V2-Lite's three expert matmuls forward and
# backward over 6,144 of 49,152 rows took 7.36 ms at (256, 512, 512), 7.39
# at (512, 512, 512), 7.59 at (512, 1024, 512), 7.75 at (512, 1024, 1024)
# and 34.0 at (128, 128, 128) (PERF.md §6).
GMM_TILE = (256, 512, 512)


def moe_init(key, cfg: ModelConfig, dtype):
    m: MoEConfig = cfg.moe
    d = cfg.d_model
    dff = m.expert_d_ff or cfg.d_ff
    k_r, k_e, k_s = jax.random.split(key, 3)
    ke = jax.random.split(k_e, 3)
    p = {
        "router": he_init(k_r, (d, m.num_experts), dtype),
        # stacked weights of the held experts [H, ...] -> expert-parallel
        "w_gate": he_init(ke[0], (m.held, d, dff), dtype, fan_in=d),
        "w_up": he_init(ke[1], (m.held, d, dff), dtype, fan_in=d),
        "w_down": he_init(ke[2], (m.held, dff, d), dtype, fan_in=dff),
    }
    if m.num_shared_experts:
        p["shared"] = swiglu_init(k_s, d, dff * m.num_shared_experts, dtype)
    return p


def capacity(tokens: int, m: MoEConfig) -> int:
    cap = int(m.capacity_factor * tokens * m.top_k / m.num_experts)
    cap = max(cap, m.top_k, 4)
    return (cap + 3) // 4 * 4  # pad to a friendly multiple


def moe_forward(params, x, cfg: ModelConfig, one_device: bool = False):
    """x: [B,S,D] -> (out [B,S,D], aux_loss scalar, tokens per held
    expert [H] int32, before any capacity drop).

    one_device: the caller's operands live on one device, so the dropless
    dispatch's grouped matmul may run compiled for the chip (a Mosaic call
    cannot be partitioned by GSPMD); elsewhere it runs interpreted.

    dispatch="global": one token pool across the whole [B,S] batch — higher
    quality capacity allocation but the scatter crosses batch shards (XLA
    inserts an all-reduce of the full expert buffer when B is data-sharded).
    dispatch="row": independent dispatch per batch row — the scatter stays
    local to each data shard (§Perf hillclimb 2).
    dispatch="dropless": see the module docstring.
    """
    m: MoEConfig = cfg.moe
    if m.dispatch == "dropless":
        return _moe_dropless(params, x, cfg, one_device)
    if m.held != m.num_experts:
        raise ValueError("only the dropless dispatch holds part of the "
                         f"experts ({m.held} of {m.num_experts})")
    if m.dispatch == "row":
        outs, auxes, counts = jax.vmap(lambda row: _moe_tokens(
            params, row, cfg))(x)
        return outs, auxes.mean(), counts.sum(0)
    B, S, D = x.shape
    out, aux, counts = _moe_tokens(params, x.reshape(B * S, D), cfg)
    return out.reshape(B, S, D), aux, counts


def _moe_tokens(params, xf, cfg: ModelConfig):
    """Core dispatch over a flat token pool. xf: [T,D]."""
    m: MoEConfig = cfg.moe
    T, D = xf.shape
    E = m.num_experts
    C = capacity(T, m)
    logits = (xf @ params["router"]).astype(jnp.float32)      # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, m.top_k)       # [T,k]
    if m.norm_topk:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- sort-based dispatch --------------------------------------------
    e_flat = gate_idx.reshape(-1)                             # [T*k]
    w_flat = gate_vals.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(T), m.top_k)

    order = jnp.argsort(e_flat, stable=True)
    e_s, tok_s, w_s = e_flat[order], tok_flat[order], w_flat[order]

    counts = jnp.zeros((E,), jnp.int32).at[e_s].add(1)
    offsets = jnp.cumsum(counts) - counts                     # group starts
    pos = jnp.arange(T * m.top_k) - offsets[e_s]              # rank in group
    keep = pos < C
    dest = e_s * C + jnp.clip(pos, 0, C - 1)                  # [T*k]

    # expert input buffer [E*C, D] (unique dest among kept entries)
    upd = jnp.where(keep[:, None], xf[tok_s], 0).astype(xf.dtype)
    xe = jnp.zeros((E * C, D), xf.dtype).at[dest].add(
        upd, mode="drop").reshape(E, C, D)

    # ---- expert FFN (E shardable over "model") ---------------------------
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"]).reshape(E * C, D)

    # ---- combine ---------------------------------------------------------
    gathered = ye[dest] * (w_s * keep)[:, None].astype(xf.dtype)
    out = jnp.zeros((T, D), xf.dtype).at[tok_s].add(gathered, mode="drop")

    if m.num_shared_experts:
        out = out + swiglu(params["shared"], xf)

    # load-balancing aux loss (Switch-style)
    me = probs.mean(axis=0)                                   # [E]
    frac = counts.astype(jnp.float32) / jnp.maximum(T * m.top_k, 1)
    aux = m.router_aux_coef * E * jnp.sum(me * frac)
    return out, aux, counts


def _moe_dropless(params, x, cfg: ModelConfig, one_device: bool):
    """The dropless dispatch over the held experts. x: [B,S,D]."""
    m: MoEConfig = cfg.moe
    B, S, D = x.shape
    T, E, K, H = B * S, m.num_experts, m.top_k, m.held
    xf = x.reshape(T, D)
    with jax.named_scope("moe.route"):
        # the published gate is a float32 linear map
        logits = jnp.dot(xf.astype(jnp.float32),
                         params["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)  # [T,E]
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, K)          # [T,K]
        if m.norm_topk:
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(-1, keepdims=True), 1e-9)
        aux = balance_loss(probs.reshape(B, S, E),
                           gate_idx.reshape(B, S, K), m)

    with jax.named_scope("moe.dispatch"):
        local = gate_idx.reshape(-1) - m.expert_offset         # [T*K]
        group = jnp.where((local >= 0) & (local < H), local, H)
        counts = jnp.zeros((H,), jnp.int32).at[group].add(1, mode="drop")
        # top-k ids are distinct, so a token has at most min(K, H) held
        # assignments: the held ones sort first and fit in R0 rows; the
        # buffer is R0 rounded up to the grouped matmul's row tile
        R0 = T * min(K, H)
        tm = min(GMM_TILE[0], -(-R0 // 8) * 8)
        R = -(-R0 // tm) * tm
        order = jnp.argsort(group, stable=True)[:R0]
        if R > R0:
            order = jnp.concatenate([order, jnp.full((R - R0,), T * K,
                                                     order.dtype)])
        valid = jnp.concatenate([group, jnp.full((1,), H)])[order] < H
        tok = jnp.minimum(order // K, T - 1)
        xs = jnp.where(valid[:, None], xf[tok], 0)

    reason = ("not_one_device" if not one_device else
              "" if _on_tpu() else f"backend_{jax.default_backend()}")
    with jax.named_scope("moe.experts"):
        def gmm(a, w):
            from jax.experimental.pallas.ops.tpu.megablox import ops as mb
            k, n = w.shape[1:]
            return mb.gmm(a, w, counts, preferred_element_type=a.dtype,
                          tiling=(tm, min(GMM_TILE[1], k),
                                  min(GMM_TILE[2], n)),
                          interpret=bool(reason))
        h = jax.nn.silu(gmm(xs, params["w_gate"])) * gmm(xs, params["w_up"])
        ys = gmm(h, params["w_down"])                           # [R,D]

    with jax.named_scope("moe.combine"):
        # rows past the groups are never written by the grouped matmul:
        # select, so that whatever they hold reaches nothing
        w = jnp.concatenate([gate_vals.reshape(-1), jnp.zeros((1,))])[order]
        ys = jnp.where(valid[:, None], ys.astype(jnp.float32), 0.0)
        out = jnp.zeros((T, D), jnp.float32).at[tok].add(ys * w[:, None])

    if m.num_shared_experts:
        with jax.named_scope("moe.shared"):
            out = out + swiglu(params["shared"], xf).astype(jnp.float32)

    from repro.kernels.ops import _emit_kernel   # Pallas: import on use
    _emit_kernel(op="moe_gmm", engaged=int(not reason), reason=reason,
                 experts_total=E, experts_held=H, top_k=K, tokens=T,
                 buffer_rows=R)
    return out.astype(x.dtype).reshape(B, S, D), aux, counts


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def balance_loss(probs, idx, m: MoEConfig):
    """The router's balance loss.  probs: [B,S,E] softmax scores; idx:
    [B,S,K] chosen experts.

    ``sequence`` (DeepSeek-V2's expert-level loss): per sequence
    ``alpha * sum_i f_i P_i`` with ``f_i = E / (K S) * #{t : i in
    topK(t)}`` and ``P_i`` the sequence's mean score of expert i, averaged
    over sequences.  ``switch``: the same over the whole token pool."""
    B, S, E = probs.shape
    K = idx.shape[-1]
    if m.balance == "switch":
        probs, idx = probs.reshape(1, B * S, E), idx.reshape(1, B * S, K)
        S = B * S
    chosen = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=(1, 2))
    f = chosen * (E / (K * S))                                 # [B',E]
    return m.router_aux_coef * jnp.mean(jnp.sum(f * probs.mean(axis=1), -1))
