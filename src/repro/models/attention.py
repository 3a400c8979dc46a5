"""Attention variants: GQA (optional sliding window) and MLA (DeepSeek/MiniCPM).

Causal GQA self-attention (training and prefill) takes one of two paths
(MLA's takes the same gate, with JAX's Pallas TPU splash kernel as its fused
path, since its value heads are narrower than its query/key heads):

- the fused kernel, ``jax.experimental.pallas.ops.tpu.flash_attention``
  with its fused dq and dk/dv backward kernels: the running max, sum and
  logsumexp stay in VMEM in float32, key blocks entirely above the
  diagonal are skipped, and no [S, S] tensor reaches HBM.  It engages
  only where the call shows it can: a TPU backend, causal self-attention,
  no sliding window shorter than S, S a multiple of ``FLASH_BLOCK``, and
  operands the caller vouches live on one device (``one_device``; a
  Mosaic call cannot be partitioned by GSPMD);
- everywhere else, query-chunked attention (``_chunked_causal_attention``):
  each chunk's float32 scores against its key slice are materialized, so
  the [S, S] matrix is never whole (a 32k prefill would otherwise need
  O(S^2) HBM).  Sliding-window archs additionally restrict the key slice
  per chunk, making prefill sub-quadratic and allowing a ring-buffer KV
  cache of just `window` slots — this is what makes `long_500k` feasible
  for SWA archs.

Each ``gqa_forward`` and ``mla_forward`` trace emits one ``kernel``
telemetry record (``op="flash_attention"``) saying which path it took and
why.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig
from repro.models.layers import apply_rope, he_init, yarn_mscale

NEG_INF = -1e30

# The fused kernel's q and k block, forward and both backward kernels.  On
# a TPU v5e at S=2048, Dh=64 a forward and backward took 2.16 ms at 512,
# 2.29 at 1024, 2.82 at 256 and 4.70 at 128 (PERF.md §6).
FLASH_BLOCK = 512
# MLA's (splash): at B=2, S=4096, 16 heads of 192 / 128 on a v5e a forward
# and backward took 9.28 ms at 1024 and 9.94 at 512; flash_attention with
# both head dims padded to 256 took 13.74 at 512 (PERF.md §6).
SPLASH_BLOCK = 1024


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def gqa_init(key, cfg: ModelConfig, dtype):
    d, h, kv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "w_q": he_init(k1, (d, h * dh), dtype),
        "w_k": he_init(k2, (d, kv * dh), dtype),
        "w_v": he_init(k3, (d, kv * dh), dtype),
        "w_o": he_init(k4, (h * dh, d), dtype, fan_in=h * dh),
    }


def _split_heads(x, n, dh):
    return x.reshape(*x.shape[:-1], n, dh)


def _gqa_scores(q, k):
    """q: [B,Sq,KV,G,Dh], k: [B,Sk,KV,Dh] -> [B,KV,G,Sq,Sk]."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q.astype(jnp.float32),
                      k.astype(jnp.float32))


def _chunked_causal_attention(q, k, v, *, window: int, chunk: int):
    """q: [B,S,KV,G,Dh]; k,v: [B,S,KV,Dh]. Causal (+ optional window) attention
    computed in query chunks; never materializes [S,S]."""
    B, S, KV, G, Dh = q.shape
    scale = 1.0 / jnp.sqrt(Dh).astype(jnp.float32)
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    n_chunks = S // chunk

    # key slice length per chunk: window-limited if SWA else full prefix
    if window and window < S:
        klen = chunk + window  # keys [q0 - window, q0 + chunk)
    else:
        klen = S

    def one_chunk(ci):
        q0 = ci * chunk
        qc = jax.lax.dynamic_slice_in_dim(q, q0, chunk, axis=1)
        if klen == S:
            kc, vc, k0 = k, v, 0
        else:
            k0 = jnp.maximum(q0 - window, 0)
            k0 = jnp.minimum(k0, S - klen)
            kc = jax.lax.dynamic_slice_in_dim(k, k0, klen, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, k0, klen, axis=1)
        s = _gqa_scores(qc, kc) * scale                      # [B,KV,G,chunk,klen]
        qpos = q0 + jnp.arange(chunk)
        kpos = k0 + jnp.arange(klen)
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask, s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", w, vc.astype(jnp.float32))
        return out.astype(q.dtype)

    outs = jax.lax.map(one_chunk, jnp.arange(n_chunks))      # [n,B,chunk,KV,G,Dh]
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, KV, G, Dh)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_fallback_reason(S: int, *, causal: bool, cross: bool, window: int,
                          one_device: bool, block: int | None = None) -> str:
    """Why the fused kernel (of q and k blocks ``block``, by default
    ``FLASH_BLOCK``) does not take this attention; '' when it does."""
    if cross:
        return "cross_attention"
    if not causal:
        return "not_causal"
    if window and window < S:
        return "sliding_window"
    if S % (block or FLASH_BLOCK):
        return "seq_not_block_multiple"
    if not one_device:
        return "not_one_device"
    if not _on_tpu():
        return f"backend_{jax.default_backend()}"
    return ""


def _flash_causal_attention(q, k, v, *, block: int):
    """q: [B,S,KV,G,Dh]; k,v: [B,S,KV,Dh]. Causal attention by the fused
    Pallas TPU kernel, K and V repeated over each KV head's G query heads
    (head h = kv * G + g, as ``q``'s reshape groups them)."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    B, S, KV, G, Dh = q.shape

    def heads(x):                                    # [B,S,H,Dh]<->[B,H,S,Dh]
        return jnp.swapaxes(x, 1, 2)

    sizes = fa.BlockSizes(
        block_q=block, block_k_major=block, block_k=block, block_b=1,
        block_q_major_dkv=block, block_k_major_dkv=block, block_k_dkv=block,
        block_q_dkv=block, block_k_major_dq=block, block_k_dq=block,
        block_q_dq=block)
    out = fa.flash_attention(
        heads(q.reshape(B, S, KV * G, Dh)),
        heads(jnp.repeat(k, G, axis=2)), heads(jnp.repeat(v, G, axis=2)),
        causal=True, sm_scale=1.0 / float(Dh) ** 0.5, block_sizes=sizes)
    return heads(out).reshape(B, S, KV, G, Dh)


def gqa_forward(params, x, positions, cfg: ModelConfig, *, chunk: int = 1024,
                use_rope: bool = True, causal: bool = True,
                kv_src: jax.Array | None = None, one_device: bool = False):
    """Training/prefill attention. x: [B,S,D] -> [B,S,D].

    kv_src: optional separate K/V source sequence (cross-attention); implies
    non-causal full attention over kv_src.  one_device: the caller's
    operands live on one device, so the fused kernel may take causal
    self-attention (see the module docstring).
    """
    h, kv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    B, S, _ = x.shape
    src = x if kv_src is None else kv_src
    q = _split_heads(x @ params["w_q"], h, dh)
    k = _split_heads(src @ params["w_k"], kv, dh)
    v = _split_heads(src @ params["w_v"], kv, dh)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, jnp.broadcast_to(jnp.arange(src.shape[1]),
                                           src.shape[:2]), cfg.rope_theta)
    q = q.reshape(B, S, kv, h // kv, dh)
    reason = flash_fallback_reason(
        S, causal=causal, cross=kv_src is not None,
        window=cfg.sliding_window, one_device=one_device)
    from repro.kernels.ops import _emit_kernel   # Pallas: import on use
    _emit_kernel(op="flash_attention", engaged=int(not reason),
                 reason=reason, seq_len=S, heads=h, head_dim=dh)
    if not reason:
        out = _flash_causal_attention(q, k, v, block=FLASH_BLOCK)
    elif causal and kv_src is None:
        out = _chunked_causal_attention(q, k, v, window=cfg.sliding_window,
                                        chunk=chunk)
    else:
        s = _gqa_scores(q, k) / jnp.sqrt(dh)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", w,
                         v.astype(jnp.float32)).astype(x.dtype)
    return out.reshape(B, S, h * dh) @ params["w_o"]


# --- KV cache -----------------------------------------------------------


def gqa_cache_len(cfg: ModelConfig, seq_len: int) -> int:
    """Ring-buffer length: `window` slots for SWA archs, else full seq."""
    if cfg.sliding_window and cfg.sliding_window < seq_len:
        return cfg.sliding_window
    return seq_len


def gqa_init_cache(cfg: ModelConfig, batch: int, seq_len: int, n_layers: int,
                   dtype) -> dict:
    kv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    clen = gqa_cache_len(cfg, seq_len)
    return {
        "k": jnp.zeros((n_layers, batch, clen, kv, dh), dtype),
        "v": jnp.zeros((n_layers, batch, clen, kv, dh), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def gqa_decode(params, x, layer_cache_k, layer_cache_v, pos, cfg: ModelConfig,
               *, use_rope: bool = True):
    """Single-token decode. x: [B,1,D]; caches [B,C,KV,Dh]; pos: tokens so far.

    Returns (out [B,1,D], new_k, new_v).
    """
    h, kv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    B = x.shape[0]
    C = layer_cache_k.shape[1]
    q = _split_heads(x @ params["w_q"], h, dh)
    k = _split_heads(x @ params["w_k"], kv, dh)
    v = _split_heads(x @ params["w_v"], kv, dh)
    if use_rope:
        posv = jnp.full((B, 1), pos)
        q = apply_rope(q, posv, cfg.rope_theta)
        k = apply_rope(k, posv, cfg.rope_theta)

    slot = jnp.mod(pos, C)
    new_k = jax.lax.dynamic_update_slice_in_dim(layer_cache_k, k, slot, axis=1)
    new_v = jax.lax.dynamic_update_slice_in_dim(layer_cache_v, v, slot, axis=1)

    qh = q.reshape(B, 1, kv, h // kv, dh)
    s = _gqa_scores(qh, new_k) / jnp.sqrt(dh)                # [B,KV,G,1,C]
    valid = jnp.arange(C) < jnp.minimum(pos + 1, C)
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, new_v.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(B, 1, h * dh)
    return out @ params["w_o"], new_k, new_v


def cross_attend(params, x, k_cache, v_cache, cfg: ModelConfig):
    """Cross-attention against precomputed (encoder) K/V. x: [B,Sq,D];
    k_cache/v_cache: [B,Se,KV,Dh]."""
    h, kv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    B, Sq, _ = x.shape
    q = _split_heads(x @ params["w_q"], h, dh).reshape(B, Sq, kv, h // kv, dh)
    s = _gqa_scores(q, k_cache) / jnp.sqrt(dh)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v_cache.astype(jnp.float32))
    return out.astype(x.dtype).reshape(B, Sq, h * dh) @ params["w_o"]


def cross_kv(params, enc_out, cfg: ModelConfig):
    """Precompute cross-attention K/V from encoder output [B,Se,D]."""
    kv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    k = _split_heads(enc_out @ params["w_k"], kv, dh)
    v = _split_heads(enc_out @ params["w_v"], kv, dh)
    return k, v


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention)
# ---------------------------------------------------------------------------


def mla_init(key, cfg: ModelConfig, dtype):
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    ks = jax.random.split(key, 8)
    p = {
        "w_dkv": he_init(ks[0], (d, m.kv_lora_rank), dtype),
        "w_kr": he_init(ks[1], (d, dr), dtype),
        "w_uk": he_init(ks[2], (m.kv_lora_rank, h * dn), dtype,
                        fan_in=m.kv_lora_rank),
        "w_uv": he_init(ks[3], (m.kv_lora_rank, h * dv), dtype,
                        fan_in=m.kv_lora_rank),
        "w_o": he_init(ks[4], (h * dv, d), dtype, fan_in=h * dv),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
    }
    if m.q_lora_rank:
        p["w_dq"] = he_init(ks[5], (d, m.q_lora_rank), dtype)
        p["w_uq"] = he_init(ks[6], (m.q_lora_rank, h * (dn + dr)), dtype,
                            fan_in=m.q_lora_rank)
        p["q_norm"] = jnp.ones((m.q_lora_rank,), dtype)
    else:
        p["w_q"] = he_init(ks[7], (d, h * (dn + dr)), dtype)
    return p


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


def _mla_qkr(params, x, positions, cfg: ModelConfig):
    """Shared q / compressed-kv / rope-key computation."""
    m: MLAConfig = cfg.mla
    h = cfg.num_heads
    dn, dr = m.qk_nope_head_dim, m.qk_rope_head_dim
    B, S, _ = x.shape
    eps, yarn = cfg.norm_eps, cfg.rope_scaling
    if m.q_lora_rank:
        q = _rms(x @ params["w_dq"], params["q_norm"], eps) @ params["w_uq"]
    else:
        q = x @ params["w_q"]
    q = q.reshape(B, S, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, yarn)
    c_kv = _rms(x @ params["w_dkv"], params["kv_norm"], eps)  # [B,S,R]
    k_rope = apply_rope((x @ params["w_kr"])[:, :, None, :], positions,
                        cfg.rope_theta, yarn)[:, :, 0, :]      # [B,S,dr]
    return q_nope, q_rope, c_kv, k_rope


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(dn + dr)^-1/2, times YaRN's mscale(factor, mscale_all_dim)^2 where
    the rope is YaRN-scaled (DeepSeek-V2's attention)."""
    m: MLAConfig = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _splash_kernel(S: int, heads: int, block: int):
    """Splash attention's causal kernel for [heads, S, .] operands, one
    device, q and k blocks of ``block`` in the forward and both backward
    kernels.  Made per trace: the kernel holds the mask's block map as
    arrays of the trace it was made in."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    mask = sm.MultiHeadMask([sm.CausalMask((S, S))] * heads)
    sizes = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    return sk.make_splash_mha_single_device(mask, block_sizes=sizes)


def _splash_causal_attention(q, k, v, *, scale: float, block: int):
    """q, k: [B,S,H,Dqk]; v: [B,S,H,Dv] -> [B,S,H,Dv].  Causal attention by
    the Pallas TPU splash kernel, which takes a value head dim other than
    the query/key one (MLA's 128 against 192).  The kernel applies no
    scale, so q is scaled first."""
    B, S, H, _ = q.shape
    kernel = _splash_kernel(S, H, block)

    def heads(x):                                    # [B,S,H,D]<->[B,H,S,D]
        return jnp.swapaxes(x, 1, 2)

    out = jax.vmap(kernel)(heads(q * jnp.asarray(scale, q.dtype)), heads(k),
                           heads(v))
    return heads(out)


def mla_forward(params, x, positions, cfg: ModelConfig, *, chunk: int = 1024,
                one_device: bool = False):
    """Training/prefill MLA (unabsorbed). x: [B,S,D].

    Causal attention takes the fused splash kernel where
    ``flash_fallback_reason`` allows it (as GQA's fused path), else the
    query-chunked float32 path below.  Each trace emits one ``kernel``
    record (``op="flash_attention"``)."""
    m: MLAConfig = cfg.mla
    h = cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    B, S, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _mla_qkr(params, x, positions, cfg)
    k_nope = (c_kv @ params["w_uk"]).reshape(B, S, h, dn)
    v = (c_kv @ params["w_uv"]).reshape(B, S, h, dv)
    scale = mla_softmax_scale(cfg)
    reason = flash_fallback_reason(S, causal=True, cross=False, window=0,
                                   one_device=one_device, block=SPLASH_BLOCK)
    from repro.kernels.ops import _emit_kernel   # Pallas: import on use
    _emit_kernel(op="flash_attention", engaged=int(not reason),
                 reason=reason, seq_len=S, heads=h, head_dim=dn + dr,
                 head_dim_v=dv)
    if not reason:
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, :, None, :], (B, S, h, dr))], axis=-1)
    with jax.named_scope("mla.attention"):
        if not reason:
            out = _splash_causal_attention(q, k, v, scale=scale,
                                           block=SPLASH_BLOCK)
        else:
            out = _mla_chunked_attention(q_nope, q_rope, k_nope, k_rope, v,
                                         scale=scale, chunk=chunk)
    return out.reshape(B, S, h * dv) @ params["w_o"]


def _mla_chunked_attention(q_nope, q_rope, k_nope, k_rope, v, *,
                           scale: float, chunk: int):
    """Causal MLA attention in query chunks of float32 scores against all
    keys. q_nope, k_nope: [B,S,H,dn]; q_rope: [B,S,H,dr]; k_rope: [B,S,dr];
    v: [B,S,H,dv] -> [B,S,H,dv]."""
    B, S, h, dv = v.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2

    def one_chunk(ci):
        q0 = ci * chunk
        qn = jax.lax.dynamic_slice_in_dim(q_nope, q0, chunk, axis=1)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, q0, chunk, axis=1)
        s = jnp.einsum("bqhd,bshd->bhqs", qn.astype(jnp.float32),
                       k_nope.astype(jnp.float32))
        s += jnp.einsum("bqhd,bsd->bhqs", qr.astype(jnp.float32),
                        k_rope.astype(jnp.float32))
        s *= scale
        qpos = q0 + jnp.arange(chunk)
        mask = jnp.arange(S)[None, :] <= qpos[:, None]
        s = jnp.where(mask, s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqs,bshd->bqhd", w, v.astype(jnp.float32))
        return out.astype(v.dtype)

    outs = jax.lax.map(one_chunk, jnp.arange(S // chunk))
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, h, dv)


def mla_init_cache(cfg: ModelConfig, batch: int, seq_len: int, n_layers: int,
                   dtype) -> dict:
    m: MLAConfig = cfg.mla
    return {
        "c_kv": jnp.zeros((n_layers, batch, seq_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((n_layers, batch, seq_len, m.qk_rope_head_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def mla_decode(params, x, cache_ckv, cache_kr, pos, cfg: ModelConfig):
    """Absorbed-matmul MLA decode: attends in the compressed latent space.

    x: [B,1,D]; cache_ckv: [B,C,R]; cache_kr: [B,C,dr].
    """
    m: MLAConfig = cfg.mla
    h = cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    B = x.shape[0]
    C = cache_ckv.shape[1]
    posv = jnp.full((B, 1), pos)
    q_nope, q_rope, c_kv, k_rope = _mla_qkr(params, x, posv, cfg)

    new_ckv = jax.lax.dynamic_update_slice_in_dim(cache_ckv, c_kv, pos, axis=1)
    new_kr = jax.lax.dynamic_update_slice_in_dim(cache_kr, k_rope, pos, axis=1)

    # absorb W_uk into q:  q_eff[h,R] = q_nope[h,dn] @ W_uk[R, h*dn] slice
    w_uk = params["w_uk"].reshape(m.kv_lora_rank, h, dn)
    q_eff = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))               # [B,1,h,R]
    s = jnp.einsum("bqhr,bsr->bhqs", q_eff, new_ckv.astype(jnp.float32))
    s += jnp.einsum("bqhd,bsd->bhqs", q_rope.astype(jnp.float32),
                    new_kr.astype(jnp.float32))
    s *= mla_softmax_scale(cfg)
    valid = jnp.arange(C) <= pos
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)                             # [B,h,1,C]
    ctx = jnp.einsum("bhqs,bsr->bqhr", w, new_ckv.astype(jnp.float32))
    # absorb W_uv on the way out
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, h, dv)
    out = jnp.einsum("bqhr,rhd->bqhd", ctx, w_uv.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(B, 1, h * dv)
    return out @ params["w_o"], new_ckv, new_kr
