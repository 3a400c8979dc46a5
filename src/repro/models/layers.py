"""Shared neural-net building blocks (pure-functional, pytree params)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def he_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in or shape[0]
    return (jax.random.normal(key, shape) / jnp.sqrt(fan_in)).astype(dtype)


def rms_norm_init(dim, dtype):
    return {"scale": jnp.ones((dim,), dtype)}


def rms_norm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dtype)


def layer_norm_init(dim, dtype):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layer_norm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    out = x * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float, scaling=None) -> jax.Array:
    """Inverse frequencies [Dh/2]; with ``scaling`` (a ``YarnConfig``),
    YaRN's blend as DeepSeek-V2 computes it: the original frequencies below
    the correction range, those divided by ``factor`` above it, a linear
    ramp between."""
    freq_extra = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                             dtype=jnp.float32) / head_dim))
    if scaling is None:
        return freq_extra
    freq_inter = freq_extra / scaling.factor
    low, high = yarn_correction_range(head_dim, theta, scaling)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return freq_inter * (1.0 - mask) + freq_extra * mask


def yarn_correction_range(dim: int, theta: float, scaling) -> tuple:
    """YaRN's (low, high) dimension bounds, clamped to [0, dim - 1]."""
    def bound(beta):
        return dim * math.log(scaling.original_max_position_embeddings
                              / (beta * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(bound(scaling.beta_fast)), 0)
    high = min(math.ceil(bound(scaling.beta_slow)), dim - 1)
    return low, (high + 0.001 if high == low else high)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None) -> jax.Array:
    """x: [..., S, H, Dh]; positions: [..., S] (broadcastable).  With
    ``scaling`` (a ``YarnConfig``) the YaRN frequencies, and cos/sin
    scaled by mscale / mscale_all_dim."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta, scaling)              # [Dh/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, Dh/2]
    cos = jnp.cos(angles)[..., None, :]                        # [..., S, 1, Dh/2]
    sin = jnp.sin(angles)[..., None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu_init(key, d_model, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": he_init(k1, (d_model, d_ff), dtype),
        "w_up": he_init(k2, (d_model, d_ff), dtype),
        "w_down": he_init(k3, (d_ff, d_model), dtype, fan_in=d_ff),
    }


def swiglu(params, x):
    h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    return h @ params["w_down"]


def gelu_mlp_init(key, d_model, d_ff, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "w_in": he_init(k1, (d_model, d_ff), dtype),
        "b_in": jnp.zeros((d_ff,), dtype),
        "w_out": he_init(k2, (d_ff, d_model), dtype, fan_in=d_ff),
        "b_out": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp(params, x):
    h = jax.nn.gelu(x @ params["w_in"] + params["b_in"])
    return h @ params["w_out"] + params["b_out"]


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_init(key, vocab, d_model, dtype):
    return {"table": (jax.random.normal(key, (vocab, d_model)) * 0.02).astype(dtype)}


def embed(params, tokens):
    return params["table"][tokens]


def unembed(params, x):
    """Tied unembedding from the embed table."""
    return x @ params["table"].T


def lm_head_init(key, d_model, vocab, dtype):
    return {"w": he_init(key, (d_model, vocab), dtype)}


def lm_head(params, x):
    return x @ params["w"]


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array | None = None) -> jax.Array:
    """Mean token cross-entropy. logits: [..., V], labels: [...] int."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
