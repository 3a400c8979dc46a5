"""Model registry: builds any assigned architecture from a ModelConfig.

All families expose the same functional interface:

    model = Model(cfg)
    params = model.init(key)
    loss, aux = model.loss(params, batch)
    logits    = model.forward(params, batch)          # [B,S,V]
    cache     = model.init_cache(batch_size, cache_len)
    logits, cache = model.prefill(params, batch)      # fills cache
    logits, cache = model.decode_step(params, tokens, cache)

Layer stacks are stored with a leading layer dimension and executed with
``jax.lax.scan`` (one compiled block body regardless of depth).  Families:

  dense   pre-norm GQA/MLA + SwiGLU                   (yi, smollm, phi3, minicpm3)
  moe     dense attention + MoE FFN                   (mixtral, deepseek-v2-lite)
  ssm     Mamba2 (zamba backbone) / RWKV-6 stacks     (rwkv6)
  hybrid  Mamba2 stack + ONE shared attention block   (zamba2)
  vlm     dense backbone consuming [img_embeds; text] (llava-next-mistral)
  audio   whisper enc-dec with stub conv frontend     (whisper-tiny)
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers as nn
from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models import ssm as ssm_lib


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _stack_init(fn, key, n):
    """vmap an init over n layer keys -> params with leading layer dim."""
    return jax.vmap(fn)(jax.random.split(key, n))


# ===========================================================================
# Block bodies (single layer; scanned)
# ===========================================================================


def _attn_op(bp, h, positions, cfg, **kw):
    if cfg.attention == "mla":
        return attn.mla_forward(bp["attn"], h, positions, cfg, **kw)
    return attn.gqa_forward(bp["attn"], h, positions, cfg, **kw)


def _dense_block(bp, x, positions, cfg: ModelConfig, one_device=False):
    h = nn.rms_norm(bp["ln1"], x, cfg.norm_eps)
    x = x + _attn_op(bp, h, positions, cfg, one_device=one_device)
    h = nn.rms_norm(bp["ln2"], x, cfg.norm_eps)
    x = x + nn.swiglu(bp["mlp"], h)
    return x, jnp.zeros((), jnp.float32)


def _moe_block(bp, x, positions, cfg: ModelConfig, one_device=False):
    h = nn.rms_norm(bp["ln1"], x, cfg.norm_eps)
    x = x + _attn_op(bp, h, positions, cfg, one_device=one_device)
    h = nn.rms_norm(bp["ln2"], x, cfg.norm_eps)
    out, aux, counts = moe_lib.moe_forward(bp["moe"], h, cfg, one_device)
    return x + out, aux, counts


def _mamba_block(bp, x, cfg: ModelConfig):
    h = nn.rms_norm(bp["ln"], x, cfg.norm_eps)
    out, state = ssm_lib.mamba2_forward(bp["ssm"], h, cfg)
    return x + out, state


def _rwkv_block(bp, x, cfg: ModelConfig, state=None, att_x=None, ffn_x=None):
    h = nn.rms_norm(bp["ln1"], x, cfg.norm_eps)
    out, (new_state, new_att_x) = rwkv_lib.rwkv6_att_forward(
        bp["att"], h, cfg, state=state, prev_x=att_x)
    x = x + out
    h = nn.rms_norm(bp["ln2"], x, cfg.norm_eps)
    out, new_ffn_x = rwkv_lib.rwkv6_ffn_forward(bp["ffn"], h, prev_x=ffn_x)
    return x + out, (new_state, new_att_x, new_ffn_x)


# ===========================================================================
# Model
# ===========================================================================


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        # megatron-style vocab padding: embedding/lm-head tables are padded
        # to a multiple of 128 so vocab-parallel sharding divides evenly
        # (whisper 51865 -> 51968, minicpm3 73448 -> 73472).  Logits cover
        # the padded vocab; label ids stay < cfg.vocab_size.
        self.padded_vocab = -(-cfg.vocab_size // 128) * 128

    # ------------------------------------------------------------- init ---

    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        dt = _dtype(cfg)
        keys = jax.random.split(key, 8)
        params: Dict[str, Any] = {
            "embed": nn.embed_init(keys[0], self.padded_vocab, cfg.d_model,
                                   dt),
            "final_norm": nn.rms_norm_init(cfg.d_model, dt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = nn.lm_head_init(
                keys[1], cfg.d_model, self.padded_vocab, dt)

        fam = cfg.family
        if fam in ("dense", "vlm", "moe"):
            n_moe = cfg.num_layers
            n_dense_ff = 0
            if cfg.moe is not None and cfg.moe.first_dense_layers:
                n_dense_ff = cfg.moe.first_dense_layers
                n_moe = cfg.num_layers - n_dense_ff
            if cfg.moe is None:
                params["blocks"] = _stack_init(
                    lambda k: self._dense_block_init(k), keys[2],
                    cfg.num_layers)
            else:
                if n_dense_ff:
                    params["dense_blocks"] = _stack_init(
                        lambda k: self._dense_block_init(
                            k, d_ff=cfg.moe.first_dense_d_ff or cfg.d_ff),
                        keys[3], n_dense_ff)
                params["blocks"] = _stack_init(
                    lambda k: self._moe_block_init(k), keys[2], n_moe)
        elif fam == "ssm":  # rwkv6
            params["blocks"] = _stack_init(
                lambda k: self._rwkv_block_init(k), keys[2], cfg.num_layers)
        elif fam == "hybrid":  # zamba2
            params["blocks"] = _stack_init(
                lambda k: self._mamba_block_init(k), keys[2], cfg.num_layers)
            params["shared_attn"] = {
                "ln": nn.rms_norm_init(cfg.d_model, dt),
                "attn": attn.gqa_init(keys[4], cfg, dt),
                "ln2": nn.rms_norm_init(cfg.d_model, dt),
                "mlp": nn.swiglu_init(keys[5], cfg.d_model, cfg.d_ff, dt),
            }
        elif fam == "audio":  # whisper
            params["enc_blocks"] = _stack_init(
                lambda k: self._whisper_enc_block_init(k), keys[2],
                cfg.encoder_layers)
            params["enc_norm"] = nn.layer_norm_init(cfg.d_model, dt)
            params["blocks"] = _stack_init(
                lambda k: self._whisper_dec_block_init(k), keys[3],
                cfg.num_layers)
            params["dec_pos"] = (0.02 * jax.random.normal(
                keys[4], (cfg.max_seq_len if cfg.max_seq_len < 1 << 17
                          else 1 << 16, cfg.d_model))).astype(dt)
        else:
            raise ValueError(f"unknown family {fam!r}")
        return params

    def _dense_block_init(self, key, d_ff: int = 0):
        cfg = self.cfg
        dt = _dtype(cfg)
        k1, k2 = jax.random.split(key)
        a_init = attn.mla_init if cfg.attention == "mla" else attn.gqa_init
        return {
            "ln1": nn.rms_norm_init(cfg.d_model, dt),
            "attn": a_init(k1, cfg, dt),
            "ln2": nn.rms_norm_init(cfg.d_model, dt),
            "mlp": nn.swiglu_init(k2, cfg.d_model, d_ff or cfg.d_ff, dt),
        }

    def _moe_block_init(self, key):
        cfg = self.cfg
        dt = _dtype(cfg)
        k1, k2 = jax.random.split(key)
        a_init = attn.mla_init if cfg.attention == "mla" else attn.gqa_init
        return {
            "ln1": nn.rms_norm_init(cfg.d_model, dt),
            "attn": a_init(k1, cfg, dt),
            "ln2": nn.rms_norm_init(cfg.d_model, dt),
            "moe": moe_lib.moe_init(k2, cfg, dt),
        }

    def _mamba_block_init(self, key):
        cfg = self.cfg
        dt = _dtype(cfg)
        return {
            "ln": nn.rms_norm_init(cfg.d_model, dt),
            "ssm": ssm_lib.mamba2_init(key, cfg, dt),
        }

    def _rwkv_block_init(self, key):
        cfg = self.cfg
        dt = _dtype(cfg)
        k1, k2 = jax.random.split(key)
        return {
            "ln1": nn.rms_norm_init(cfg.d_model, dt),
            "att": rwkv_lib.rwkv6_att_init(k1, cfg, dt),
            "ln2": nn.rms_norm_init(cfg.d_model, dt),
            "ffn": rwkv_lib.rwkv6_ffn_init(k2, cfg, dt),
        }

    def _whisper_enc_block_init(self, key):
        cfg = self.cfg
        dt = _dtype(cfg)
        k1, k2 = jax.random.split(key)
        return {
            "ln1": nn.layer_norm_init(cfg.d_model, dt),
            "attn": attn.gqa_init(k1, cfg, dt),
            "ln2": nn.layer_norm_init(cfg.d_model, dt),
            "mlp": nn.gelu_mlp_init(k2, cfg.d_model, cfg.d_ff, dt),
        }

    def _whisper_dec_block_init(self, key):
        cfg = self.cfg
        dt = _dtype(cfg)
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "ln1": nn.layer_norm_init(cfg.d_model, dt),
            "attn": attn.gqa_init(k1, cfg, dt),
            "ln_x": nn.layer_norm_init(cfg.d_model, dt),
            "xattn": attn.gqa_init(k2, cfg, dt),
            "ln2": nn.layer_norm_init(cfg.d_model, dt),
            "mlp": nn.gelu_mlp_init(k3, cfg.d_model, cfg.d_ff, dt),
        }

    # --------------------------------------------------------- embedding ---

    def _embed_inputs(self, params, batch):
        """Returns (x [B,S,D], label_mask [B,S] or None)."""
        cfg = self.cfg
        x = nn.embed(params["embed"], batch["tokens"])
        mask = None
        if cfg.family == "vlm":
            img = batch["image_embeds"].astype(x.dtype)      # [B,Nimg,D]
            x = jnp.concatenate([img, x], axis=1)
            B, S = x.shape[:2]
            mask = (jnp.arange(S) >= img.shape[1]).astype(jnp.float32)
            mask = jnp.broadcast_to(mask, (B, S))
        if cfg.family == "audio":
            P = params["dec_pos"]
            pos = jnp.arange(x.shape[1]) % P.shape[0]
            x = x + P[pos]
        return x, mask

    # ------------------------------------------------------------ encoder --

    def _encode(self, params, frames):
        """Whisper encoder over stub frame embeddings [B,Se,D]."""
        cfg = self.cfg
        Se = frames.shape[1]
        pos = _sinusoidal(Se, cfg.d_model).astype(frames.dtype)
        x = frames + pos

        def body(x, bp):
            h = nn.layer_norm(bp["ln1"], x, cfg.norm_eps)
            x = x + attn.gqa_forward(bp["attn"], h, None, cfg,
                                     use_rope=False, causal=False)
            h = nn.layer_norm(bp["ln2"], x, cfg.norm_eps)
            x = x + nn.gelu_mlp(bp["mlp"], h)
            return x, None

        x, _ = jax.lax.scan(body, x, params["enc_blocks"])
        return nn.layer_norm(params["enc_norm"], x, cfg.norm_eps)

    # ------------------------------------------------------------ forward --

    REMAT_POLICIES = {
        None: None,
        "full": None,
        "dots": "dots_with_no_batch_dims_saveable",
        "nothing": "nothing_saveable",
    }

    def _ckpt(self, fn, remat, policy):
        if not remat:
            return fn
        pol_name = self.REMAT_POLICIES.get(policy, policy)
        pol = getattr(jax.checkpoint_policies, pol_name) if pol_name else None
        return jax.checkpoint(fn, policy=pol)

    def forward(self, params, batch, *, remat: bool = True,
                remat_policy: str | None = None, one_device: bool = False):
        """Full-sequence logits [B,S,V] (train / prefill compute path).

        one_device: the caller's operands live on one device, so causal
        self-attention may take the fused kernel (models/attention.py)."""
        cfg = self.cfg
        x, _ = self._embed_inputs(params, batch)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        fam = cfg.family

        if fam == "audio":
            enc_out = self._encode(params, batch["frames"])

            def dec_body(x, bp):
                h = nn.layer_norm(bp["ln1"], x, cfg.norm_eps)
                x = x + attn.gqa_forward(bp["attn"], h, positions, cfg,
                                         use_rope=False, causal=True,
                                         one_device=one_device)
                h = nn.layer_norm(bp["ln_x"], x, cfg.norm_eps)
                x = x + attn.gqa_forward(bp["xattn"], h, None, cfg,
                                         use_rope=False, causal=False,
                                         kv_src=enc_out)
                h = nn.layer_norm(bp["ln2"], x, cfg.norm_eps)
                x = x + nn.gelu_mlp(bp["mlp"], h)
                return x, None

            body = self._ckpt(dec_body, remat, remat_policy)
            x, _ = jax.lax.scan(body, x, params["blocks"])
            x = nn.rms_norm(params["final_norm"], x, cfg.norm_eps)
            return self._logits(params, x)

        if fam == "ssm":  # rwkv6
            def body(x, bp):
                x, _ = _rwkv_block(bp, x, cfg)
                return x, None

            body = self._ckpt(body, remat, remat_policy)
            x, _ = jax.lax.scan(body, x, params["blocks"])

        elif fam == "hybrid":  # zamba2: static groups of `every` mamba
            # layers followed by the shared attention block (no lax.cond:
            # exact flop accounting + one compiled body per group size)
            shared = params["shared_attn"]

            def mamba_stack(x, blocks):
                def body(x, bp):
                    x, _ = _mamba_block(bp, x, cfg)
                    return x, None
                b = self._ckpt(body, remat, remat_policy)
                x, _ = jax.lax.scan(b, x, blocks)
                return x

            def shared_block(x):
                h = nn.rms_norm(shared["ln"], x, cfg.norm_eps)
                x = x + attn.gqa_forward(shared["attn"], h, positions, cfg,
                                         one_device=one_device)
                h = nn.rms_norm(shared["ln2"], x, cfg.norm_eps)
                return x + nn.swiglu(shared["mlp"], h)

            for g0, g1, has_attn in _hybrid_groups(cfg):
                x = mamba_stack(x, jax.tree.map(
                    lambda b: b[g0:g1], params["blocks"]))
                if has_attn:
                    x = shared_block(x)

        else:  # dense / moe / vlm
            if "dense_blocks" in params:
                d_ff = cfg.moe.first_dense_d_ff or cfg.d_ff

                def dbody(x, bp):
                    x, _ = _dense_block(bp, x, positions, cfg, one_device)
                    return x, None

                dbody = self._ckpt(dbody, remat, remat_policy)
                x, _ = jax.lax.scan(dbody, x, params["dense_blocks"])

            def body(carry, bp):
                x, aux = carry
                if cfg.moe is None:
                    x, a = _dense_block(bp, x, positions, cfg, one_device)
                    return (x, aux + a), None
                x, a, counts = _moe_block(bp, x, positions, cfg, one_device)
                return (x, aux + a), counts

            body = self._ckpt(body, remat, remat_policy)
            (x, aux), counts = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)), params["blocks"])
            self._last_aux = aux
            if counts is not None:     # tokens per held expert, all layers
                self._last_expert_tokens = counts.sum(0)

        x = nn.rms_norm(params["final_norm"], x, cfg.norm_eps)
        return self._logits(params, x)

    def _logits(self, params, x):
        if self.cfg.tie_embeddings:
            return nn.unembed(params["embed"], x)
        return nn.lm_head(params["lm_head"], x)

    # --------------------------------------------------------------- loss --

    def loss(self, params, batch, *, remat: bool = True,
             remat_policy: str | None = None, one_device: bool = False):
        """Next-token CE; returns (loss, aux_dict)."""
        cfg = self.cfg
        self._last_aux = jnp.zeros((), jnp.float32)
        logits = self.forward(params, batch, remat=remat,
                              remat_policy=remat_policy,
                              one_device=one_device)
        labels = batch["labels"]
        if cfg.family == "vlm":
            # logits cover [img; text]; labels only cover text
            n_img = batch["image_embeds"].shape[1]
            logits = logits[:, n_img:, :]
        ce = nn.cross_entropy(logits, labels, batch.get("mask"))
        aux = getattr(self, "_last_aux", jnp.zeros((), jnp.float32))
        stats = {"ce": ce, "router_aux": aux}
        if cfg.moe is not None:
            stats["expert_tokens"] = self._last_expert_tokens
        return ce + aux, stats

    # -------------------------------------------------------------- cache --

    def init_cache(self, batch_size: int, cache_len: int):
        cfg = self.cfg
        dt = _dtype(cfg)
        fam = cfg.family
        L = cfg.num_layers
        if fam in ("dense", "vlm", "moe"):
            if cfg.attention == "mla":
                return attn.mla_init_cache(cfg, batch_size, cache_len, L, dt)
            return attn.gqa_init_cache(cfg, batch_size, cache_len, L, dt)
        if fam == "ssm":
            return rwkv_lib.rwkv6_init_cache(cfg, batch_size, L, dt)
        if fam == "hybrid":
            n_attn = L // cfg.hybrid_attn_every
            c = ssm_lib.mamba2_init_cache(cfg, batch_size, L, dt)
            kvc = attn.gqa_init_cache(cfg, batch_size, cache_len, n_attn, dt)
            c["attn_k"], c["attn_v"] = kvc["k"], kvc["v"]
            c["pos"] = jnp.zeros((), jnp.int32)
            return c
        if fam == "audio":
            c = attn.gqa_init_cache(cfg, batch_size, cache_len, L, dt)
            kv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
            Se = cfg.encoder_seq_len
            c["xk"] = jnp.zeros((L, batch_size, Se, kv, dh), dt)
            c["xv"] = jnp.zeros((L, batch_size, Se, kv, dh), dt)
            return c
        raise ValueError(fam)

    # -------------------------------------------------------------- decode --

    def decode_step(self, params, tokens, cache):
        """One token for every sequence. tokens: [B] int32."""
        cfg = self.cfg
        fam = cfg.family
        x = nn.embed(params["embed"], tokens[:, None])        # [B,1,D]
        pos = cache["pos"]
        if fam == "audio":
            P = params["dec_pos"]
            x = x + P[pos % P.shape[0]]

        if fam in ("dense", "vlm", "moe"):
            x = self._decode_dense(params, x, cache)
        elif fam == "ssm":
            x = self._decode_rwkv(params, x, cache)
        elif fam == "hybrid":
            x = self._decode_hybrid(params, x, cache)
        elif fam == "audio":
            x = self._decode_whisper(params, x, cache)
        cache["pos"] = pos + 1
        x = nn.rms_norm(params["final_norm"], x, cfg.norm_eps)
        return self._logits(params, x)[:, 0, :], cache

    def _decode_dense(self, params, x, cache):
        cfg = self.cfg
        pos = cache["pos"]
        mla = cfg.attention == "mla"

        if "dense_blocks" in params:
            nd = cfg.moe.first_dense_layers

            def dbody(x, inp):
                bp, *c = inp
                x, newc = self._dense_decode_block(bp, x, c, pos, swiglu=True)
                return x, newc

            if mla:
                xs = (params["dense_blocks"], cache["c_kv"][:nd],
                      cache["k_rope"][:nd])
            else:
                xs = (params["dense_blocks"], cache["k"][:nd],
                      cache["v"][:nd])
            x, newc = jax.lax.scan(dbody, x, xs)
            if mla:
                cache["c_kv"] = cache["c_kv"].at[:nd].set(newc[0])
                cache["k_rope"] = cache["k_rope"].at[:nd].set(newc[1])
            else:
                cache["k"] = cache["k"].at[:nd].set(newc[0])
                cache["v"] = cache["v"].at[:nd].set(newc[1])
        else:
            nd = 0

        is_moe = cfg.moe is not None

        def body(x, inp):
            bp, *c = inp
            x, newc = self._dense_decode_block(bp, x, c, pos,
                                               swiglu=not is_moe)
            return x, newc

        if mla:
            xs = (params["blocks"], cache["c_kv"][nd:], cache["k_rope"][nd:])
        else:
            xs = (params["blocks"], cache["k"][nd:], cache["v"][nd:])
        x, newc = jax.lax.scan(body, x, xs)
        if mla:
            cache["c_kv"] = cache["c_kv"].at[nd:].set(newc[0])
            cache["k_rope"] = cache["k_rope"].at[nd:].set(newc[1])
        else:
            cache["k"] = cache["k"].at[nd:].set(newc[0])
            cache["v"] = cache["v"].at[nd:].set(newc[1])
        return x

    def _dense_decode_block(self, bp, x, c, pos, *, swiglu: bool):
        cfg = self.cfg
        h = nn.rms_norm(bp["ln1"], x, cfg.norm_eps)
        if cfg.attention == "mla":
            out, nk, nv = attn.mla_decode(bp["attn"], h, c[0], c[1], pos, cfg)
        else:
            out, nk, nv = attn.gqa_decode(bp["attn"], h, c[0], c[1], pos, cfg,
                                          use_rope=cfg.attention == "gqa")
        x = x + out
        h = nn.rms_norm(bp["ln2"], x, cfg.norm_eps)
        if swiglu:
            x = x + nn.swiglu(bp["mlp"], h)
        else:
            out, *_ = moe_lib.moe_forward(bp["moe"], h, cfg)
            x = x + out
        return x, (nk, nv)

    def _decode_rwkv(self, params, x, cache):
        cfg = self.cfg

        def body(x, inp):
            bp, st, ax, fx = inp
            x, (nst, nax, nfx) = _rwkv_block(bp, x, cfg, state=st,
                                             att_x=ax, ffn_x=fx)
            return x, (nst, nax, nfx)

        x, (nst, nax, nfx) = jax.lax.scan(
            body, x, (params["blocks"], cache["wkv"], cache["att_x"],
                      cache["ffn_x"]))
        cache["wkv"], cache["att_x"], cache["ffn_x"] = nst, nax, nfx
        return x

    def _decode_hybrid(self, params, x, cache):
        cfg = self.cfg
        shared = params["shared_attn"]
        pos = cache["pos"]

        def body(x, inp):
            bp, h_st, conv_st = inp
            h = nn.rms_norm(bp["ln"], x, cfg.norm_eps)
            out, nh, nconv = ssm_lib.mamba2_decode(bp["ssm"], h, h_st,
                                                   conv_st, cfg)
            return x + out, (nh, nconv)

        nh_all, nconv_all, nk_all, nv_all = [], [], [], []
        slot = 0
        for g0, g1, has_attn in _hybrid_groups(cfg):
            sl = lambda t: t[g0:g1]
            x, (nh, nconv) = jax.lax.scan(
                body, x, (jax.tree.map(sl, params["blocks"]),
                          cache["h"][g0:g1], cache["conv"][g0:g1]))
            nh_all.append(nh)
            nconv_all.append(nconv)
            if has_attn:
                h = nn.rms_norm(shared["ln"], x, cfg.norm_eps)
                out, nk, nv = attn.gqa_decode(
                    shared["attn"], h, cache["attn_k"][slot],
                    cache["attn_v"][slot], pos, cfg)
                x = x + out
                h = nn.rms_norm(shared["ln2"], x, cfg.norm_eps)
                x = x + nn.swiglu(shared["mlp"], h)
                nk_all.append(nk)
                nv_all.append(nv)
                slot += 1
        cache["h"] = jnp.concatenate(nh_all, 0)
        cache["conv"] = jnp.concatenate(nconv_all, 0)
        cache["attn_k"] = jnp.stack(nk_all, 0)
        cache["attn_v"] = jnp.stack(nv_all, 0)
        return x

    def _decode_whisper(self, params, x, cache):
        cfg = self.cfg
        pos = cache["pos"]

        def body(x, inp):
            bp, k_l, v_l, xk_l, xv_l = inp
            h = nn.layer_norm(bp["ln1"], x, cfg.norm_eps)
            out, nk, nv = attn.gqa_decode(bp["attn"], h, k_l, v_l, pos, cfg,
                                          use_rope=False)
            x = x + out
            h = nn.layer_norm(bp["ln_x"], x, cfg.norm_eps)
            x = x + attn.cross_attend(bp["xattn"], h, xk_l, xv_l, cfg)
            h = nn.layer_norm(bp["ln2"], x, cfg.norm_eps)
            x = x + nn.gelu_mlp(bp["mlp"], h)
            return x, (nk, nv)

        x, (nk, nv) = jax.lax.scan(
            body, x, (params["blocks"], cache["k"], cache["v"],
                      cache["xk"], cache["xv"]))
        cache["k"], cache["v"] = nk, nv
        return x

    # ------------------------------------------------------------- prefill --

    def prefill(self, params, batch, max_len: int = 0):
        """Run the full prompt, build the decode cache, return last logits.

        max_len: cache capacity (>= prompt + expected decode tokens);
        defaults to prompt + 64.  Implemented as forward + cache extraction;
        used by serve drivers and lowered for the `prefill_32k` dry-run.
        """
        cfg = self.cfg
        fam = cfg.family
        x, _ = self._embed_inputs(params, batch)
        B, S, _ = x.shape
        cache = self.init_cache(B, max_len or S + 64)
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))

        if fam in ("dense", "vlm", "moe"):
            x, cache = self._prefill_dense(params, x, positions, cache)
        elif fam == "ssm":
            x, cache = self._prefill_rwkv(params, x, cache)
        elif fam == "hybrid":
            x, cache = self._prefill_hybrid(params, x, positions, cache)
        elif fam == "audio":
            x, cache = self._prefill_whisper(params, x, positions, cache,
                                             batch["frames"])
        cache["pos"] = jnp.asarray(S, jnp.int32)
        x = nn.rms_norm(params["final_norm"], x, cfg.norm_eps)
        return self._logits(params, x[:, -1:, :])[:, 0, :], cache

    def _fill_ring(self, cache_kv, k):
        """Write a full prefill sequence into a (possibly ring) cache.

        cache_kv: [B,C,KV,Dh]; k: [B,S,KV,Dh] with S tokens, C slots."""
        C = cache_kv.shape[1]
        S = k.shape[1]
        if S >= C:
            tail = k[:, S - C:]
            return jnp.roll(tail, (S - C) % C, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(cache_kv, k, 0, axis=1)

    def _prefill_dense(self, params, x, positions, cache):
        cfg = self.cfg
        mla = cfg.attention == "mla"

        def run_stack(x, blocks, is_moe):
            def body(x, bp):
                h = nn.rms_norm(bp["ln1"], x, cfg.norm_eps)
                if mla:
                    qn, qr, c_kv, k_rope = attn._mla_qkr(bp["attn"], h,
                                                         positions, cfg)
                    out = attn.mla_forward(bp["attn"], h, positions, cfg)
                    saved = (c_kv, k_rope)
                else:
                    kk = attn._split_heads(h @ bp["attn"]["w_k"],
                                           cfg.num_kv_heads,
                                           cfg.resolved_head_dim)
                    vv = attn._split_heads(h @ bp["attn"]["w_v"],
                                           cfg.num_kv_heads,
                                           cfg.resolved_head_dim)
                    kk = attn.apply_rope(kk, positions, cfg.rope_theta)
                    out = attn.gqa_forward(bp["attn"], h, positions, cfg)
                    saved = (kk, vv)
                x = x + out
                h = nn.rms_norm(bp["ln2"], x, cfg.norm_eps)
                if is_moe:
                    out, *_ = moe_lib.moe_forward(bp["moe"], h, cfg)
                    x = x + out
                else:
                    x = x + nn.swiglu(bp["mlp"], h)
                return x, saved

            return jax.lax.scan(body, x, blocks)

        nd = 0
        saved_all = []
        if "dense_blocks" in params:
            nd = cfg.moe.first_dense_layers
            x, saved = run_stack(x, params["dense_blocks"], False)
            saved_all.append(saved)
        x, saved = run_stack(x, params["blocks"], cfg.moe is not None)
        saved_all.append(saved)
        s0 = jnp.concatenate([s[0] for s in saved_all], 0) \
            if len(saved_all) > 1 else saved_all[0][0]
        s1 = jnp.concatenate([s[1] for s in saved_all], 0) \
            if len(saved_all) > 1 else saved_all[0][1]

        if mla:
            # caches [L,B,C,R]: write first S positions
            S = s0.shape[2]
            cache["c_kv"] = jax.lax.dynamic_update_slice_in_dim(
                cache["c_kv"], s0, 0, axis=2)
            cache["k_rope"] = jax.lax.dynamic_update_slice_in_dim(
                cache["k_rope"], s1, 0, axis=2)
        else:
            cache["k"] = jax.vmap(self._fill_ring)(cache["k"], s0)
            cache["v"] = jax.vmap(self._fill_ring)(cache["v"], s1)
        return x, cache

    def _prefill_rwkv(self, params, x, cache):
        cfg = self.cfg

        def body(x, bp):
            x, st = _rwkv_block(bp, x, cfg)
            return x, st

        x, (wkv, att_x, ffn_x) = jax.lax.scan(body, x, params["blocks"])
        cache["wkv"], cache["att_x"], cache["ffn_x"] = wkv, att_x, ffn_x
        return x, cache

    def _prefill_hybrid(self, params, x, positions, cache):
        cfg = self.cfg
        shared = params["shared_attn"]

        def body(x, bp):
            h = nn.rms_norm(bp["ln"], x, cfg.norm_eps)
            out, st = ssm_lib.mamba2_forward(bp["ssm"], h, cfg)
            return x + out, st

        h_all, conv_all, k_all, v_all = [], [], [], []
        for g0, g1, has_attn in _hybrid_groups(cfg):
            x, st = jax.lax.scan(
                body, x, jax.tree.map(lambda b: b[g0:g1], params["blocks"]))
            h_all.append(st["h"])
            conv_all.append(st["conv"])
            if has_attn:
                h = nn.rms_norm(shared["ln"], x, cfg.norm_eps)
                kk = attn._split_heads(h @ shared["attn"]["w_k"],
                                       cfg.num_kv_heads,
                                       cfg.resolved_head_dim)
                vv = attn._split_heads(h @ shared["attn"]["w_v"],
                                       cfg.num_kv_heads,
                                       cfg.resolved_head_dim)
                kk = attn.apply_rope(kk, positions, cfg.rope_theta)
                x = x + attn.gqa_forward(shared["attn"], h, positions, cfg)
                h2 = nn.rms_norm(shared["ln2"], x, cfg.norm_eps)
                x = x + nn.swiglu(shared["mlp"], h2)
                slot = len(k_all)
                k_all.append(self._fill_ring(cache["attn_k"][slot], kk))
                v_all.append(self._fill_ring(cache["attn_v"][slot], vv))
        cache["h"] = jnp.concatenate(h_all, 0)
        cache["conv"] = jnp.concatenate(conv_all, 0)
        cache["attn_k"] = jnp.stack(k_all, 0)
        cache["attn_v"] = jnp.stack(v_all, 0)
        return x, cache

    def _prefill_whisper(self, params, x, positions, cache, frames):
        cfg = self.cfg
        enc_out = self._encode(params, frames)

        def body(x, bp):
            h = nn.layer_norm(bp["ln1"], x, cfg.norm_eps)
            kk = attn._split_heads(h @ bp["attn"]["w_k"], cfg.num_kv_heads,
                                   cfg.resolved_head_dim)
            vv = attn._split_heads(h @ bp["attn"]["w_v"], cfg.num_kv_heads,
                                   cfg.resolved_head_dim)
            x = x + attn.gqa_forward(bp["attn"], h, positions, cfg,
                                     use_rope=False, causal=True)
            h = nn.layer_norm(bp["ln_x"], x, cfg.norm_eps)
            xk, xv = attn.cross_kv(bp["xattn"], enc_out, cfg)
            x = x + attn.cross_attend(bp["xattn"], h, xk, xv, cfg)
            h = nn.layer_norm(bp["ln2"], x, cfg.norm_eps)
            x = x + nn.gelu_mlp(bp["mlp"], h)
            return x, (kk, vv, xk, xv)

        x, (kk, vv, xk, xv) = jax.lax.scan(body, x, params["blocks"])
        cache["k"] = jax.vmap(self._fill_ring)(cache["k"], kk)
        cache["v"] = jax.vmap(self._fill_ring)(cache["v"], vv)
        cache["xk"], cache["xv"] = xk, xv
        return x, cache


def _hybrid_groups(cfg: ModelConfig):
    """Static (start, end, has_attn) layer groups for the zamba2 schedule:
    shared attention fires after every `hybrid_attn_every` mamba layers."""
    every = cfg.hybrid_attn_every
    L = cfg.num_layers
    groups = []
    i = 0
    while i < L:
        j = min(i + every, L)
        groups.append((i, j, j - i == every))
        i = j
    return groups


def _sinusoidal(length: int, dim: int) -> jax.Array:
    pos = np.arange(length)[:, None]
    i = np.arange(dim // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / dim)
    return jnp.asarray(
        np.concatenate([np.sin(angle), np.cos(angle)], axis=-1),
        jnp.float32)
