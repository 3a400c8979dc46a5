"""Plain reference for DeepSeek-V2-Lite (one chip's share) under GFL, in
float32 jax.numpy at the highest matmul precision.

It imports nothing of the program.  The GFL round (per-client clip, server
mean, psi in bfloat16, graph-homomorphic Laplace noise, the combine), the
token rows (``draw_batches``), ``laplace`` and ``ring_matrix`` are
smollm-135m's reference, loaded from its file; this file gives the model:

* multi-head latent attention, unabsorbed: q = x W_q split into a 128-dim
  part and a 64-dim rope part per head; the key/value latent c = RMSNorm(x
  W_dkv) (512 wide); k = [c W_uk | rope key], the rope key x W_kr (64
  dims) shared by all heads; v = c W_uv; causal softmax of q.k scaled by
  (128 + 64)^-1/2 * mscale(40, 0.707)^2, computed in query blocks;
* YaRN rope: inverse frequencies blended between theta^(-2i/d) and that
  over ``factor`` by the linear ramp over YaRN's correction range, cos and
  sin scaled by mscale / mscale_all_dim;
* layer 0 a dense SwiGLU (10944); the others an expert layer: the router's
  softmax over all routed experts, greedy top-6, gates not renormalised,
  each held expert's SwiGLU (1408) applied to every token and multiplied
  by its gate where it is among the token's top 6, else by 0 (no sort, no
  buffer); plus the shared experts (one SwiGLU of 2816) on every token;
* DeepSeek-V2's per-sequence balance loss alpha * sum_i f_i P_i, averaged
  over the client's sequences, added to the mean next-token cross-entropy.

Departures, each noted:

* rope on split halves (rotate-half) where DeepSeek-V2 interleaves pairs:
  a fixed permutation of the rope columns of ``w_q`` and ``w_kr``, so with
  random weights the same model;
* the per-client clip is over the norm of the parameters held here, where
  the deployment would sum over the 8 chips' shares;
* the vocabulary is the chip's slice: ids, logits and loss over 12,800;
* only the held experts (``n_routed_experts`` of ``router_experts``, from
  ``expert_offset``) contribute: the others' part lies on other chips;
* the balance-loss coefficient (``aux_loss_alpha``) is assumed;
* ``matmul="fp8"`` is the control: every product of the forward pass
  takes float8 (e4m3) inputs, each scaled per tensor (smollm's).
"""
from __future__ import annotations

import functools
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np


def _load_base():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "smollm-135m.reference.py")
    spec = importlib.util.spec_from_file_location("chipbench_smollm_ref",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_base = _load_base()
draw_batches = _base.draw_batches
laplace = _base.laplace
ring_matrix = _base.ring_matrix
_mm = _base._mm
_rms = _base._rms
QUERY_BLOCK = 512


def _sizes(cfg: dict) -> dict:
    return {
        "d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
        "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"], "r": cfg["kv_lora_rank"],
        "ff": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
        "fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        "E": cfg["router_experts"], "H": cfg["n_routed_experts"],
        "K": cfg["num_experts_per_tok"],
        "n_dense": cfg["first_k_dense_replace"],
        "n_moe": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
        "V": -(-cfg["vocab_size"] // 128) * 128,
    }


def param_shapes(cfg: dict) -> dict:
    """One server's parameter tree, as (shape, init std) per leaf; a std
    of None means ones (norm scales)."""
    z = _sizes(cfg)
    d, h = z["d"], z["h"]

    def blocks(n, ffn):
        def w(fan_in, *shape):
            return ((n,) + shape, fan_in ** -0.5)
        return {
            "attn": {"w_q": w(d, d, h * (z["dn"] + z["dr"])),
                     "w_dkv": w(d, d, z["r"]), "w_kr": w(d, d, z["dr"]),
                     "w_uk": w(z["r"], z["r"], h * z["dn"]),
                     "w_uv": w(z["r"], z["r"], h * z["dv"]),
                     "w_o": w(h * z["dv"], h * z["dv"], d),
                     "kv_norm": ((n, z["r"]), None)},
            "ln1": {"scale": ((n, d), None)},
            "ln2": {"scale": ((n, d), None)},
            **ffn(w),
        }

    def swiglu(w, f, *lead):
        return {"w_gate": w(d, *lead, d, f), "w_up": w(d, *lead, d, f),
                "w_down": w(f, *lead, f, d)}

    return {
        "dense_blocks": blocks(z["n_dense"],
                               lambda w: {"mlp": swiglu(w, z["ff"])}),
        "blocks": blocks(z["n_moe"], lambda w: {"moe": {
            "router": w(d, d, z["E"]), **swiglu(w, z["fe"], z["H"]),
            "shared": swiglu(w, z["fs"])}}),
        "embed": {"table": ((z["V"], d), 0.02)},
        "final_norm": {"scale": ((d,), None)},
        "lm_head": {"w": ((d, z["V"]), d ** -0.5)},
    }


def init_params(key, cfg: dict, P: int):
    """P identical server copies in bfloat16, from one key (jit this)."""
    spec = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (shape, std) in zip(keys, leaves):
        x = (jnp.ones(shape, jnp.float32) if std is None
             else std * jax.random.normal(k, shape, jnp.float32))
        out.append(jnp.broadcast_to(x.astype(jnp.bfloat16)[None],
                                    (P,) + shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def _yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, rs: dict) -> np.ndarray:
    """YaRN's inverse frequencies [dim/2] (float32)."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = extra / rs["factor"]
    L0 = rs["original_max_position_embeddings"]

    def bound(beta):
        return (dim * math.log(L0 / (beta * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(bound(rs["beta_fast"])), 0)
    high = min(math.ceil(bound(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def _rope(x, cfg: dict):
    """x: [B, S, H, dr]; YaRN rope on the two halves of each head."""
    rs = cfg["rope_scaling"]
    S, dr = x.shape[1], x.shape[-1]
    freqs = jnp.asarray(yarn_inv_freq(dr, float(cfg["rope_theta"]), rs))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    m = (_yarn_mscale(rs["factor"], rs["mscale"])
         / _yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    cos = (m * jnp.cos(ang))[None, :, None]
    sin = (m * jnp.sin(ang))[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    m = _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _attention(a, p, cfg: dict, mm):
    """MLA of one layer, unabsorbed. a: [B, S, d] -> [B, S, d]."""
    z = _sizes(cfg)
    B, S, _ = a.shape
    h, dn, dr, dv = z["h"], z["dn"], z["dr"], z["dv"]
    q = mm(a, p["w_q"]).reshape(B, S, h, dn + dr)
    qn, qr = q[..., :dn], _rope(q[..., dn:], cfg)
    c = _rms(mm(a, p["w_dkv"]), p["kv_norm"], cfg["rms_norm_eps"])
    kr = _rope(mm(a, p["w_kr"])[:, :, None, :], cfg)           # [B,S,1,dr]
    kn = mm(c, p["w_uk"]).reshape(B, S, h, dn)
    v = mm(c, p["w_uv"]).reshape(B, S, h, dv)
    qn, qr = qn.transpose(0, 2, 1, 3), qr.transpose(0, 2, 1, 3)  # [B,h,S,.]
    kn_t = kn.transpose(0, 2, 3, 1)                              # [B,h,dn,S]
    kr_t = kr.transpose(0, 2, 3, 1)                              # [B,1,dr,S]
    v = v.transpose(0, 2, 1, 3)                                  # [B,h,S,dv]
    scale = softmax_scale(cfg)
    nb = max(S // QUERY_BLOCK, 1)
    bq = S // nb

    @jax.checkpoint
    def block(i):
        sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                               start_index=i * bq, slice_size=bq, axis=2)
        s = (mm(sl(qn), kn_t) + mm(sl(qr), kr_t)) * scale       # [B,h,bq,S]
        causal = (jnp.arange(S)[None, :]
                  <= i * bq + jnp.arange(bq)[:, None])
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm(w, v)                                          # [B,h,bq,dv]

    o = jax.lax.map(block, jnp.arange(nb))                  # [nb,B,h,bq,dv]
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, S, h * dv)
    return mm(o, p["w_o"])


def _swiglu(a, p, mm):
    return mm(jax.nn.silu(mm(a, p["w_gate"])) * mm(a, p["w_up"]), p["w_down"])


def _experts(a, p, cfg: dict, mm):
    """The held routed experts plus the shared ones, densely, and the
    sequence balance loss. a: [B, S, d]."""
    z = _sizes(cfg)
    E, H, K = z["E"], z["H"], z["K"]
    B, S, d = a.shape
    probs = jax.nn.softmax(mm(a, p["router"]), axis=-1)          # [B,S,E]
    top_p, top_i = jax.lax.top_k(probs, K)                       # [B,S,K]
    held = cfg["expert_offset"] + jnp.arange(H)
    gate = jnp.sum(jnp.where(top_i[..., None] == held, top_p[..., None],
                             0.0), axis=-2)                      # [B,S,H]
    out = _swiglu(a, p["shared"], mm)
    for e in range(H):
        pe = {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}
        out = out + gate[..., e:e + 1] * _swiglu(a, pe, mm)
    chosen = jnp.sum(jax.nn.one_hot(top_i, E), axis=(1, 2))       # [B,E]
    f = chosen * E / (K * S)
    aux = cfg["aux_loss_alpha"] * jnp.mean(
        jnp.sum(f * probs.mean(axis=1), axis=-1))
    return out, aux


def loss(params, tokens, labels, cfg: dict, matmul: str = "f32"):
    """Mean next-token cross-entropy plus the balance loss, of one
    server's float32 params."""
    eps = cfg["rms_norm_eps"]
    mm = functools.partial(_mm, matmul=matmul)
    x = params["embed"]["table"][tokens]

    @jax.checkpoint
    def dense(x, bp):
        x = x + _attention(_rms(x, bp["ln1"]["scale"], eps), bp["attn"], cfg,
                           mm)
        return x + _swiglu(_rms(x, bp["ln2"]["scale"], eps), bp["mlp"],
                           mm), None

    @jax.checkpoint
    def moe(x, bp):
        x = x + _attention(_rms(x, bp["ln1"]["scale"], eps), bp["attn"], cfg,
                           mm)
        out, aux = _experts(_rms(x, bp["ln2"]["scale"], eps), bp["moe"],
                            cfg, mm)
        return x + out, aux

    x, _ = jax.lax.scan(dense, x, params["dense_blocks"])
    x, aux = jax.lax.scan(moe, x, params["blocks"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    logits = mm(x, params["lm_head"]["w"])
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold) + jnp.sum(aux)


class Reference(_base.Reference):
    """smollm-135m's GFL round over this model's loss."""

    def __init__(self, cfg: dict, gfl: dict, P: int, devices,
                 combine: str = "dense", matmul: str = "f32"):
        super().__init__(cfg, gfl, P, devices, combine=combine,
                         matmul=matmul)

        def client(w, tokens, labels):
            w32 = jax.tree.map(lambda x: x.astype(jnp.float32), w)
            with jax.default_matmul_precision("highest"):
                return jax.value_and_grad(loss)(w32, tokens, labels, cfg,
                                                matmul)

        self._client = jax.jit(client)
