"""Operations and bytes of DeepSeek-V2-Lite's layers (one chip's share),
from shapes and from the step's own count of routed tokens.

Like ``counts.py`` these belong to the benchmark: a later change to how the
program computes a layer leaves them as they are.  Recomputation is never
counted.  A configuration here is the JSON of ``configs/`` (the published
config's keys, with ``n_routed_experts`` the experts held here and
``router_experts`` the router's outputs).
"""
from __future__ import annotations

BF16 = 2


def _attn_matmul_params(cfg: dict) -> int:
    """MLA's projection weights of one layer (no q compression)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    return (d * h * (dn + dr) + d * r + d * dr + r * h * dn + r * h * dv
            + h * dv * d)


def _expert_params(cfg: dict) -> int:
    """One routed expert's SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _moe_dense_params(cfg: dict) -> int:
    """What every token of an expert layer goes through: the router and
    the shared experts."""
    d = cfg["hidden_size"]
    return (d * cfg["router_experts"]
            + 3 * d * cfg["moe_intermediate_size"] * cfg["n_shared_experts"])


def param_count(cfg: dict) -> int:
    """All parameters held here: weights, norm scales, embedding and head
    (the vocabulary padded to 128, as stored)."""
    d = cfg["hidden_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    norms = 2 * d + cfg["kv_lora_rank"]
    per_dense = (_attn_matmul_params(cfg) + 3 * d * cfg["intermediate_size"]
                 + norms)
    per_moe = (_attn_matmul_params(cfg) + _moe_dense_params(cfg)
               + cfg["n_routed_experts"] * _expert_params(cfg) + norms)
    v = -(-cfg["vocab_size"] // 128) * 128
    return n_dense * per_dense + n_moe * per_moe + 2 * v * d + d


def held_experts_per_token(cfg: dict) -> float:
    """Expected held experts a token is routed to: top-k over all routed
    experts, of which this chip holds its share."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_experts"])


def mla_core_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward FLOPs per token of one layer's causal attention core: q.k
    over qk_nope + qk_rope dims and p.v over v dims, on average (S+1)/2
    keys."""
    h = cfg["num_attention_heads"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2.0 * h * (dqk + cfg["v_head_dim"]) * (seq_len + 1) / 2


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward FLOPs per token: 6 per matmul parameter a token
    goes through (the held experts at their expected load), plus the
    attention core, three times forward's."""
    d = cfg["hidden_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    per_token = (cfg["num_hidden_layers"] * _attn_matmul_params(cfg)
                 + n_dense * 3 * d * cfg["intermediate_size"]
                 + n_moe * (_moe_dense_params(cfg)
                            + held_experts_per_token(cfg)
                            * _expert_params(cfg))
                 + d * cfg["vocab_size"])
    core = cfg["num_hidden_layers"] * mla_core_flops_per_token(cfg, seq_len)
    return 6.0 * per_token + 3.0 * core


def gmm_flops(cfg: dict, assignments: int) -> float:
    """The held experts' grouped matmuls, forward and backward, for
    ``assignments`` token-to-held-expert assignments (summed over layers)."""
    return 6.0 * _expert_params(cfg) * assignments


def gmm_bytes(cfg: dict, assignments: int, calls: int) -> float:
    """HBM bytes the grouped matmuls need at least: each of ``calls``
    expert-layer calls reads the held experts' weights twice (forward and
    the input gradient) and writes their gradient once; each assignment's
    rows are read and written by the three matmuls (x twice, gate and up
    out, h in, y out: 3 d + 3 f), backward twice as much as forward."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    w = cfg["n_routed_experts"] * _expert_params(cfg)
    return BF16 * (3.0 * w * calls + 3.0 * (3 * d + 3 * f) * assignments)


def mla_core_flops(cfg: dict, seq_len: int, rows: int) -> float:
    """The causal attention core of every layer, forward and backward, for
    ``rows`` sequences of ``seq_len``."""
    return (3.0 * cfg["num_hidden_layers"] * rows * seq_len
            * mla_core_flops_per_token(cfg, seq_len))


def mla_core_bytes(cfg: dict, seq_len: int, rows: int) -> float:
    """HBM bytes of the attention core at least: forward reads q, k, v and
    writes o; backward reads q, k, v, o and do and writes dq, dk, dv."""
    h = cfg["num_attention_heads"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    per_token = h * ((2 * dqk + 2 * dv) + (2 * dqk + 3 * dv)
                     + (2 * dqk + dv))
    return BF16 * cfg["num_hidden_layers"] * rows * seq_len * per_token
