"""Operations and bytes that the algorithm needs, from shapes alone.

These counts belong to the benchmark, not to any implementation: a later
change to how a kernel or a step computes its result leaves them as they
are, so a share of the roofline stays comparable across changes.
"""
from __future__ import annotations


def lm_matmul_params(cfg: dict) -> int:
    """Parameters that enter a matmul once per token (tied unembedding
    included, the embedding lookup excluded)."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    ff = cfg["intermediate_size"]
    per_layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def lm_param_count(cfg: dict) -> int:
    """All parameters: matmul weights, norms, and the embedding table when
    it is not tied to the unembedding."""
    d = cfg["hidden_size"]
    n = lm_matmul_params(cfg) + (2 * cfg["num_hidden_layers"] + 1) * d
    if not cfg["tie_word_embeddings"]:
        n += cfg["vocab_size"] * d
    return n


def lm_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward FLOPs per token of a causal decoder: 6 per
    matmul parameter, plus the score and value products over the causal
    prefix (on average (S+1)/2 keys), three times forward's.  Recomputation
    is not counted."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or d // h
    attn_fwd = 2 * 2 * h * dh * (seq_len + 1) / 2 * cfg["num_hidden_layers"]
    return 6.0 * lm_matmul_params(cfg) + 3.0 * attn_fwd
