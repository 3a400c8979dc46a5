"""Per-arch smoke tests (reduced configs) + decode/forward consistency.

The decode-consistency test is the strongest cache-path check we have: the
logits produced by prefill(prompt) followed by decode_step(tok) must match
the full-sequence forward at the same positions.  For mamba2 it also
validates the chunked SSD algorithm against the step recurrence.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, all_configs, get_config
from repro.models import Model

ARCHS = [a for a in ARCH_IDS if a != "gfl-logreg"]


def _batch_for(cfg, B, S, key):
    k1, k2, k3 = jax.random.split(key, 3)
    batch = {
        "tokens": jax.random.randint(k1, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(k2, (B, S), 0, cfg.vocab_size),
    }
    if cfg.family == "vlm":
        batch["image_embeds"] = 0.1 * jax.random.normal(
            k3, (B, cfg.num_image_tokens, cfg.d_model))
    if cfg.family == "audio":
        batch["frames"] = 0.1 * jax.random.normal(
            k3, (B, cfg.encoder_seq_len, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_train_step(arch):
    """One forward + one SGD train step on the reduced config: shapes +
    finiteness (deliverable f)."""
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    B, S = 2, 32
    batch = _batch_for(cfg, B, S, key)

    logits = jax.jit(model.forward)(params, batch)
    S_out = S + (cfg.num_image_tokens if cfg.family == "vlm" else 0)
    assert logits.shape == (B, S_out, model.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()

    (loss, aux), grads = jax.jit(
        jax.value_and_grad(model.loss, has_aux=True))(params, batch)
    assert np.isfinite(float(loss))
    new_params = jax.tree.map(lambda p, g: p - 0.1 * g.astype(p.dtype),
                              params, grads)
    loss2, _ = jax.jit(model.loss)(new_params, batch)
    assert np.isfinite(float(loss2))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_forward(arch):
    """prefill + N decode steps == full forward (teacher forcing).

    MoE archs run with drop-free capacity (cf = E): capacity-based routing
    legitimately drops different tokens for different batch shapes, which is
    a semantic property of the router, not a cache bug."""
    import dataclasses
    cfg = get_config(arch).reduced()
    if cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
    model = Model(cfg)
    key = jax.random.PRNGKey(1)
    params = model.init(key)
    B, S, n_dec = 2, 24, 4
    batch = _batch_for(cfg, B, S + n_dec, key)
    full_logits = jax.jit(model.forward)(
        params, batch)                         # [B, S_total(+img), V]
    off = cfg.num_image_tokens if cfg.family == "vlm" else 0

    prompt = dict(batch)
    prompt["tokens"] = batch["tokens"][:, :S]
    prompt["labels"] = batch["labels"][:, :S]
    last_logits, cache = jax.jit(model.prefill)(params, prompt)

    np.testing.assert_allclose(
        np.asarray(last_logits, np.float32),
        np.asarray(full_logits[:, off + S - 1], np.float32),
        atol=2e-2, rtol=2e-2)

    decode = jax.jit(model.decode_step)
    for t in range(n_dec):
        tok = batch["tokens"][:, S + t]
        logits, cache = decode(params, tok, cache)
        np.testing.assert_allclose(
            np.asarray(logits, np.float32),
            np.asarray(full_logits[:, off + S + t], np.float32),
            atol=2e-2, rtol=2e-2,
            err_msg=f"{arch} decode step {t}")


def test_sliding_window_matches_windowed_reference():
    """SWA chunked attention == naive masked attention."""
    from repro.models import attention as attn
    cfg = get_config("phi3-mini-3.8b").reduced()
    assert cfg.sliding_window > 0
    model = Model(cfg)
    key = jax.random.PRNGKey(2)
    p = attn.gqa_init(key, cfg, jnp.float32)
    B, S = 2, 130
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    out_chunked = attn.gqa_forward(p, x, pos, cfg, chunk=32)
    # naive reference: full masked attention
    import dataclasses
    h, kv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = attn._split_heads(x @ p["w_q"], h, dh)
    k = attn._split_heads(x @ p["w_k"], kv, dh)
    v = attn._split_heads(x @ p["w_v"], kv, dh)
    q = attn.apply_rope(q, pos, cfg.rope_theta)
    k = attn.apply_rope(k, pos, cfg.rope_theta)
    q = q.reshape(B, S, kv, h // kv, dh)
    s = attn._gqa_scores(q, k) / np.sqrt(dh)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = (j <= i) & (j > i - cfg.sliding_window)
    s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, -1)
    exp = jnp.einsum("bkgqs,bskd->bqkgd", w, v).reshape(B, S, h * dh) \
        @ p["w_o"]
    np.testing.assert_allclose(np.asarray(out_chunked), np.asarray(exp),
                               atol=2e-4, rtol=2e-3)


def test_mamba2_chunked_equals_sequential():
    """Chunked SSD == naive per-step recurrence on random inputs."""
    from repro.models import ssm as ssm_lib
    cfg = get_config("zamba2-1.2b").reduced()
    model = Model(cfg)
    key = jax.random.PRNGKey(4)
    p = ssm_lib.mamba2_init(key, cfg, jnp.float32)
    B, S = 2, 37
    x = 0.5 * jax.random.normal(jax.random.fold_in(key, 1),
                                (B, S, cfg.d_model))
    out_chunked, st = ssm_lib.mamba2_forward(p, x, cfg)
    # sequential reference via decode steps
    d_inner, H, N, G = ssm_lib.ssm_dims(cfg)
    h = jnp.zeros((B, H, cfg.ssm.headdim, N), jnp.float32)
    conv = jnp.zeros((B, cfg.ssm.conv_dim - 1, d_inner + 2 * G * N))
    outs = []
    for t in range(S):
        o, h, conv = ssm_lib.mamba2_decode(p, x[:, t:t + 1], h, conv, cfg)
        outs.append(o)
    out_seq = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(out_chunked), np.asarray(out_seq),
                               atol=2e-3, rtol=2e-2)
    np.testing.assert_allclose(np.asarray(st["h"]), np.asarray(h),
                               atol=2e-3, rtol=2e-2)


def test_moe_row_dispatch_matches_global():
    """Row-local dispatch (§Perf HC-2) == global dispatch when capacity is
    drop-free (semantic equivalence of the locality optimization)."""
    import dataclasses
    from repro.models import moe as moe_lib
    cfg = get_config("deepseek-v2-lite-16b").reduced()
    E = cfg.moe.num_experts
    base = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=float(E), dispatch="global"))
    row = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=float(E), dispatch="row"))
    p = moe_lib.moe_init(jax.random.PRNGKey(0), base, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 32, cfg.d_model))
    o1, *_ = moe_lib.moe_forward(p, x, base)
    o2, *_ = moe_lib.moe_forward(p, x, row)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_moe_capacity_drops_are_bounded():
    """Router load-balance: with uniform logits, token drop rate stays low."""
    from repro.models import moe as moe_lib
    cfg = get_config("mixtral-8x7b").reduced()
    model = Model(cfg)
    p = moe_lib.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
    out, aux, _ = moe_lib.moe_forward(p, x, cfg)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    assert float(aux) >= 0.0


def test_full_configs_match_assignment():
    """Exact assigned hyperparameters (deliverable f provenance check)."""
    spec = {
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "rwkv6-3b": (32, 2560, 40, 40, 8960, 65536),
        "yi-6b": (32, 4096, 32, 4, 11008, 64000),
        "llava-next-mistral-7b": (32, 4096, 32, 8, 14336, 32000),
        "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
        "deepseek-v2-lite-16b": (27, 2048, 16, 16, 1408, 102400),
        "smollm-135m": (30, 576, 9, 3, 1536, 49152),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "minicpm3-4b": (62, 2560, 40, 40, 6400, 73448),
        "phi3-mini-3.8b": (32, 3072, 32, 32, 8192, 32064),
    }
    for arch, (L, d, h, kv, ff, v) in spec.items():
        cfg = get_config(arch)
        assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                cfg.d_ff, cfg.vocab_size) == (L, d, h, kv, ff, v), arch
        assert cfg.source, f"{arch} missing source citation"
    assert get_config("zamba2-1.2b").ssm.state_dim == 64
    assert get_config("deepseek-v2-lite-16b").mla.kv_lora_rank == 512
    assert get_config("deepseek-v2-lite-16b").moe.num_experts == 64
    assert get_config("deepseek-v2-lite-16b").moe.top_k == 6
    assert get_config("mixtral-8x7b").moe.num_experts == 8
    assert get_config("mixtral-8x7b").moe.top_k == 2
