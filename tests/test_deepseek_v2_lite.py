"""DeepSeek-V2-Lite's mechanisms at a small size on the CPU.

The program (``Model.loss``: MLA with YaRN rope, a dense layer, dropless
expert layers over a share of the routed experts, the sequence balance
loss) against the benchmark's plain float32 reference
(``benchmarks/chip/configs/deepseek-v2-lite.reference.py``) on seeded
random weights; the expert shares adding up to the uncut layer; the
dropless dispatch under a router rigged to crowd the held experts; YaRN's
frequencies against their closed form; plain rope bit for bit; and the
fused latent attention (splash kernel, interpret mode) against the chunked
path.
"""
import dataclasses
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import MLAConfig, YarnConfig
from repro.configs.registry import get_config
from repro.models import Model
from repro.models import attention as attn
from repro.models import layers as nn
from repro.models import moe as moe_lib
from repro.telemetry import session

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmarks", "chip", "configs")

# the published config, cut to a size the CPU runs in seconds: every
# mechanism stays (YaRN, no renormalisation, sequence loss, a held share
# that is neither the first nor all of the routed experts, top-k above it)
TINY = {"num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 2,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "intermediate_size": 96,
        "moe_intermediate_size": 32, "vocab_size": 256, "router_experts": 8,
        "n_routed_experts": 2, "expert_offset": 2, "num_experts_per_tok": 3,
        "aux_loss_alpha": 0.01}


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "deepseek_reference",
        os.path.join(CONFIGS, "deepseek-v2-lite.reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _conf() -> dict:
    with open(os.path.join(CONFIGS, "deepseek-v2-lite.json")) as f:
        return {**json.load(f), **TINY}


def _program_cfg(conf: dict):
    base = get_config("deepseek-v2-lite-16b")
    return dataclasses.replace(
        base, num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_attention_heads"],
        d_ff=conf["moe_intermediate_size"], vocab_size=conf["vocab_size"],
        param_dtype="float32",
        mla=MLAConfig(kv_lora_rank=conf["kv_lora_rank"], q_lora_rank=0,
                      qk_nope_head_dim=conf["qk_nope_head_dim"],
                      qk_rope_head_dim=conf["qk_rope_head_dim"],
                      v_head_dim=conf["v_head_dim"]),
        moe=dataclasses.replace(
            base.moe, num_experts=conf["router_experts"],
            experts_held=conf["n_routed_experts"],
            expert_offset=conf["expert_offset"],
            top_k=conf["num_experts_per_tok"],
            expert_d_ff=conf["moe_intermediate_size"],
            first_dense_d_ff=conf["intermediate_size"],
            router_aux_coef=conf["aux_loss_alpha"]))


def test_program_matches_the_plain_reference(ref):
    """Loss and every gradient leaf of ``Model.loss`` equal the float32
    reference's, to float32 rounding (both run in float32 on the CPU)."""
    conf = _conf()
    cfg = _program_cfg(conf)
    assert cfg.rope_scaling is not None and not cfg.moe.norm_topk
    assert cfg.moe.balance == "sequence" and cfg.moe.dispatch == "dropless"
    model = Model(cfg)
    key = jax.random.PRNGKey(0)
    params = jax.tree.map(lambda x: x[0].astype(jnp.float32),
                          ref.init_params(key, conf, 1))
    mine = jax.eval_shape(model.init, key)
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert all(a.shape == b.shape for a, b in zip(jax.tree.leaves(mine),
                                                  jax.tree.leaves(params)))
    # norm scales off one, so that a misplaced scale shows
    params = jax.tree_util.tree_map_with_path(
        lambda p, x: x * (1.0 + 0.1 * jax.random.normal(
            jax.random.fold_in(key, len(str(p))), x.shape))
        if "norm" in str(p) or "ln" in str(p) else x, params)
    B, S = 2, 32
    tok = jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0,
                             conf["vocab_size"])
    lab = jax.random.randint(jax.random.fold_in(key, 2), (B, S), 0,
                             conf["vocab_size"])

    def program(p):
        return model.loss(p, {"tokens": tok, "labels": lab})[0]

    def reference(p):
        with jax.default_matmul_precision("highest"):
            return ref.loss(p, tok, lab, conf)

    lp, gp = jax.jit(jax.value_and_grad(program))(params)
    lr, gr = jax.jit(jax.value_and_grad(reference))(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    for path, a, b in zip(jax.tree_util.tree_leaves_with_path(gp),
                          jax.tree.leaves(gp), jax.tree.leaves(gr)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(b).max() > 0, path[0]
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-4 * np.abs(b).max(),
                                   err_msg=jax.tree_util.keystr(path[0]))
    aux = model.loss(params, {"tokens": tok, "labels": lab})[1]
    # every assignment to the held experts is counted, over both MoE layers
    assert int(aux["expert_tokens"].sum()) > 0
    assert aux["expert_tokens"].shape == (conf["n_routed_experts"],)


def _layer_cfg(E=8, H=8, offset=0, K=3):
    cfg = get_config("deepseek-v2-lite-16b").reduced()
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=E, experts_held=H, expert_offset=offset,
        top_k=K, num_shared_experts=1))


def test_expert_shares_add_up_to_the_uncut_layer():
    """Expert parallelism ties the share to the model: the routed parts of
    every share (offsets 0, h, 2h, ...) plus the shared experts counted
    once equal the layer that holds all 8 experts."""
    full = _layer_cfg()
    p = moe_lib.moe_init(jax.random.PRNGKey(0), full, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, full.d_model))
    want, _, counts_full = moe_lib.moe_forward(p, x, full)
    shared = moe_lib.swiglu(p["shared"], x)
    h = 2
    total, counts = shared, []
    for off in range(0, 8, h):
        cfg = _layer_cfg(H=h, offset=off)
        ps = {**p, **{k: p[k][off:off + h]
                      for k in ("w_gate", "w_up", "w_down")}}
        out, _, c = moe_lib.moe_forward(ps, x, cfg)
        total = total + (out - shared)
        counts.append(c)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=0, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(counts), counts_full)


def test_dropless_keeps_every_token_when_the_held_experts_are_crowded():
    """A router rigged to send every token to the same three held experts
    fills the buffer to its worst case: no assignment is dropped, and the
    output equals each expert applied densely with its gate."""
    cfg = _layer_cfg(E=8, H=4, offset=0, K=3)
    p = moe_lib.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    router = np.zeros(p["router"].shape, np.float32)
    router[0, :3] = [3.0, 2.0, 1.0]
    p["router"] = jnp.asarray(router)
    B, S = 2, 24
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    x = x.at[..., 0].set(4.0)
    out, _, counts = moe_lib.moe_forward(p, x, cfg)
    np.testing.assert_array_equal(counts, [B * S] * 3 + [0])
    xf = x.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(xf @ p["router"], -1)
    want = moe_lib.swiglu(p["shared"], xf)
    for e in range(3):
        pe = {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}
        want = want + probs[:, e:e + 1] * moe_lib.swiglu(pe, xf)
    np.testing.assert_allclose(np.asarray(out).reshape(-1, cfg.d_model),
                               np.asarray(want), rtol=0, atol=1e-5)


def test_yarn_frequencies_follow_the_closed_form():
    y = YarnConfig()
    d, theta = 64, 10_000.0
    i = np.arange(d // 2, dtype=np.float64)
    extra = theta ** (-2 * i / d)
    inter = extra / y.factor

    def dim(beta):
        return d * math.log(y.original_max_position_embeddings
                            / (2 * math.pi * beta)) / (2 * math.log(theta))
    low = max(math.floor(dim(y.beta_fast)), 0)
    high = min(math.ceil(dim(y.beta_slow)), d - 1)
    assert (low, high) == (10, 23)
    mask = 1 - np.clip((i - low) / (high - low), 0, 1)
    want = inter * (1 - mask) + extra * mask
    got = np.asarray(nn.rope_frequencies(d, theta, y))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert nn.yarn_mscale(y.factor, y.mscale) == pytest.approx(
        0.1 * 0.707 * math.log(40) + 1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_plain_rope_is_unchanged_bit_for_bit(dtype):
    """Without YaRN, ``apply_rope`` is the formula it always was."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 48, 3, 64)).astype(dtype)
    pos = jnp.broadcast_to(jnp.arange(48), (2, 48))

    def before(x, positions, theta):
        dh = x.shape[-1]
        freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32)
                                 / dh))
        angles = positions[..., None].astype(jnp.float32) * freqs
        cos = jnp.cos(angles)[..., None, :]
        sin = jnp.sin(angles)[..., None, :]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
        return out.astype(x.dtype)

    np.testing.assert_array_equal(
        np.asarray(jax.jit(nn.apply_rope, static_argnums=2)(x, pos, 1e4),
                   np.float32),
        np.asarray(jax.jit(before, static_argnums=2)(x, pos, 1e4),
                   np.float32))


def test_fused_latent_attention_matches_the_chunked_path():
    """The splash kernel (interpret mode) against the chunked float32 path
    at MLA's head shape (q.k over 128 + 64, values 128): output and the
    gradients of every operand."""
    B, S, H, dn, dr, dv = 1, 256, 2, 128, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    qn = jax.random.normal(ks[0], (B, S, H, dn))
    qr = jax.random.normal(ks[1], (B, S, H, dr))
    kn = jax.random.normal(ks[2], (B, S, H, dn))
    kr = jax.random.normal(ks[3], (B, S, dr))
    v = jax.random.normal(ks[4], (B, S, H, dv))
    ct = jax.random.normal(ks[5], (B, S, H, dv))
    scale = 0.08

    def chunked(qn, qr, kn, kr, v):
        return attn._mla_chunked_attention(qn, qr, kn, kr, v, scale=scale,
                                           chunk=64)

    def fused(qn, qr, kn, kr, v):
        q = jnp.concatenate([qn, qr], -1)
        k = jnp.concatenate([kn, jnp.broadcast_to(kr[:, :, None, :],
                                                  (B, S, H, dr))], -1)
        return attn._splash_causal_attention(q, k, v, scale=scale, block=128)

    def both(f):
        def loss(*a):
            return jnp.sum(f(*a) * ct)
        args = (qn, qr, kn, kr, v)
        return (f(*args),) + jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)

    with pltpu.force_tpu_interpret_mode():
        got = both(fused)
    for g, w in zip(got, both(chunked)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=1e-5 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("tpu", [True, False], ids=["tpu", "cpu"])
def test_latent_attention_takes_the_fused_kernel_on_one_tpu(tpu,
                                                            monkeypatch):
    """MLA's training attention passes GQA's gate: a one-device TPU takes
    the kernel, and the record names both head dims."""
    monkeypatch.setattr(attn, "SPLASH_BLOCK", 128)
    if tpu:
        monkeypatch.setattr(attn, "_on_tpu", lambda: True)
    conf = _conf()
    cfg = _program_cfg(conf)
    cfg = dataclasses.replace(cfg, mla=dataclasses.replace(
        cfg.mla, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128))
    p = attn.mla_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 128, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(128), (1, 128))
    with session("memory") as sess, pltpu.force_tpu_interpret_mode():
        jaxpr = str(jax.make_jaxpr(lambda p, x: attn.mla_forward(
            p, x, pos, cfg, one_device=True))(p, x))
    recs = [r for r in sess.memory_records("kernel")
            if r["op"] == "flash_attention"]
    assert len(recs) == 1
    r = recs[0]
    assert (r["heads"], r["head_dim"], r["head_dim_v"]) == (2, 192, 128)
    assert r["engaged"] == int(tpu)
    assert r["reason"] == ("" if tpu else "backend_cpu")
    assert ("pallas_call" in jaxpr) == tpu


def test_dropless_layer_emits_its_kernel_record():
    cfg = _layer_cfg(E=8, H=4, offset=4, K=3)
    p = moe_lib.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    with session("memory") as sess:
        jax.make_jaxpr(lambda p, x: moe_lib.moe_forward(
            p, x, cfg, one_device=True))(p, x)
        jax.make_jaxpr(lambda p, x: moe_lib.moe_forward(p, x, cfg))(p, x)
    recs = [r for r in sess.memory_records("kernel") if r["op"] == "moe_gmm"]
    assert len(recs) == 2
    for r in recs:
        assert (r["experts_total"], r["experts_held"], r["top_k"],
                r["tokens"]) == (8, 4, 3, 32)
        assert r["buffer_rows"] >= 32 * 3
    assert [(r["engaged"], r["reason"]) for r in recs] == [
        (0, "backend_cpu"), (0, "not_one_device")]


def test_model_loss_takes_both_kernels_through_the_layer_scans(monkeypatch):
    """``Model.loss`` with the fused latent attention and the compiled
    grouped matmul engaged (as on a one-device TPU; the HLO interpreter
    here) through the dense and expert layer scans and their remat: loss
    and gradients equal the chunked, interpreted path's to float32
    rounding."""
    monkeypatch.setattr(attn, "SPLASH_BLOCK", 128)
    conf = {**_conf(), "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128}
    model = Model(_program_cfg(conf))
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0,
                              conf["vocab_size"])
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def run(one_device):
        return jax.value_and_grad(
            lambda p: model.loss(p, batch, one_device=one_device)[0])(params)

    loss0, grads0 = run(False)
    monkeypatch.setattr(attn, "_on_tpu", lambda: True)
    monkeypatch.setattr(moe_lib, "_on_tpu", lambda: True)
    with session("memory") as sess, pltpu.force_tpu_interpret_mode(True):
        loss, grads = run(True)
    recs = sess.memory_records("kernel")
    assert {r["op"] for r in recs} == {"flash_attention", "moe_gmm"}
    assert all(r["engaged"] == 1 for r in recs), recs
    np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-5)
    for path, g, g0 in zip(jax.tree_util.tree_leaves_with_path(grads),
                           jax.tree.leaves(grads), jax.tree.leaves(grads0)):
        g, g0 = np.asarray(g), np.asarray(g0)
        gap = np.linalg.norm(g - g0) / max(np.linalg.norm(g0), 1e-12)
        assert gap < 1e-4, (jax.tree_util.keystr(path[0]), gap)
