"""The fused causal flash-attention path of ``models/attention.py``.

Causal GQA self-attention takes the Pallas TPU kernel
(``jax.experimental.pallas.ops.tpu.flash_attention``) only on a TPU, for
causal self-attention without a shorter sliding window, with S a multiple
of ``FLASH_BLOCK`` and operands on one device; everywhere else
``_chunked_causal_attention`` runs as before, bit for bit.  On the CPU the
kernel runs under ``force_tpu_interpret_mode`` with ``_on_tpu`` patched,
which is how these tests steer the dispatch.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import GFLConfig
from repro.configs.registry import get_config
from repro.data import TokenStream, federated_token_batches
from repro.launch import steps as S
from repro.launch.mesh import make_device_mesh
from repro.models import Model
from repro.models import attention as attn
from repro.telemetry import session

BLOCK = 128


def _bf16_close(got, want):
    """Equal to bfloat16 rounding: within two bf16 ulps of the largest
    magnitude (the kernel rounds the softmax weights to bfloat16 before
    the product with V, as the TPU's default-precision einsum does)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2.0 ** -6 * np.abs(want).max())


@pytest.fixture
def on_tpu(monkeypatch):
    """The dispatch sees a TPU and takes 128-blocks (small S on the CPU)."""
    monkeypatch.setattr(attn, "_on_tpu", lambda: True)
    monkeypatch.setattr(attn, "FLASH_BLOCK", BLOCK)


@pytest.fixture(scope="module")
def gqa_parity():
    """Kernel (interpret mode) and reference outputs and q/k/v gradients at
    GQA shapes: B=1, S=256, KV=3, G=3, Dh=64, block 128."""
    B, S, KV, G, Dh = 1, 256, 3, 3, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, S, KV, G, Dh)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, Dh)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, Dh)).astype(jnp.bfloat16)
    ct = jax.random.normal(ks[3], q.shape).astype(jnp.bfloat16)

    def ref(q, k, v):
        return attn._chunked_causal_attention(q, k, v, window=0, chunk=1024)

    def kern(q, k, v):
        return attn._flash_causal_attention(q, k, v, block=BLOCK)

    def both(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) * ct)
        return (fn(q, k, v), *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))

    with pltpu.force_tpu_interpret_mode():
        got = both(kern)
    return dict(zip(("forward", "grad_q", "grad_k", "grad_v"),
                    zip(got, both(ref))))


def _model_loss_parity():
    """value_and_grad of Model.loss through the layer scan and
    jax.checkpoint: the kernel path against the chunked path, in float32
    (the reduced config's dtype), where the two agree to float32 rounding."""
    cfg = get_config("smollm-135m").reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, BLOCK * 2 + 1), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def run(one_device):
        return jax.value_and_grad(
            lambda p: model.loss(p, batch, one_device=one_device)[0])(params)

    # the HLO interpreter (interpret=True): the TPU interpreter's ordered
    # callbacks cannot be partially evaluated under jax.checkpoint
    with session("memory") as sess, pltpu.force_tpu_interpret_mode(True):
        (loss, grads) = run(True)
    recs = [r for r in sess.memory_records("kernel")
            if r["op"] == "flash_attention"]
    assert recs and all(r["engaged"] == 1 for r in recs), recs
    (loss0, grads0) = run(False)
    np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-5)
    for path, g, g0 in zip(jax.tree_util.tree_leaves_with_path(grads),
                           jax.tree.leaves(grads), jax.tree.leaves(grads0)):
        g, g0 = np.asarray(g, np.float32), np.asarray(g0, np.float32)
        gap = np.linalg.norm(g - g0) / max(np.linalg.norm(g0), 1e-12)
        assert gap < 1e-4, (jax.tree_util.keystr(path[0]), gap)


@pytest.mark.parametrize("case", ["forward", "grad_q", "grad_k", "grad_v",
                                  "model_loss"])
def test_flash_kernel_matches_chunked_attention(case, request, on_tpu):
    if case == "model_loss":
        _model_loss_parity()
        return
    got, want = request.getfixturevalue("gqa_parity")[case]
    assert got.dtype == want.dtype == jnp.bfloat16
    _bf16_close(got, want)


# --- dispatch ------------------------------------------------------------


def _today(params, x, positions, cfg, *, chunk=1024, use_rope=True,
           causal=True, kv_src=None):
    """gqa_forward as it was before the fused path: the fallback's oracle."""
    h, kv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    B, Sq, _ = x.shape
    src = x if kv_src is None else kv_src
    q = attn._split_heads(x @ params["w_q"], h, dh)
    k = attn._split_heads(src @ params["w_k"], kv, dh)
    v = attn._split_heads(src @ params["w_v"], kv, dh)
    if use_rope:
        q = attn.apply_rope(q, positions, cfg.rope_theta)
        k = attn.apply_rope(k, jnp.broadcast_to(jnp.arange(src.shape[1]),
                                                src.shape[:2]),
                            cfg.rope_theta)
    q = q.reshape(B, Sq, kv, h // kv, dh)
    if causal and kv_src is None:
        out = attn._chunked_causal_attention(
            q, k, v, window=cfg.sliding_window, chunk=chunk)
    else:
        s = attn._gqa_scores(q, k) / jnp.sqrt(dh)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", w,
                         v.astype(jnp.float32)).astype(x.dtype)
    return out.reshape(B, Sq, h * dh) @ params["w_o"]


# case: (arch, S, kwargs, patch the backend to a TPU, expected reason)
FALLBACKS = {
    "sliding_window": ("phi3-mini-3.8b", 256,
                       {"one_device": True, "chunk": 64}, True,
                       "sliding_window"),
    "cross_attention": ("whisper-tiny", 256,
                        {"use_rope": False, "causal": False,
                         "one_device": True, "cross": True}, True,
                        "cross_attention"),
    "not_causal": ("whisper-tiny", 256,
                   {"use_rope": False, "causal": False, "one_device": True},
                   True, "not_causal"),
    "seq_not_block_multiple": ("smollm-135m", 200, {"one_device": True},
                               True, "seq_not_block_multiple"),
    "cpu_without_interpret": ("smollm-135m", 256, {"one_device": True},
                              False, "backend_cpu"),
    "multi_device": ("smollm-135m", 256, {}, True, "not_one_device"),
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_fallback_is_todays_path_bit_for_bit(case, monkeypatch):
    arch, S_, kw, tpu, reason = FALLBACKS[case]
    monkeypatch.setattr(attn, "FLASH_BLOCK", BLOCK)
    if tpu:
        monkeypatch.setattr(attn, "_on_tpu", lambda: True)
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(3)
    p = attn.gqa_init(key, cfg, jnp.bfloat16)
    x = jax.random.normal(jax.random.fold_in(key, 1),
                          (2, S_, cfg.d_model)).astype(jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(S_), (2, S_))
    kw = dict(kw)
    if kw.pop("cross", False):
        kw["kv_src"] = jax.random.normal(
            jax.random.fold_in(key, 2), (2, 96, cfg.d_model)
        ).astype(jnp.bfloat16)
    with session("memory") as sess:
        out = jax.jit(lambda p, x: attn.gqa_forward(p, x, pos, cfg, **kw))(
            p, x)
        jaxpr = str(jax.make_jaxpr(
            lambda p, x: attn.gqa_forward(p, x, pos, cfg, **kw))(p, x))
    kw.pop("one_device", None)
    want = jax.jit(lambda p, x: _today(p, x, pos, cfg, **kw))(p, x)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want, np.float32))
    assert "pallas_call" not in jaxpr
    recs = [r for r in sess.memory_records("kernel")
            if r["op"] == "flash_attention"]
    assert recs and all(r["engaged"] == 0 and r["reason"] == reason
                        for r in recs), recs
    assert recs[0]["seq_len"] == S_
    assert (recs[0]["heads"], recs[0]["head_dim"]) == (
        cfg.num_heads, cfg.resolved_head_dim)


def _step_jaxpr(mesh, P):
    """The train step's jaxpr, traced under a memory telemetry session,
    with the kernel records it emitted."""
    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              num_layers=2, vocab_size=512)
    model = Model(cfg)
    gfl = GFLConfig(topology="ring", privacy="hybrid", sigma_g=0.01, mu=0.1,
                    grad_bound=10.0, combine_impl="dense")
    params = jax.eval_shape(
        lambda k: jax.tree.map(lambda x: jnp.broadcast_to(x, (P,) + x.shape),
                               model.init(k)), jax.random.PRNGKey(0))
    state = S.TrainState(params, jax.ShapeDtypeStruct((), jnp.int32),
                         jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    batch = federated_token_batches(TokenStream(vocab=512, seed=0), seed=0,
                                    step=0, P=P, L=2, per_client=1,
                                    seq_len=BLOCK * 2)
    with session("memory") as sess:
        jaxpr = str(jax.make_jaxpr(S.make_train_step(model, gfl, mesh))(
            state, batch))
    return jaxpr, [r for r in sess.memory_records("kernel")
                   if r["op"] == "flash_attention"]


@pytest.mark.parametrize("devices", [1, 2])
def test_step_engages_the_kernel_only_on_a_one_device_mesh(devices, on_tpu):
    """The step builder tells the model whether its mesh is one device; a
    two-server mesh (described abstractly: the tests see one CPU device)
    keeps the chunked path."""
    if devices == 1:
        mesh = make_device_mesh(jax.devices()[:1])
    else:
        mesh = jax.sharding.AbstractMesh((2, 1), ("data", "model"))
    jaxpr, recs = _step_jaxpr(mesh, devices)
    assert recs
    if devices == 1:
        assert "pallas_call" in jaxpr
        assert all(r["engaged"] == 1 and r["reason"] == "" for r in recs)
    else:
        assert "pallas_call" not in jaxpr
        assert all(r["engaged"] == 0 and r["reason"] == "not_one_device"
                   for r in recs)
