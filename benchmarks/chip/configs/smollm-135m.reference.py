"""Plain reference for smollm-135m under GFL, in float32 jax.numpy.

It imports nothing of the program.  It draws the cell's token rows itself
(:func:`draw_batches`) and follows the published SmolLM-135M
block (Llama-style: RMSNorm, rotary embeddings on split halves, grouped
query attention with causal softmax, SwiGLU, tied unembedding) and the GFL
round of Rizk & Sayed 2021 eqs. 6-8 as the trainer runs it:

* each client's gradient of the mean next-token cross-entropy, clipped to
  global norm ``grad_bound``; the server takes the mean over its clients;
* psi = w - mu * mean, stored in the parameters' dtype (bfloat16);
* graph-homomorphic Laplace noise g (variance sigma^2, one draw per leaf
  from the round key, in bfloat16) travels with psi, and each server
  subtracts its own: w_p = sum_m a_mp (psi_m + g_m) - g_p, mixed in
  float32 at the highest matmul precision and stored in bfloat16.  Where
  servers exchange psi + g between chips (the sparse and rotate combines)
  it crosses the wire in bfloat16.

Departures from a textbook float32 model, each made because the
configuration states it: parameters are stored in bfloat16 between rounds,
and the noise is drawn exactly as the round key dictates (threefry,
partitionable; one key per leaf in the pytree's flatten order; inverse-CDF
Laplace on uniforms in (-1/2, 1/2)), so that a run and its reference see
the same noise.

``matmul="fp8"`` is the control: every product of the forward pass takes
float8 (e4m3) inputs, each scaled per tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _sizes(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    return d, h, kv, dh, cfg["intermediate_size"], cfg["num_hidden_layers"]


def param_shapes(cfg: dict) -> dict:
    """One server's parameter tree, as (shape, init std) per leaf; a std
    of None means ones (norm scales)."""
    d, h, kv, dh, ff, n = _sizes(cfg)
    v = -(-cfg["vocab_size"] // 128) * 128

    def w(fan_in, *shape):
        return ((n,) + shape, fan_in ** -0.5)

    return {
        "blocks": {
            "attn": {"w_q": w(d, d, h * dh), "w_k": w(d, d, kv * dh),
                     "w_v": w(d, d, kv * dh), "w_o": w(h * dh, h * dh, d)},
            "ln1": {"scale": ((n, d), None)},
            "ln2": {"scale": ((n, d), None)},
            "mlp": {"w_gate": w(d, d, ff), "w_up": w(d, d, ff),
                    "w_down": w(ff, ff, d)},
        },
        "embed": {"table": ((v, d), 0.02)},
        "final_norm": {"scale": ((d,), None)},
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


def _mm(a, b, matmul: str):
    if matmul == "fp8":
        def q(x):   # rounded forward, straight-through backward
            s = jax.lax.stop_gradient(
                jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX)
            r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
            return x + jax.lax.stop_gradient(r - x)
        return jnp.matmul(q(a), q(b), precision=HIGHEST)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [B, S, H, Dh]; rotate the two halves of each head."""
    S, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def loss(params, tokens, labels, cfg: dict, matmul: str = "f32"):
    """Mean next-token cross-entropy of one server's float32 params."""
    d, h, kv, dh, ff, n = _sizes(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    mm = functools.partial(_mm, matmul=matmul)
    B, S = tokens.shape
    x = params["embed"]["table"][tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def layer(x, bp):
        a = _rms(x, bp["ln1"]["scale"], eps)
        q = _rope(mm(a, bp["attn"]["w_q"]).reshape(B, S, h, dh), theta)
        k = _rope(mm(a, bp["attn"]["w_k"]).reshape(B, S, kv, dh), theta)
        v = mm(a, bp["attn"]["w_v"]).reshape(B, S, kv, dh)
        g = h // kv
        q = q.reshape(B, S, kv, g, dh).transpose(0, 2, 3, 1, 4)
        k = k.transpose(0, 2, 1, 3)[:, :, None]
        v = v.transpose(0, 2, 1, 3)[:, :, None]
        s = mm(q, jnp.swapaxes(k, -1, -2)) / jnp.sqrt(float(dh))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = mm(p, v).transpose(0, 3, 1, 2, 4).reshape(B, S, h * dh)
        x = x + mm(o, bp["attn"]["w_o"])
        a = _rms(x, bp["ln2"]["scale"], eps)
        m = jax.nn.silu(mm(a, bp["mlp"]["w_gate"])) * mm(a, bp["mlp"]["w_up"])
        return x + mm(m, bp["mlp"]["w_down"]), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    logits = mm(x, params["embed"]["table"].T)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


def draw_batches(data_seed: int, step: int, P: int, L: int, per_client: int,
                 seq_len: int, vocab: int, bigram_frac: float) -> dict:
    """The traffic's token rows for one round, drawn plainly from the seed.

    The law: a successor table, the permutation of the vocabulary that
    ``numpy.random.default_rng(data_seed)`` draws; for client (p, l) the key
    PRNGKey(data_seed) folded with the round, then p, then l, split in
    three; each of ``seq_len + 1`` positions draws a Zipf token (logits
    -log rank, by ``jax.random.categorical`` under the first key) and, with
    probability ``bigram_frac`` (``jax.random.bernoulli`` under the
    second), takes the successor of the position before it instead.
    Tokens are the first ``seq_len`` positions, labels the last."""
    succ = np.random.default_rng(data_seed).permutation(vocab)
    logits = -jnp.log(jnp.arange(1, vocab + 1, dtype=jnp.float32))
    base = jax.random.fold_in(jax.random.PRNGKey(data_seed), step)
    n = seq_len + 1
    rows = np.empty((P, L, per_client, n), np.int64)
    for p in range(P):
        for l in range(L):
            k1, k2, _ = jax.random.split(
                jax.random.fold_in(jax.random.fold_in(base, p), l), 3)
            draws = np.asarray(jax.random.categorical(
                k1, logits, shape=(per_client, n)))
            bigram = np.asarray(jax.random.bernoulli(
                k2, bigram_frac, (per_client, n)))
            t = draws.copy()
            for i in range(1, n):
                t[:, i] = np.where(bigram[:, i], succ[t[:, i - 1]], draws[:, i])
            rows[p, l] = t
    return {"tokens": rows[..., :-1].astype(np.int32),
            "labels": rows[..., 1:].astype(np.int32)}


def ring_matrix(P: int) -> np.ndarray:
    """Metropolis weights of a ring of P servers (1 for one server)."""
    A = np.zeros((P, P))
    if P == 1:
        return np.ones((1, 1))
    for p in range(P):
        for m in {(p - 1) % P, (p + 1) % P} - {p}:
            A[p, m] = 1.0 / 3.0
        A[p, p] = 1.0 - A[p].sum()
    return A


def laplace(key, shape, sigma):
    """Lap(0, sigma/sqrt 2), variance sigma^2, by the inverse CDF."""
    u = jax.random.uniform(key, shape, jnp.float32,
                           minval=-0.5 + 1e-7, maxval=0.5 - 1e-7)
    b = jnp.float32(sigma / np.sqrt(2.0))
    return -b * jnp.sign(u) * jnp.log1p(-2.0 * jnp.abs(u))


class Reference:
    """The reference run of the first steps of one cell."""

    def __init__(self, cfg: dict, gfl: dict, P: int, devices,
                 combine: str = "dense", matmul: str = "f32"):
        self.cfg, self.gfl, self.P = cfg, gfl, P
        # between chips psi + g travels in the parameters' dtype
        self.wire_dtype = None if combine == "dense" else jnp.bfloat16
        self.devices = list(devices)
        self.A = jnp.asarray(ring_matrix(P), jnp.float32)

        def client(w, tokens, labels):
            w32 = jax.tree.map(lambda x: x.astype(jnp.float32), w)
            return jax.value_and_grad(loss)(w32, tokens, labels, cfg, matmul)

        self._client = jax.jit(client)
        self._noise = jax.jit(self._draw_noise)
        self._combine = jax.jit(self._mix)

    def _draw_noise(self, key, like):
        leaves, treedef = jax.tree_util.tree_flatten(like)
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(treedef, [
            laplace(k, x.shape, self.gfl["sigma_g"]).astype(x.dtype)
            for k, x in zip(keys, leaves)])

    def _mix(self, psi, g, A):
        def leaf(x, n):
            wire = x.astype(jnp.float32) + n.astype(jnp.float32)
            if self.wire_dtype is not None:
                wire = wire.astype(self.wire_dtype).astype(jnp.float32)
            mixed = jnp.einsum("mp,m...->p...", A, wire, precision=HIGHEST)
            return (mixed - n.astype(jnp.float32)).astype(x.dtype)
        return jax.tree.map(leaf, psi, g)

    def step(self, params, key, batch):
        """One round: params [P, ...] bfloat16 -> (params, key, loss)."""
        mu, bound = self.gfl["mu"], self.gfl["grad_bound"]
        P = self.P
        L = batch["tokens"].shape[1]
        psis, losses = [], []
        for p in range(P):
            dev = self.devices[p % len(self.devices)]
            w_p = jax.device_put(jax.tree.map(lambda x: x[p], params), dev)
            acc = None
            for l in range(L):
                tok = jax.device_put(batch["tokens"][p, l], dev)
                lab = jax.device_put(batch["labels"][p, l], dev)
                val, g = self._client(w_p, tok, lab)
                nrm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
                coef = jnp.minimum(1.0, bound / jnp.maximum(nrm, 1e-12))
                g = jax.tree.map(lambda x: x * coef, g)
                acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
                losses.append(val)
            psis.append(jax.device_put(jax.tree.map(
                lambda w, a: (w.astype(jnp.float32) - mu * a / L
                              ).astype(w.dtype), w_p, acc), self.devices[0]))
        psi = jax.tree.map(lambda *xs: jnp.stack(xs), *psis)
        key, k_noise, _ = jax.random.split(key, 3)
        g = self._noise(k_noise, psi)
        new = self._combine(psi, g, self.A)
        lossv = float(np.mean([float(v) for v in losses]))
        return new, key, lossv

    def run(self, params0, key0, batches):
        """The first len(batches) rounds: (losses, params after 1, params
        after len(batches))."""
        params, key = params0, key0
        losses, after1 = [], None
        for i, batch in enumerate(batches):
            params, key, lv = self.step(params, key, batch)
            losses.append(lv)
            if i == 0:
                after1 = params
        return losses, after1, params
