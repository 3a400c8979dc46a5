"""Deterministic synthetic data pipelines.

Two generators:
  * the paper's logistic-regression data (Section V), per (server, client);
  * a token-stream LM pipeline (zipf-ish unigram + induction-head bigram
    structure so models actually have signal to fit) for the LM trainers,
    batched per (server, client) for the GFL protocol.

Everything is counter-based (jax.random.fold_in chains) so any batch is
reproducible from (seed, server, client, step) without global state.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


def logistic_client_data(key, P: int, K: int, N: int, M: int,
                         sigma_h_range=(0.5, 1.5)):
    """Section-V generator: labels +-1, h | gamma ~ N(gamma*1, sigma^2 I)."""
    k1, k2, k3 = jax.random.split(key, 3)
    labels = jnp.where(jax.random.bernoulli(k1, 0.5, (P, K, N)), 1.0, -1.0)
    sigma = jax.random.uniform(k2, (P, K, 1, 1), minval=sigma_h_range[0],
                               maxval=sigma_h_range[1])
    feats = labels[..., None] + sigma * jax.random.normal(k3, (P, K, N, M))
    return feats, labels


@functools.lru_cache(maxsize=16)
def _successors(vocab: int, seed: int) -> jax.Array:
    """The successor permutation, drawn and put on the device once."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.permutation(vocab), jnp.int32)


@functools.lru_cache(maxsize=16)
def _zipf_logits(vocab: int) -> jax.Array:
    """Unigram logits ``-log rank``, computed by an eager device op: inside
    a compiled program XLA could fold them at compile time, on the host."""
    return -jnp.log(jnp.arange(1, vocab + 1, dtype=jnp.float32))


@functools.lru_cache(maxsize=16)
def _seed_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(seed)


def _rows(keys, succ, logits, per_client: int, n: int, bigram_frac: float):
    """Token rows ``[C, per_client, n]`` for ``C`` client keys.

    Each client draws a Zipf token per position and, with probability
    ``bigram_frac``, takes the successor of the position before instead;
    the successor chain is one scan over all ``C * per_client`` rows."""
    def draws(key):
        k1, k2, _ = jax.random.split(key, 3)
        return (jax.random.categorical(k1, logits, shape=(per_client, n)),
                jax.random.bernoulli(k2, bigram_frac, (per_client, n)))

    d, use_bigram = jax.vmap(draws)(keys)
    d, use_bigram = d.reshape(-1, n), use_bigram.reshape(-1, n)

    def step(prev, inp):
        di, ub = inp
        tok = jnp.where(ub, succ[prev], di)
        return tok, tok

    first = d[:, 0]
    _, toks = jax.lax.scan(step, first, (d[:, 1:].T, use_bigram[:, 1:].T))
    toks = jnp.concatenate([first[:, None], toks.T], axis=1)
    return toks.reshape(-1, per_client, n)


@functools.partial(jax.jit, static_argnames=("per_client", "n",
                                              "bigram_frac"))
def _sample(keys, succ, logits, per_client, n, bigram_frac):
    with jax.named_scope("gfl.input"):
        return _rows(keys, succ, logits, per_client, n, bigram_frac)


@functools.partial(jax.jit, static_argnames=("per_client", "seq_len",
                                              "bigram_frac"))
def _round_batches(key, step, ids, succ, logits, per_client, seq_len,
                   bigram_frac):
    """One round's ``{tokens, labels}``, ``[P, L, per_client, seq_len]``:
    client (p, l) draws under ``fold_in(fold_in(fold_in(key, step), p),
    ids[p, l])``."""
    with jax.named_scope("gfl.input"):
        P, L = ids.shape
        base = jax.random.fold_in(key, step)
        keys = jax.vmap(lambda p, cid: jax.random.fold_in(
            jax.random.fold_in(base, p), cid))(
                jnp.repeat(jnp.arange(P), L), ids.reshape(-1))
        toks = _rows(keys, succ, logits, per_client, seq_len + 1,
                     bigram_frac).reshape(P, L, per_client, seq_len + 1)
        return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}


@dataclass(frozen=True)
class TokenStream:
    """Synthetic LM distribution: zipf unigram mixed with a deterministic
    bigram successor table (induction structure)."""
    vocab: int
    seed: int = 0
    bigram_frac: float = 0.5

    def sample(self, key, batch: int, seq_len: int) -> jax.Array:
        return _sample(jnp.expand_dims(key, 0),
                       _successors(self.vocab, self.seed),
                       _zipf_logits(self.vocab), per_client=batch, n=seq_len,
                       bigram_frac=self.bigram_frac)[0]


def make_batch(stream: TokenStream, key, batch: int, seq_len: int) -> dict:
    toks = stream.sample(key, batch, seq_len + 1)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def federated_token_batches(stream: TokenStream, seed: int, step: int,
                            P: int, L: int, per_client: int, seq_len: int,
                            client_ids=None) -> dict:
    """Batch pytree with leading [P, L] dims for :func:`repro.core.gfl.gfl_round`.

    Each (server, client) pair gets its own fold_in chain, so client data is
    disjoint and reproducible.  ``client_ids`` ([P, L] ints, optional)
    names the *population* client behind each cohort slot — a virtual
    client keeps the same data chain whichever round (and slot) a
    :class:`~repro.core.population.CohortScheduler` samples it into;
    the default is the positional identity ``client_ids[p, l] = l``.

    The whole round is one compiled program, built once per shape; the
    round and the ids are its arguments.  The build runs in the
    ``gfl.input`` span (``round=step``) and its device work under the
    ``gfl.input`` scope."""
    from repro.telemetry import trace_span
    with trace_span("gfl.input", round=step):
        ids = (np.broadcast_to(np.arange(L, dtype=np.int32), (P, L))
               if client_ids is None
               else np.asarray(client_ids, np.int32).reshape(P, L))
        return _round_batches(
            _seed_key(seed), np.int32(step), ids,
            _successors(stream.vocab, stream.seed),
            _zipf_logits(stream.vocab), per_client=per_client,
            seq_len=seq_len, bigram_frac=stream.bigram_frac)
