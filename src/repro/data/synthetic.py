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


@dataclass(frozen=True)
class TokenStream:
    """Synthetic LM distribution: zipf unigram mixed with a deterministic
    bigram successor table (induction structure)."""
    vocab: int
    seed: int = 0
    bigram_frac: float = 0.5

    def _succ_table(self):
        rng = np.random.default_rng(self.seed)
        return jnp.asarray(rng.permutation(self.vocab), jnp.int32)

    def sample(self, key, batch: int, seq_len: int) -> jax.Array:
        with jax.named_scope("gfl.input"):
            succ = self._succ_table()
            k1, k2, k3 = jax.random.split(key, 3)
            # zipf via exponential rank trick
            ranks = jnp.arange(1, self.vocab + 1, dtype=jnp.float32)
            logits = -jnp.log(ranks)
            draws = jax.random.categorical(k1, logits, shape=(batch, seq_len))
            use_bigram = jax.random.bernoulli(k2, self.bigram_frac,
                                              (batch, seq_len))

            def step(prev, inp):
                d, ub = inp
                tok = jnp.where(ub, succ[prev], d)
                return tok, tok

            first = draws[:, 0]
            _, toks = jax.lax.scan(step, first,
                                   (draws[:, 1:].T, use_bigram[:, 1:].T))
            return jnp.concatenate([first[:, None], toks.T], axis=1)


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

    The build runs in the ``gfl.input`` span (``round=step``) and its
    device work under the ``gfl.input`` scope."""
    from repro.telemetry import trace_span
    with trace_span("gfl.input", round=step), jax.named_scope("gfl.input"):
        base = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        if client_ids is not None:
            client_ids = np.asarray(client_ids)

        def client_batch(p, l):
            cid = l if client_ids is None else int(client_ids[p, l])
            k = jax.random.fold_in(jax.random.fold_in(base, p), cid)
            return make_batch(stream, k, per_client, seq_len)

        batches = [[client_batch(p, l) for l in range(L)] for p in range(P)]
        return jax.tree.map(lambda *xs: jnp.stack(xs).reshape(
            P, L, *xs[0].shape), *[b for row in batches for b in row])
