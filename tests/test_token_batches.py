"""The round's token batch: one compiled program, the law bit for bit.

``federated_token_batches`` builds all P x L clients' rows in one jitted
call.  These tests hold it to the law as the benchmark's reference draws it
(per client, eagerly, the successor chain in a Python loop), check that new
rounds and cohorts reuse the compiled program, and that its device work
still carries the ``gfl.input`` scope.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import TokenStream, federated_token_batches, make_batch
from repro.data import synthetic
from repro.telemetry import profile

SEED = 2718281829          # above 2**31, as the benchmark's data seeds are
P, L, PER_CLIENT, SEQ, VOCAB = 2, 3, 2, 12, 97


def _slow_rows(key, succ, per_client, n, vocab, bigram_frac):
    """One client's rows, drawn eagerly with the chain in Python."""
    logits = -jnp.log(jnp.arange(1, vocab + 1, dtype=jnp.float32))
    k1, k2, _ = jax.random.split(key, 3)
    draws = np.asarray(jax.random.categorical(k1, logits,
                                              shape=(per_client, n)))
    bigram = np.asarray(jax.random.bernoulli(k2, bigram_frac,
                                             (per_client, n)))
    t = draws.copy()
    for i in range(1, n):
        t[:, i] = np.where(bigram[:, i], succ[t[:, i - 1]], draws[:, i])
    return t


def _slow_batches(seed, step, P, L, per_client, seq_len, vocab, bigram_frac,
                  client_ids=None):
    succ = np.random.default_rng(seed).permutation(vocab)
    base = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    rows = np.empty((P, L, per_client, seq_len + 1), np.int64)
    for p in range(P):
        for l in range(L):
            cid = l if client_ids is None else int(client_ids[p][l])
            key = jax.random.fold_in(jax.random.fold_in(base, p), cid)
            rows[p, l] = _slow_rows(key, succ, per_client, seq_len + 1,
                                    vocab, bigram_frac)
    return {"tokens": rows[..., :-1], "labels": rows[..., 1:]}


IDS = [[7, 0, 4], [2, 9, 5]]     # explicit, not positional


@pytest.mark.parametrize("ids", [None, IDS], ids=["positional", "cohort"])
@pytest.mark.parametrize("step", [0, 1, 7])
def test_round_batch_matches_the_per_client_law(step, ids):
    stream = TokenStream(vocab=VOCAB, seed=SEED, bigram_frac=0.5)
    got = federated_token_batches(stream, seed=SEED, step=step, P=P, L=L,
                                  per_client=PER_CLIENT, seq_len=SEQ,
                                  client_ids=ids)
    want = _slow_batches(SEED, step, P, L, PER_CLIENT, SEQ, VOCAB, 0.5, ids)
    for k in ("tokens", "labels"):
        assert got[k].shape == (P, L, PER_CLIENT, SEQ)
        assert got[k].dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got[k]), want[k])


@pytest.mark.parametrize("bigram_frac", [0.0, 0.5, 1.0])
def test_make_batch_matches_the_per_client_law(bigram_frac):
    stream = TokenStream(vocab=VOCAB, seed=SEED, bigram_frac=bigram_frac)
    key = jax.random.fold_in(jax.random.PRNGKey(SEED), 3)
    got = make_batch(stream, key, PER_CLIENT, SEQ)
    succ = np.random.default_rng(SEED).permutation(VOCAB)
    rows = _slow_rows(key, succ, PER_CLIENT, SEQ + 1, VOCAB, bigram_frac)
    np.testing.assert_array_equal(np.asarray(got["tokens"]), rows[:, :-1])
    np.testing.assert_array_equal(np.asarray(got["labels"]), rows[:, 1:])


def test_new_rounds_and_cohorts_reuse_the_compiled_build():
    assert profile.ensure_listener()
    stream = TokenStream(vocab=VOCAB, seed=SEED)
    build = dict(stream=stream, seed=SEED, P=P, L=L, per_client=PER_CLIENT,
                 seq_len=SEQ)
    jax.block_until_ready(federated_token_batches(step=0, **build))
    before = profile.compile_counters()
    for step, ids in ((1, None), (2, IDS), (3, [[1, 1, 8], [3, 6, 0]])):
        jax.block_until_ready(federated_token_batches(step=step,
                                                      client_ids=ids,
                                                      **build))
    after = profile.compile_counters()
    for k in ("retraces", "compiles", "cache_loads"):
        assert after[k] == before[k], k
    # the listener sees a build of a new shape
    jax.block_until_ready(federated_token_batches(
        step=0, **{**build, "seq_len": SEQ + 1}))
    assert profile.compile_counters()["retraces"] > after["retraces"]


def test_compiled_build_carries_the_input_scope():
    hlo = synthetic._round_batches.lower(
        synthetic._seed_key(SEED), np.int32(0),
        np.zeros((P, L), np.int32), synthetic._successors(VOCAB, SEED),
        synthetic._zipf_logits(VOCAB), per_client=PER_CLIENT, seq_len=SEQ,
        bigram_frac=0.5).compile().as_text()
    names = [n for n in re.findall(r'op_name="([^"]*)"', hlo)
             if n.startswith("jit(_round_batches)/")]
    assert names
    assert all(n.startswith("jit(_round_batches)/gfl.input/")
               for n in names), names
    assert any("/while" in n for n in names)      # the successor chain
    assert any("_gumbel" in n for n in names)     # the Zipf draw
