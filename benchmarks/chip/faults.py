"""Faults planted under the timed path, for the harness's tests and for
reading each fault's numbers on the chip (``calibrate.py``).

Each fault patches the program's entry points that a driver calls while it
builds, so the window and the first rounds run broken:

``unchanged_state``  the round returns its state as it got it;
``half_batch``       half of each server's clients are left out and the
                     mean is taken over the rest;
``altered_token``    one token of each round's batch is changed where the
                     batch is produced.
"""
from __future__ import annotations

import contextlib

FAULTS = ("unchanged_state", "half_batch", "altered_token")


def _half(batch):
    import jax
    L = jax.tree_util.tree_leaves(batch)[0].shape[1]
    return jax.tree.map(lambda x: x[:, :L // 2], batch)


@contextlib.contextmanager
def planted(name: str):
    import repro.data
    from repro.launch import steps
    saved = [(steps, "make_train_step", steps.make_train_step),
             (repro.data, "federated_token_batches",
              repro.data.federated_token_batches)]
    train0, batches0 = saved[0][2], saved[1][2]
    if name == "unchanged_state":
        def make_train_step(*a, **k):
            real = train0(*a, **k)
            return lambda state, batch, *r, **kw: (
                state, real(state, batch, *r, **kw)[1])
        steps.make_train_step = make_train_step
    elif name == "half_batch":
        def make_train_step(*a, **k):
            real = train0(*a, **k)
            return lambda state, batch, *r, **kw: real(state, _half(batch),
                                                       *r, **kw)
        steps.make_train_step = make_train_step
    elif name == "altered_token":
        def federated_token_batches(stream, *a, **k):
            b = batches0(stream, *a, **k)
            t = b["tokens"]
            return {**b, "tokens": t.at[0, 0, 0, 0].set(
                (t[0, 0, 0, 0] + 1) % stream.vocab)}
        repro.data.federated_token_batches = federated_token_batches
    else:
        raise ValueError(f"unknown fault {name!r}; one of {FAULTS}")
    try:
        yield
    finally:
        for mod, attr, val in saved:
            setattr(mod, attr, val)
