"""The round's device scopes in the mesh train step.

``launch/steps.py`` names each phase of the round with ``jax.named_scope``
(``gfl.client_grads``, ``gfl.clip``, ``gfl.client_mean``, ``gfl.update``,
``gfl.privatize``, ``gfl.combine``) so that a profiler trace joined with
the compiled HLO gives device time per phase.  The scopes are metadata:
the compiled step carries them in its ``op_name``s, and a build inside a
profiling telemetry session computes the same bits as one without.
"""
import dataclasses
import re

import jax
import numpy as np
import pytest

from repro.configs.base import GFLConfig
from repro.configs.registry import get_config
from repro.data import TokenStream, federated_token_batches
from repro.launch import steps as S
from repro.launch.mesh import make_device_mesh
from repro.models import Model
from repro.telemetry import session

SCOPES = ("gfl.client_grads", "gfl.clip", "gfl.client_mean", "gfl.update",
          "gfl.privatize", "gfl.combine")


@pytest.fixture(scope="module")
def parts():
    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              num_layers=2, vocab_size=512)
    model = Model(cfg)
    mesh = make_device_mesh(jax.devices()[:1])
    batch = federated_token_batches(TokenStream(vocab=512, seed=0), seed=0,
                                    step=0, P=1, L=2, per_client=2,
                                    seq_len=32)
    return model, mesh, batch


def _run(parts, gfl):
    model, mesh, batch = parts
    with mesh:
        step = jax.jit(S.make_train_step(model, gfl, mesh))
        state = S.init_train_state(model, gfl, mesh, jax.random.PRNGKey(0))
        hlo = step.lower(state, batch).compile().as_text()
        new, metrics = step(state, batch)
    return hlo, jax.device_get((new.params, metrics["loss"]))


@pytest.mark.parametrize("client_parallel", [False, True])
def test_step_carries_each_scope_and_the_same_bits(parts, client_parallel):
    gfl = GFLConfig(topology="ring", privacy="hybrid", sigma_g=0.01, mu=0.1,
                    grad_bound=10.0, combine_impl="dense",
                    client_parallel=client_parallel)
    hlo, (params, loss) = _run(parts, gfl)
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in SCOPES:
        assert any(scope in n for n in names), scope
    # forward and backward of the clients' loss both carry its scope
    grads = [n for n in names if "gfl.client_grads" in n]
    assert any("transpose(" in n for n in grads)
    assert any("transpose(" not in n for n in grads)

    with session("memory", profile=True):
        hlo_on, (params_on, loss_on) = _run(parts, gfl)
    assert np.array_equal(loss, loss_on)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params_on)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
