"""DeepSeek-V2-Lite's operation and byte counts (``counts_deepseek.py``)."""
import json
import os

import pytest

import bench
import counts_deepseek as counts

CONF = json.load(open(os.path.join(bench.HERE, "configs",
                                   "deepseek-v2-lite.json")))


def _program_parameters(conf):
    import jax
    import numpy as np

    from repro.models import Model
    drv = bench.load_module(os.path.join(bench.HERE, "drivers",
                                         "moe_mesh_trainer.py"),
                            "moe_mesh_trainer")
    shapes = jax.eval_shape(Model(drv._program_config(conf)).init,
                            jax.random.PRNGKey(0))
    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))


def test_parameter_count_is_the_programs_for_the_cut():
    """The cut: a dense layer of 81.0M, five expert layers of 100.4M with
    8 experts held each, embedding and head over the 12,800-id slice."""
    n = counts.param_count(CONF)
    assert n == _program_parameters(CONF)
    assert n == pytest.approx(636e6, rel=0.01)


def test_train_flops_per_token_splits_as_the_layers_do():
    """2.15 GFLOP a token at 4096 tokens: attention 0.87, the expert
    layers 0.72 (at 0.75 held experts a token), the dense MLP 0.40, the
    head 0.16."""
    S = 4096
    total = counts.train_flops_per_token(CONF, S)
    assert counts.held_experts_per_token(CONF) == 0.75
    mlp = 6 * 3 * 2048 * 10944
    head = 6 * 2048 * 12800
    moe = 5 * 6 * (2048 * 64 + 3 * 2048 * 2816 + 0.75 * 3 * 2048 * 1408)
    attention = total - mlp - head - moe
    assert attention == pytest.approx(0.87e9, rel=0.01)
    assert moe == pytest.approx(0.72e9, rel=0.01)
    assert total == pytest.approx(2.15e9, rel=0.01)
    core = 3 * 6 * 2 * 16 * (192 + 128) * (S + 1) / 2
    assert counts.mla_core_flops(CONF, S, rows=1) == pytest.approx(core * S)


def test_grouped_matmul_flops_are_six_per_expert_parameter_a_token():
    assert counts.gmm_flops(CONF, 768) == 6 * 3 * 2048 * 1408 * 768
    # at 768 rows an expert the weights' bytes are a small part
    flops = counts.gmm_flops(CONF, 6144)
    assert counts.gmm_bytes(CONF, 6144, calls=1) * 240 < flops
