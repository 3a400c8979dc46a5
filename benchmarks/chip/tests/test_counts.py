"""The benchmark's own operation and byte counts, and its table of peaks."""
import json
import os

import pytest

import bench
import counts

CONF = json.load(open(os.path.join(bench.HERE, "configs",
                                   "smollm-135m.json")))


def test_smollm_parameter_count_is_the_published_one():
    assert counts.lm_param_count(CONF) == 134_515_008


def test_smollm_flops_per_token_is_six_per_parameter_plus_attention():
    S = 1024
    n_matmul = counts.lm_param_count(CONF) - 61 * 576   # norms do no matmul
    attention = 6 * 30 * (S + 1) * 9 * 64      # QK and AV, causal, x3
    assert counts.lm_train_flops_per_token(CONF, S) == pytest.approx(
        6 * n_matmul + attention, rel=1e-12)


def test_peaks_are_keyed_by_device_kind():
    assert bench.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        bench.peaks_for("TPU v9 imaginary")
