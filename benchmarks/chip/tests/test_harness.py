"""The harness end to end on the CPU at tiny sizes: the one command refuses
to run without a TPU or without the program; a sound run of each cell comes
out correct; with the timed path broken underneath (``faults.py``), and with
the control in the program's place, ``correct`` comes out false."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import bench
import calibrate
import faults
import run

TINY = {
    "mesh_trainer": {
        "config": {"num_hidden_layers": 2, "hidden_size": 128,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "intermediate_size": 256, "vocab_size": 512},
        "traffic": {"seq_len": 32, "clients": 2, "per_client": 2}},
}
PEAKS = {"bf16_flops_per_s": 1e12}
ONE_CHIP = ["smollm135m.hybrid.1chip"]


def tiny(cell: str) -> dict:
    drv = bench.Cell(cell).config["driver"]
    return {**TINY[drv], "peaks": PEAKS}


def run_tiny(cell: str, seed: int = 2147483659, trace: int = 0) -> dict:
    return run.run_cell(["--workload", cell, "--seed", str(seed),
                         "--seconds", "1", "--trace", str(trace)],
                        require_tpu=False, overrides=tiny(cell))


def test_refuses_the_cpu():
    p = subprocess.run([sys.executable, os.path.join(bench.HERE, "run.py"),
                        "--workload", ONE_CHIP[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(bench.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(bench.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces"))
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", ONE_CHIP[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120,
                       env={k: v for k, v in os.environ.items()
                            if k != "PYTHONPATH"})
    assert p.returncode != 0
    assert not p.stdout.strip()


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_sound_run_is_correct(cell):
    res = run_tiny(cell)
    assert res["correct"], res["checks"]
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    names = {m["name"] for m in bench.Cell(cell).e2e}
    assert set(res["metrics"]) == names


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_traced_run_reads_per_layer_metrics(cell):
    res = run_tiny(cell, trace=1)
    assert "compile_s" in res["metrics"] and "mfu" in res["metrics"]
    assert 0 < res["metrics"]["mfu"]["value"] <= 100


@pytest.mark.parametrize("cell,fault", [(c, f) for c in ONE_CHIP
                                        for f in faults.FAULTS])
def test_fault_makes_run_incorrect(cell, fault):
    with faults.planted(fault):
        res = run_tiny(cell)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_control_fails_a_limit(cell, capsys):
    c = bench.Cell(cell, overrides={k: v for k, v in tiny(cell).items()
                                    if k != "peaks"})
    import jax
    calibrate.readings(c, jax.devices()[:1], [7, 8], n_control=2)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    for line in lines:
        assert all(v <= c.limits[k] for k, v in line["gaps"].items())
        assert any(v > c.limits[k] for k, v in line["control_gaps"].items())
