"""The DeepSeek-V2-Lite cell end to end on the CPU at a tiny size: a
sound run is correct, the control fails a limit, a planted fault makes
the run incorrect, and a traced run reads the expert layer's and the
latent attention's metrics from the step's scopes."""
import json

import jax
import pytest

import bench
import calibrate
import faults
import run

CELL = "dsv2lite.ep8.hybrid.1chip"
TINY = {
    "config": {"num_hidden_layers": 2, "hidden_size": 128,
               "num_attention_heads": 4, "kv_lora_rank": 32,
               "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
               "v_head_dim": 32, "intermediate_size": 256,
               "moe_intermediate_size": 64, "vocab_size": 512,
               "router_experts": 16, "n_routed_experts": 4},
    "traffic": {"seq_len": 32, "clients": 2, "per_client": 2},
    # the cell's limits are set at its published widths on the chip; at
    # this size bfloat16 rounding moves the worst leaf more (sound runs
    # read up to 5.3e-3 grad_norm and 0.041 change_norm on the CPU, the
    # control 0.058 and 0.039 at least), so the tiny run has its own; like
    # the cell, it holds no limit on the loss
    "limits": {"grad_norm": 0.02, "change_norm": 0.1}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
NEW_METRICS = ("device.moe_ms_per_round", "moe.gmm_roofline",
               "mla.attention_roofline")


def run_tiny(seed: int = 2147483659, trace: int = 0) -> dict:
    return run.run_cell(["--workload", CELL, "--seed", str(seed),
                         "--seconds", "1", "--trace", str(trace)],
                        require_tpu=False,
                        overrides={**TINY, "peaks": PEAKS})


def test_sound_run_is_correct():
    res = run_tiny()
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == set(bench.Cell(CELL, overrides=TINY).limits)
    names = {m["name"] for m in bench.Cell(CELL).e2e}
    assert set(res["metrics"]) == names


def test_control_fails_a_limit(capsys):
    c = bench.Cell(CELL, overrides=TINY)
    calibrate.readings(c, jax.devices()[:1], [7, 8], n_control=2)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert len(lines) == 2
    for line in lines:
        assert all(line["gaps"][k] <= lim for k, lim in c.limits.items())
        assert any(line["control_gaps"][k] > lim
                   for k, lim in c.limits.items())


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fault_makes_run_incorrect(fault):
    with faults.planted(fault):
        res = run_tiny()
    assert not res["correct"], res["checks"]


def test_traced_run_reads_the_scoped_metrics(monkeypatch):
    """On the CPU the trace holds no TPU op, so the driver's reduction of
    the step's scopes is given device seconds by scope: the three new
    metrics read them with the window's counted work."""
    drv = bench.Cell(CELL).driver()
    seen = {}

    def scopes(st):
        seen["hlo"] = st.step_program
        return {"moe.route": 0.01, "moe.dispatch": 0.02,
                "moe.experts": 0.5, "moe.combine": 0.02, "moe.shared": 0.05,
                "mla.attention": 0.4, "unscoped": 1.0}

    monkeypatch.setattr(drv, "step_scope_seconds", scopes)
    monkeypatch.setattr(bench.Cell, "driver", lambda self: drv)
    res = run_tiny(trace=1)
    assert seen["hlo"].startswith("jit_step_fn")
    for name in NEW_METRICS:
        assert name in res["metrics"], name
    assert res["metrics"]["device.moe_ms_per_round"]["value"] > 0
    for name in NEW_METRICS[1:]:
        assert 0 < res["metrics"][name]["value"] <= 100


def test_step_hlo_names_the_expert_and_attention_scopes():
    """The step's compiled HLO carries the moe.* and mla.* scopes that the
    driver's copy of ``trace_scopes`` reads."""
    c = bench.Cell(CELL, overrides=TINY)
    drv = c.driver()
    T = drv.Trainer(c, jax.devices()[:1])
    state, _ = T.start(2147483659)
    with T.mesh:
        text = T.step.lower(state, T.batch(2147483659, 0)).compile().as_text()
    module, scopes = drv._ts.hlo_scopes(text)
    assert module.startswith("jit_step_fn")
    found = set(scopes.values())
    assert set(drv.MOE_SCOPES) <= found, found
    assert drv.MLA_SCOPE in found


def test_kernels_take_their_scope_from_their_name():
    """The grouped matmuls and splash kernels count to ``moe.experts`` and
    ``mla.attention`` whatever ``op_name`` the compiled HLO left them; other
    ops keep their scope, and other programs' ops are left out."""
    from types import SimpleNamespace as NS
    drv = bench.Cell(CELL).driver()
    names = ["splash_mha_fwd_residuals.56", "splash_mha_dq_no_residuals.12",
             "gmm.45", "tgmm.13", "fusion.7", "fusion.8", "gmm_like.1"]
    scopes = ["unscoped", "moe.experts", "moe.experts", "unscoped",
              "moe.route", "unscoped", "moe.combine"]
    st = NS(summary=NS(ops=[NS(name=n) for n in names], n_devices=1),
            program=["jit_step_fn"] * 6 + ["jit_other"],
            scope=scopes, exclusive=[1e9, 2e9, 3e9, 4e9, 5e9, 6e9, 7e9],
            step_program="jit_step_fn")
    assert drv.step_scope_seconds(st) == {
        "mla.attention": 3.0, "moe.experts": 7.0, "moe.route": 5.0,
        "unscoped": 6.0}
