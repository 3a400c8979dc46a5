"""Driver: the mesh trainer (``mesh_trainer.py``) for a configuration with
latent attention and a dropless expert layer over this chip's share of the
routed experts (DeepSeek-V2-Lite).

It is ``mesh_trainer``'s ``Trainer``, ``compare`` and window loop with
three differences:

- the program's configuration maps MLA, the held experts, the router and
  YaRN from the configuration's JSON (``_program_config``);
- the step's count of tokens routed to each held expert is kept for every
  round of the window (device arrays, read only after it) and gives the
  grouped matmul's FLOPs; the round's model FLOPs come from
  ``counts_deepseek`` at the expected expert load;
- a traced run reduces the trace by the program's ``moe.*`` and
  ``mla.*`` scopes (exclusive device seconds of the step's ops, from the
  step's compiled HLO) before the trace is removed;
- ``correct`` holds the numbers of ``compare`` to which the workload gives
  a limit.  This cell gives none to ``loss``: after one update the third
  round's loss moves as far for one changed token of 16,384 as for the
  reference in float8, so it cannot tell a sound run from a faulty one
  (PERF.md §2).  The gap is still logged with the readings.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import os
import re
import sys
import time

import bench
import counts_deepseek as counts

_mt = bench.load_module(os.path.join(bench.HERE, "drivers", "mesh_trainer.py"),
                        "mesh_trainer_for_moe")


def _private_copy(path: str, name: str):
    """A module of its own from ``path`` (registered, for its
    dataclasses), whose globals this driver may change."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


_ts = _private_copy(os.path.join(bench.HERE, "trace_scopes.py"),
                    "chipbench_trace_scopes_for_moe")
# this copy of trace_scopes names each op by its first moe.* / mla.* scope
_ts.SCOPE = re.compile(r"\b((?:moe|mla)\.[A-Za-z_]+)")

CHECK_ROUNDS = _mt.CHECK_ROUNDS
compare, readings, data_seed = _mt.compare, _mt.readings, _mt.data_seed
MOE_SCOPES = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine",
              "moe.shared")
MLA_SCOPE = "mla.attention"


def _program_config(conf: dict):
    from repro.configs.base import MLAConfig, YarnConfig
    from repro.configs.registry import get_config
    base = get_config(conf["program_arch"])
    rs = conf["rope_scaling"]
    n_dense = conf["first_k_dense_replace"]
    return dataclasses.replace(
        base, num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["moe_intermediate_size"], vocab_size=conf["vocab_size"],
        tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]), norm_eps=conf["rms_norm_eps"],
        param_dtype=conf["torch_dtype"],
        rope_scaling=YarnConfig(
            factor=float(rs["factor"]),
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"]),
        mla=MLAConfig(kv_lora_rank=conf["kv_lora_rank"],
                      q_lora_rank=conf["q_lora_rank"] or 0,
                      qk_nope_head_dim=conf["qk_nope_head_dim"],
                      qk_rope_head_dim=conf["qk_rope_head_dim"],
                      v_head_dim=conf["v_head_dim"]),
        moe=dataclasses.replace(
            base.moe, num_experts=conf["router_experts"],
            experts_held=conf["n_routed_experts"],
            expert_offset=conf["expert_offset"],
            num_shared_experts=conf["n_shared_experts"],
            top_k=conf["num_experts_per_tok"],
            expert_d_ff=conf["moe_intermediate_size"],
            first_dense_layers=n_dense,
            first_dense_d_ff=conf["intermediate_size"],
            norm_topk=conf["norm_topk_prob"],
            balance="sequence" if conf["seq_aux"] else "switch",
            router_aux_coef=conf["aux_loss_alpha"], dispatch="dropless"))


# mesh_trainer's Trainer builds its model from its module's _program_config
_mt._program_config = _program_config
Trainer = _mt.Trainer


class ScopedWindow(bench.Window):
    """``bench.Window`` that, when traced, also reduces the trace by the
    step's ``moe.*`` / ``mla.*`` scopes before removing it."""

    def __init__(self, seconds, trace, clock, devices, hlo_text: str = ""):
        super().__init__(seconds, trace, clock, devices)
        self.hlo_text = hlo_text
        self.scopes = None

    def __exit__(self, *exc):
        import shutil

        import jax
        self.t1 = time.perf_counter()
        self.compiles = self.clock.count - self.compiles0
        self.cache_loads = self.clock.cache_loads - self.loads0
        if self.trace:
            self._ann.__exit__(*exc)
            jax.profiler.stop_trace()
            if exc[0] is None:
                from trace_reduce import find_xplane
                ids = {d.id for d in self.devices}
                st = _ts.reduce_scoped(find_xplane(bench.TRACE_DIR),
                                       self.hlo_text, ids)
                self.summary = st.summary
                self.scopes = step_scope_seconds(st)
            shutil.rmtree(bench.TRACE_DIR, ignore_errors=True)
        return False


#: Pallas kernels take their scope from the name they run under: the
#: compiled HLO does not keep their ``op_name`` (on a v5e splash's forward
#: kernel carries none, and its dq kernel a grouped matmul's)
KERNEL_SCOPES = ((re.compile(r"splash_mha_"), MLA_SCOPE),
                 (re.compile(r"t?gmm(\.\d+)?$"), "moe.experts"))


def step_scope_seconds(st) -> dict:
    """Exclusive device seconds per chip of the step's ops by scope."""
    out: dict[str, float] = {}
    for o, p, s, x in zip(st.summary.ops, st.program, st.scope,
                          st.exclusive):
        if p != st.step_program:
            continue
        s = next((k for pat, k in KERNEL_SCOPES if pat.match(o.name)), s)
        out[s] = out.get(s, 0.0) + x
    return {k: v * 1e-9 / max(st.summary.n_devices, 1)
            for k, v in out.items()}


def run(cell, seed, seconds, trace, setup, devices, clock, **_):
    import jax
    import numpy as np

    from repro.core.privacy.mechanism import mechanism_for
    setup.lap("import")
    T = Trainer(cell, devices)
    acc = mechanism_for(T.gcfg).accountant()
    setup.lap("build")
    state, prog = T.start(seed, acc)
    setup.lap("compile_and_check_rounds")
    hlo_text = ""
    if trace:
        with T.mesh:
            hlo_text = T.step.lower(state, T.batch(seed, 0)).compile(
            ).as_text()
        setup.lap("step_hlo")

    spans = bench.Spans(annotate=trace)
    setup_s, compile_s = setup.total(), clock.seconds
    i, n, prev, held = CHECK_ROUNDS, 0, None, []
    with T.mesh, ScopedWindow(seconds, trace, clock, devices,
                              hlo_text) as win:
        while True:
            with spans("bench.input"):
                batch = T.batch(seed, i)
            with spans("bench.step"):
                state, m = T.step(state, batch)
            with spans("bench.accountant"):
                acc.advance(1)
            if prev is not None:
                with spans("bench.wait"):
                    prev.block_until_ready()
            prev, i, n = m["loss"], i + 1, n + 1
            held.append(m["expert_tokens"])
            if win.over():
                break
        jax.block_until_ready(state)
    last_loss = float(prev)
    held = np.asarray(jax.device_get(held), np.int64)     # [n, P, H]
    mem = bench.memory_peak_bytes(devices)
    del state, m, prev, batch
    gc.collect()

    t_ref = time.perf_counter()
    ref = T.reference(seed)
    gaps = compare(prog, ref, cell.config["gfl"]["mu"])
    conf = cell.config
    per_expert = held.sum(axis=(0, 1))
    bench.log("check_detail", {"program": readings(prog),
                               "reference": readings(ref),
                               "reference_s": time.perf_counter() - t_ref})
    if win.scopes is not None:
        bench.log("step_scopes_s", win.scopes)
    bench.log("expert_load", {
        "tokens_per_held_expert": per_expert.tolist(),
        "max_over_mean": float(per_expert.max() / max(per_expert.mean(), 1)),
        "expected_per_expert_client_step": (
            T.b * T.S * conf["num_experts_per_tok"] / conf["router_experts"])})
    finite = [np.isfinite(x) for x in prog["loss"] + [last_loss]]
    tokens = T.P * T.L * T.b * T.S
    n_moe = conf["num_hidden_layers"] - conf["first_k_dense_replace"]
    assignments = int(held.sum())
    return {
        "setup_s": setup_s, "compile_s": compile_s,
        "rounds": n, "window_s": win.window_s,
        "compiles_in_window": win.compiles,
        "cache_loads_in_window": win.cache_loads,
        "updates_per_round": T.P * T.L,
        "spans": spans.seconds, "trace": win.summary, "scopes": win.scopes,
        "work": {
            "flops_per_round": counts.train_flops_per_token(conf, T.S)
            * tokens,
            "gmm_flops": counts.gmm_flops(conf, assignments),
            "gmm_bytes": counts.gmm_bytes(conf, assignments,
                                          n * T.P * T.L * n_moe),
            "mla_flops": n * counts.mla_core_flops(conf, T.S,
                                                   T.P * T.L * T.b),
            "mla_bytes": n * counts.mla_core_bytes(conf, T.S,
                                                   T.P * T.L * T.b)},
        "memory_peak_bytes": mem,
        "checks": [(k, v, cell.limits[k]) for k, v in gaps.items()
                   if k in cell.limits],
        "attempted": CHECK_ROUNDS + n,
        "failed": int(len(finite) - sum(finite)),
    }
