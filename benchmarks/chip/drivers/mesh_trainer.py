"""Driver: the mesh trainer (``launch/train.py``'s loop) on one or more chips.

It builds the program as ``launch.train.main`` does — ``jax.jit`` of
``steps.make_train_step``, one GFL server per chip, and per round
``federated_token_batches`` then the step, with the accountant charged once
per round — but from a state whose weights the benchmark makes from the
seed.  The first three rounds are set-up: they compile the step and feed
the comparison with the plain reference.  The same step and state then run
the window.  Rounds are dispatched asynchronously, one round in flight.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time

import bench
import counts

CHECK_ROUNDS = 3


def _program_config(conf: dict):
    from repro.configs.registry import get_config
    return dataclasses.replace(
        get_config(conf["program_arch"]),
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=conf["rope_theta"], norm_eps=conf["rms_norm_eps"],
        head_dim=conf.get("head_dim", 0), param_dtype=conf["torch_dtype"])


def _leaf_norms(a, b):
    import jax
    import jax.numpy as jnp
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def worst_leaf_gap(prog: list[float], ref: list[float]) -> float:
    """Largest gap between the program's and the reference's norm of a
    leaf, over the reference's norm of that leaf or of the median leaf,
    whichever is larger.  Leaves whose reference norm is under a
    thousandth of the median leaf's are left out (round-off alone)."""
    import numpy as np
    med = float(np.median(ref))
    return float(max(abs(p - r) / max(r, med) for p, r in zip(prog, ref)
                     if r >= 1e-3 * med))


def batch_mismatch(prog: list[dict], ref: list[dict]) -> float:
    """Share of the first rounds' token and label positions at which the
    batch the program's step took differs from the reference's own draw."""
    import numpy as np
    pairs = [(np.asarray(p[k]), np.asarray(r[k]))
             for p, r in zip(prog, ref) for k in ("tokens", "labels")]
    if any(a.shape != b.shape for a, b in pairs):
        return 1.0
    return float(sum(np.sum(a != b) for a, b in pairs)
                 / sum(a.size for a, _ in pairs))


def compare(prog: dict, ref: dict, mu: float) -> dict:
    """The numbers compared: the batches' mismatch, the loss gap over the
    first rounds, the worst-leaf gap of the first gradient (from the state
    after one round) and of the parameters' change after all of them."""
    return {
        "batch_mismatch": batch_mismatch(prog["batches"], ref["batches"]),
        "loss": max(abs(p - r) / abs(r)
                    for p, r in zip(prog["loss"], ref["loss"])),
        "grad_norm": worst_leaf_gap([x / mu for x in prog["d1"]],
                                    [x / mu for x in ref["d1"]]),
        "change_norm": worst_leaf_gap(prog["d3"], ref["d3"]),
    }


class Trainer:
    """The program as the window drives it, built once."""

    def __init__(self, cell, devices):
        import jax

        from repro.configs.base import GFLConfig
        from repro.data import TokenStream, federated_token_batches
        from repro.launch import steps as steps_lib
        from repro.launch.mesh import make_device_mesh
        from repro.models import Model

        self.cell, self.devices = cell, devices
        conf, tr = cell.config, cell.traffic
        self.ref = cell.reference()
        self.mesh = make_device_mesh(devices)
        self.P = len(devices)
        self.L, self.b, self.S = tr["clients"], tr["per_client"], tr["seq_len"]
        self.gcfg = GFLConfig(
            topology=conf["gfl"]["topology"], privacy=tr["privacy"],
            sigma_g=conf["gfl"]["sigma_g"], mu=conf["gfl"]["mu"],
            grad_bound=conf["gfl"]["grad_bound"], combine_impl=tr["combine"])
        self.steps_lib = steps_lib
        self.TokenStream = TokenStream
        self.batches_fn = federated_token_batches
        model = Model(_program_config(conf))
        self.init = functools.partial(self.ref.init_params, cfg=conf,
                                      P=self.P)
        with self.mesh:
            self.step = jax.jit(steps_lib.make_train_step(model, self.gcfg,
                                                          self.mesh))
            sds, self.shardings = steps_lib.params_specs(model, self.mesh,
                                                         gfl_train=True)
        mine = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        if (jax.tree.structure(mine) != jax.tree.structure(sds) or any(
                (a.shape, a.dtype) != (c.shape, c.dtype) for a, c in
                zip(jax.tree.leaves(mine), jax.tree.leaves(sds)))):
            raise RuntimeError("the program's parameter tree differs from "
                               "the one the reference builds")
        self.norms = jax.jit(_leaf_norms)

    def keys(self, seed: int):
        import jax
        key = bench.key_from_seed(seed)
        return jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)

    def batch(self, seed: int, i: int):
        return self.batches_fn(self.stream, seed=data_seed(seed), step=i,
                               P=self.P, L=self.L, per_client=self.b,
                               seq_len=self.S)

    def start(self, seed: int, acc=None):
        """State after the first rounds and the program's readings of them,
        with the batches (on the host) that its step took."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        self.stream = self.TokenStream(
            vocab=self.cell.config["vocab_size"], seed=data_seed(seed),
            bigram_frac=self.cell.traffic["bigram_frac"])
        wkey, skey = self.keys(seed)
        with self.mesh:
            params = jax.jit(self.init, out_shardings=self.shardings)(wkey)
            rep = NamedSharding(self.mesh, PartitionSpec())
            state = self.steps_lib.TrainState(
                params, jax.device_put(jnp.zeros((), jnp.int32), rep),
                jax.device_put(skey, rep))
            losses, batches = [], []
            for i in range(CHECK_ROUNDS):
                batch = self.batch(seed, i)
                batches.append(jax.device_get(batch))
                state, m = self.step(state, batch)
                if acc is not None:
                    acc.advance(1)
                losses.append(m["loss"])
                if i == 0:
                    d1 = self.norms(state.params, params)
            d3 = self.norms(state.params, params)
            losses, d1, d3 = jax.device_get((losses, d1, d3))
        prog = {"loss": [float(x) for x in losses],
                "d1": [float(x) for x in d1], "d3": [float(x) for x in d3],
                "batches": batches}
        return state, prog

    def reference(self, seed: int, control: bool = False) -> dict:
        """The plain reference's readings of the same first rounds, on the
        token rows it draws itself; with ``control``, the control's: the
        reference with float8 products, one precision below the
        configuration's bfloat16."""
        import jax
        tr = self.cell.traffic
        batches = [self.ref.draw_batches(
            data_seed(seed), i, self.P, self.L, self.b, self.S,
            self.cell.config["vocab_size"], tr["bigram_frac"])
            for i in range(CHECK_ROUNDS)]
        wkey, skey = self.keys(seed)
        R = self.ref.Reference(self.cell.config, self.cell.config["gfl"],
                               self.P, self.devices,
                               combine=self.cell.traffic["combine"],
                               matmul="fp8" if control else "f32")
        params0 = jax.jit(self.init)(wkey)
        losses, r1, r3 = R.run(params0, skey, batches)
        d1, d3 = jax.device_get((self.norms(r1, params0),
                                 self.norms(r3, params0)))
        return {"loss": list(losses), "d1": [float(x) for x in d1],
                "d3": [float(x) for x in d3], "batches": batches}


def data_seed(seed: int) -> int:
    """The token stream's seed: the run's seed cut to 32 bits."""
    return seed & 0xFFFFFFFF


def readings(d: dict) -> dict:
    """A reading without its batches, for printing."""
    return {k: v for k, v in d.items() if k != "batches"}


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

    spans = bench.Spans(annotate=trace)
    setup_s, compile_s = setup.total(), clock.seconds
    i, n, prev = CHECK_ROUNDS, 0, None
    with T.mesh, bench.Window(seconds, trace, clock, devices) as win:
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
            if win.over():
                break
        jax.block_until_ready(state)
    last_loss = float(prev)
    mem = bench.memory_peak_bytes(devices)
    del state, m, prev, batch
    gc.collect()

    t_ref = time.perf_counter()
    ref = T.reference(seed)
    gaps = compare(prog, ref, cell.config["gfl"]["mu"])
    bench.log("check_detail", {"program": readings(prog),
                               "reference": readings(ref),
                               "reference_s": time.perf_counter() - t_ref})
    finite = [np.isfinite(x) for x in prog["loss"] + [last_loss]]
    tokens = T.P * T.L * T.b * T.S
    return {
        "setup_s": setup_s, "compile_s": compile_s,
        "rounds": n, "window_s": win.window_s,
        "compiles_in_window": win.compiles,
        "cache_loads_in_window": win.cache_loads,
        "updates_per_round": T.P * T.L,
        "spans": spans.seconds, "trace": win.summary,
        "work": {"flops_per_round": counts.lm_train_flops_per_token(
            cell.config, T.S) * tokens},
        "memory_peak_bytes": mem,
        "checks": [(k, v, cell.limits[k]) for k, v in gaps.items()],
        "attempted": CHECK_ROUNDS + n,
        "failed": int(len(finite) - sum(finite)),
    }
