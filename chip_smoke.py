#!/usr/bin/env python3
"""Chip smoke test: drive the system's main paths once on a TPU and check them.

    python chip_smoke.py              # one chip: phases (a) and (b)
    python chip_smoke.py --four-chip  # four chips: phase (c) only

(a) The paper's engine with the fused kernels: ``run_gfl_population``
    (scan executor, ``use_kernels=True``) on a ``SyntheticPopulation`` with
    P=16 servers, K=1000 clients each, L=32 sampled, batch 10, M=16384
    features, ring topology, in each fold mode of ``round_fold``: privacy
    ``none``, ``hybrid`` (in-kernel masks) and ``iid_dp`` (Laplace).  The
    kernels must run compiled (``tpu_custom_call`` in the round program,
    no interpret mode, no ``ref`` backend) and match the same run under
    the ``ref`` backend.  For hybrid the network centroid must be
    noise-free.
(b) The transformer trainer at published width: ``launch.train.main`` on
    smollm-135m (30 layers, d576, vocab 49152), one server on the chip,
    hybrid privacy, dense combine, seq 1024, 4 clients, with and without
    ``--use-kernels``.  Losses must be finite and the two runs must agree.
(c) Four chips: the phase (b) model with one server per chip, sparse
    combine against rotate and dense.  Centroids must agree and each chip
    must hold only its own server's shard.

Every phase runs; any failure is printed and makes the script exit 1.  It
exits 1 at once when JAX finds no TPU, and 2 outside a checkout of the
repository.  The last line of a passing run is one JSON object naming the
device.  Weights and data are random, made from fixed seeds.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# phase (a): the engine at deployment scale on one chip
POP = dict(P=16, K=1000, L=32, batch=10, M=16384, iters=36)
FOLD_MODES = (("none", "none"), ("hybrid", "mask"), ("iid_dp", "laplace"))
# final-parameter and MSD agreement of the Pallas run with the ref run,
# relative to the ref run's largest magnitude.  Both mix the servers at
# f32 (precision HIGHEST): at the TPU's default precision the ref's matmul
# rounds to bf16 and drifted 8% from the kernel over 36 rounds.  The
# gradient matmuls stay at the default precision, the same in both runs.
REF_RTOL = 1e-4
# hybrid: centroid gap to the noise-free run, relative to the per-server
# noise that the same round adds (f32 cancellation leaves ~1e-6)
CENTROID_RTOL = 1e-4

# phases (b) and (c): the trainer at published width
TRAIN_ARGS = ["--arch", "smollm-135m", "--privacy", "hybrid", "--seq", "1024",
              "--clients", "4", "--per-client", "2", "--steps", "4"]
LOSS_ATOL = 1e-2        # bf16 params: the combines differ by rounding only
CENTROID_ATOL = 5e-3    # as tests/test_dryrun.py asserts on CPU


class CompileClock:
    """Sums JAX's own trace, lowering and backend-compile durations."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


@contextlib.contextmanager
def kernel_backend(name: str):
    """Run the engines' kernel calls on ``name`` ("pallas" or "ref").

    The ops wrappers read ``REPRO_KERNEL_BACKEND`` at trace time and their
    jit caches do not key on it, so the caches are cleared on each switch.
    """
    import jax
    old = os.environ.get("REPRO_KERNEL_BACKEND")
    os.environ["REPRO_KERNEL_BACKEND"] = name
    jax.clear_caches()
    try:
        yield
    finally:
        if old is None:
            del os.environ["REPRO_KERNEL_BACKEND"]
        else:
            os.environ["REPRO_KERNEL_BACKEND"] = old
        jax.clear_caches()


def _rel_gap(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def phase_fused_round(clock: CompileClock, P, K, L, batch, M, iters,
                      expect=("pallas", False)) -> None:
    """(a): the population engine's scan path with the fused kernels."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import GFLConfig
    from repro.core import gfl
    from repro.core.population import SyntheticPopulation, run_gfl_population
    from repro.core.population.engine import uniform_cohort_batch
    from repro.core.simulate import base_combination_matrix, make_grad_fn
    from repro.kernels import ops

    pop = SyntheticPopulation(P, K, mode="hetero", M=M, data_seed=0)
    # the engine's Monte-Carlo minimizer takes minutes at this M; parity
    # needs a fixed reference point only, so "MSD" here is |w_c|^2
    w_ref = jnp.zeros((M,), jnp.float32)
    print(f"[a] population P={P} K={K} L={L} batch={batch} M={M}; "
          f"MSD against w_ref = 0")
    seen = set()
    resolve = ops._resolve

    def spy(backend, interpret):
        got = resolve(backend, interpret)
        seen.add(got)
        return got

    def cfg_for(privacy, use_kernels=True):
        return GFLConfig(num_servers=P, clients_per_server=K,
                         clients_sampled=L, topology="ring", privacy=privacy,
                         use_kernels=use_kernels)

    def run(privacy, n, use_kernels=True):
        t = time.time()
        res = run_gfl_population(pop, cfg_for(privacy, use_kernels),
                                 iters=n, batch_size=batch, seed=0,
                                 w_ref=w_ref, scan=True)
        params = np.asarray(jax.device_get(res.params), np.float64)
        return res, params, time.time() - t

    failures = []
    centroid = {}
    for privacy, mode in FOLD_MODES:
        cfg = cfg_for(privacy)
        seen.clear()
        ops._resolve = spy
        try:
            clock.lap()
            res_k, p_k, secs = run(privacy, iters)
            # the round program as the scan body runs it, compiled alone
            A = jnp.asarray(base_combination_matrix(cfg, P), jnp.float32)
            grad_fn = make_grad_fn(pop.rho)

            def one_round(params, key, kb):
                cohort = uniform_cohort_batch(kb, pop, L, batch)
                return gfl.gfl_round(params, cohort, key, A=A,
                                     grad_fn=grad_fn, cfg=cfg)

            key = jax.random.PRNGKey(0)
            hlo = jax.jit(one_round).lower(
                jnp.zeros((P, M), jnp.float32), key, key).compile().as_text()
        finally:
            ops._resolve = resolve
        blocks = {k[0]: v for k, v in ops._AUTOTUNE_CACHE.items()
                  if k[2] == expect[1]}
        print(f"[a] {privacy:7s} mode={mode:7s} pallas run {secs:.2f}s "
              f"(compile {clock.lap():.2f}s); resolve={sorted(seen)}; "
              f"tpu_custom_call x{hlo.count('tpu_custom_call')}; "
              f"block_d={blocks}")
        if seen != {expect}:
            failures.append(f"{privacy}: kernels resolved to {sorted(seen)}, "
                            f"expected {expect}")
        if not expect[1] and "tpu_custom_call" not in hlo:
            failures.append(f"{privacy}: no tpu_custom_call in the round")
        if not np.all(np.isfinite(p_k)) or not np.all(np.isfinite(res_k.msd)):
            failures.append(f"{privacy}: non-finite parameters or MSD")

        with kernel_backend("ref"):
            res_r, p_r, secs_r = run(privacy, iters)
        gap_p = _rel_gap(p_k, p_r)
        gap_m = _rel_gap(res_k.msd, res_r.msd)
        print(f"[a] {privacy:7s} ref run {secs_r:.2f}s; final MSD pallas "
              f"{float(res_k.msd[-1])!r} ref {float(res_r.msd[-1])!r}; "
              f"rel gap params {gap_p!r} msd {gap_m!r} (tol {REF_RTOL})")
        if not (gap_p <= REF_RTOL and gap_m <= REF_RTOL):
            failures.append(f"{privacy}: pallas vs ref gap params {gap_p} "
                            f"msd {gap_m} > {REF_RTOL}")
        # one round from the same start and cohort: the privacy invariant
        _, p1, _ = run(privacy, 1)
        centroid[privacy] = p1

    # the jnp path (use_kernels=False) must keep the invariant too
    centroid["hybrid jnp"] = run("hybrid", 1, use_kernels=False)[1]
    base = centroid["none"]
    for privacy in ("hybrid", "hybrid jnp", "iid_dp"):
        p1 = centroid[privacy]
        c_gap = float(np.max(np.abs(p1.mean(0) - base.mean(0))))
        s_gap = float(np.max(np.abs(p1 - base)))
        print(f"[a] {privacy:10s} one round vs none: centroid gap "
              f"{c_gap!r}, per-server gap {s_gap!r}, ratio {c_gap / s_gap!r}")
        if privacy != "iid_dp" and not c_gap <= CENTROID_RTOL * s_gap:
            failures.append(f"{privacy} centroid not noise-free: {c_gap} > "
                            f"{CENTROID_RTOL} x {s_gap}")
        if privacy == "iid_dp" and not c_gap > CENTROID_RTOL * s_gap:
            failures.append(f"iid_dp centroid unexpectedly noise-free: "
                            f"{c_gap} vs {s_gap}")
    if failures:
        raise AssertionError("; ".join(failures))


def _train(clock: CompileClock, argv: list) -> dict:
    import jax
    from repro.launch import train
    clock.lap()
    out = train.main(argv)
    out["compile_s"] = clock.lap()
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes"] = stats.get("peak_bytes_in_use")
    return out


def _report(tag: str, name: str, out: dict) -> None:
    print(f"[{tag}] {name}: losses {out['losses'].tolist()}; compile "
          f"{out['compile_s']:.2f}s; first step {out['first_step_s']:.2f}s; "
          f"step {out['step_s']!r}s after warm-up; peak_bytes_in_use "
          f"{out['peak_bytes']} (device 0, whole process)")


def phase_trainer(clock: CompileClock, train_args=TRAIN_ARGS) -> None:
    """(b): smollm-135m at published width, with and without the kernel."""
    import numpy as np
    runs = {}
    for name, extra in (("einsum", []), ("kernels", ["--use-kernels"])):
        runs[name] = out = _train(clock, train_args + ["--combine", "dense"]
                                  + extra)
        _report("b", name, out)
    failures = []
    for name, out in runs.items():
        if not np.all(np.isfinite(out["losses"])):
            failures.append(f"{name}: non-finite loss {out['losses']}")
    gap = float(np.max(np.abs(runs["kernels"]["losses"]
                              - runs["einsum"]["losses"])))
    print(f"[b] loss gap kernels vs einsum {gap!r} (tol {LOSS_ATOL})")
    if not gap <= LOSS_ATOL:
        failures.append(f"loss gap {gap} > {LOSS_ATOL}")
    if failures:
        raise AssertionError("; ".join(failures))


def phase_four_chip(clock: CompileClock, train_args=TRAIN_ARGS) -> None:
    """(c): one server per chip; sparse against rotate and dense."""
    import jax
    import numpy as np
    n = len(jax.devices())
    runs = {}
    failures = []
    for impl in ("sparse", "rotate", "dense"):
        out = _train(clock, train_args + ["--combine", impl])
        _report("c", impl, out)
        leaves = jax.tree.leaves(out["state"].params)
        for leaf in leaves:
            rows = sorted((s.index[0].start or 0, s.device.id)
                          for s in leaf.addressable_shards)
            if (leaf.shape[0] != n
                    or any(s.data.shape[0] != 1
                           for s in leaf.addressable_shards)
                    or [r for r, _ in rows] != list(range(n))
                    or len({d for _, d in rows}) != n):
                failures.append(f"{impl}: leaf {leaf.shape} is not one "
                                f"server per device: {rows}")
                break
        table = out["state"].params["embed"]["table"]
        runs[impl] = (out["losses"],
                      np.asarray(jax.device_get(table), np.float32).mean(0))
        if not np.all(np.isfinite(out["losses"])):
            failures.append(f"{impl}: non-finite loss {out['losses']}")
    print(f"[c] shards: every leaf [P={n}, ...] holds one server per device")
    for impl in ("rotate", "dense"):
        gap = float(np.max(np.abs(runs[impl][1] - runs["sparse"][1])))
        print(f"[c] centroid gap {impl} vs sparse {gap!r} "
              f"(tol {CENTROID_ATOL})")
        if not gap <= CENTROID_ATOL:
            failures.append(f"{impl} centroid gap {gap} > {CENTROID_ATOL}")
    if failures:
        raise AssertionError("; ".join(failures))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only phase (c), on four chips")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repository beside {__file__} (src/repro "
              f"missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if os.environ.get("REPRO_KERNEL_BACKEND", "pallas") != "pallas":
        print("chip_smoke: REPRO_KERNEL_BACKEND must be unset or 'pallas'",
              file=sys.stderr)
        return 1
    if args.four_chip and len(devices) != 4:
        print(f"chip_smoke: --four-chip needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from repro import use_compile_cache
    print(f"device {dev.device_kind} x{len(devices)}; jax {jax.__version__}; "
          f"compile cache {use_compile_cache()}")
    clock = CompileClock()
    if args.four_chip:
        phases = [("c four-chip combines", phase_four_chip)]
    else:
        phases = [("a fused round", lambda c: phase_fused_round(c, **POP)),
                  ("b trainer", phase_trainer)]
    failed = []
    for name, fn in phases:
        t = time.time()
        try:
            fn(clock)
        except Exception:  # noqa: BLE001 - reported, and the run fails
            traceback.print_exc()
            failed.append(name)
            print(f"phase {name}: FAILED after {time.time() - t:.1f}s")
        else:
            print(f"phase {name}: passed in {time.time() - t:.1f}s")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
