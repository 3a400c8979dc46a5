"""Training launcher: GFL training of any --arch on a mesh.

By default the mesh fits the devices present: one GFL server per device on
``data`` and ``model`` = 1, so the same command runs on one chip, on a
four-chip host, or on forced CPU devices.  ``--mesh production`` (16x16)
and ``production-multipod`` name the pod layouts the dry run compiles for;
``--mesh 2x4`` style specs pick an explicit (data x model) layout.

    python -m repro.launch.train --arch smollm-135m --steps 20 \
        --seq 1024 --clients 4 --privacy hybrid --combine dense

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --reduced --mesh 2x4 --steps 20

``main`` returns the final state, the per-step losses and set-up timings,
so a caller (``chip_smoke.py``) can check them.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import rng_key, use_compile_cache
from repro.checkpoint import save_checkpoint
from repro.configs.base import GFLConfig
from repro.configs.registry import get_config
from repro.core.privacy.mechanism import mechanism_for
from repro.data import TokenStream, federated_token_batches
from repro.launch import steps as steps_lib
from repro.launch.mesh import (make_device_mesh, make_production_mesh,
                               make_test_mesh, num_servers)
from repro.models import Model


def parse_mesh(spec: str):
    dims = tuple(int(x) for x in spec.split("x"))
    if len(dims) == 2:
        return make_test_mesh(dims, ("data", "model"))
    return make_test_mesh(dims, ("pod", "data", "model"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default="devices",
                    help="'devices' (one server per device, the default), "
                         "'production', 'production-multipod' or e.g. "
                         "'2x4'")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--clients", type=int, default=2)
    ap.add_argument("--per-client", type=int, default=2)
    ap.add_argument("--privacy", default="hybrid",
                    help="registered mechanism spec (see "
                         "repro.core.privacy.mechanism), e.g. hybrid, "
                         "gaussian_dp, scheduled:iid_dp")
    ap.add_argument("--sigma", type=float, default=0.01)
    ap.add_argument("--mu", type=float, default=0.1)
    ap.add_argument("--combine", default="sparse",
                    choices=["sparse", "rotate", "dense"])
    ap.add_argument("--fault", default="none",
                    help="resilience fault spec (docs/resilience.md), e.g. "
                         "links:0.1+dropout:0.2")
    ap.add_argument("--virtual-clients", type=int, default=0,
                    help="virtual population size K per server; 0 keeps the "
                         "positional --clients cohort.  With K > 0 a "
                         "CohortScheduler samples --clients ids per round "
                         "from the population (docs/population.md) and the "
                         "accountant reports subsampling-amplified epsilon")
    ap.add_argument("--cohort", default="uniform",
                    help="cohort-scheduler spec (docs/population.md), e.g. "
                         "uniform+trace:diurnal,period=24,min=0.2")
    ap.add_argument("--async", dest="async_spec", default="none",
                    help="event-driven executor spec (docs/async.md), e.g. "
                         "async:buffer=8,latency=lognorm:0.5,max_stale=4 — "
                         "drives staleness-weighted cohort weights and "
                         "per-server release accounting")
    ap.add_argument("--use-kernels", action="store_true",
                    help="route the round through the fused Pallas kernel "
                         "layer (docs/kernels.md): the dense combine runs "
                         "the fused graph-combine per leaf (interpret mode "
                         "on CPU)")
    ap.add_argument("--telemetry", default="off",
                    help="telemetry sink spec (docs/observability.md): "
                         "'off' (default, bit-identical), or a '+'-joined "
                         "jsonl[:path]|csv[:base]|memory|console[:every] "
                         "spec — per-step mesh metrics, the privacy "
                         "ledger stream and a Chrome trace JSON land in "
                         "the sinks (inspect with python -m "
                         "repro.telemetry.inspect)")
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)

    if args.mesh == "devices":
        mesh = make_device_mesh()
    elif args.mesh == "production":
        mesh = make_production_mesh()
    elif args.mesh == "production-multipod":
        mesh = make_production_mesh(multi_pod=True)
    else:
        mesh = parse_mesh(args.mesh)
    Pn = num_servers(mesh)
    print(f"mesh {dict(mesh.shape)} -> {Pn} GFL servers; arch {cfg.name}")

    gfl_cfg = GFLConfig(topology="ring", privacy=args.privacy,
                        sigma_g=args.sigma, mu=args.mu, grad_bound=10.0,
                        combine_impl=args.combine, fault=args.fault,
                        cohort=args.cohort, async_spec=args.async_spec,
                        use_kernels=args.use_kernels,
                        telemetry=args.telemetry)
    # mechanism-aware: the noise profile picks the curve (eps is inf for
    # a zero-noise config — the honest Theorem-2 answer)
    acc = mechanism_for(gfl_cfg).accountant()
    stream = TokenStream(vocab=cfg.vocab_size, seed=0)

    scheduler = None
    if args.virtual_clients <= 0 and args.cohort != "uniform":
        raise SystemExit(
            "--cohort only takes effect with --virtual-clients > 0 (the "
            "scheduler samples cohort ids from the virtual population); "
            "pass --virtual-clients or drop --cohort")
    if args.virtual_clients > 0:
        from repro.core.population import CohortScheduler, parse_cohort_spec
        sampler, floor, trace = parse_cohort_spec(args.cohort)
        if sampler == "importance":
            raise SystemExit(
                "--cohort importance needs per-client gradient-norm "
                "feedback, which the mesh step does not report; use the "
                "simulator engine (run_gfl_population) or a uniform "
                "sampler with a trace")
        # dropout realizations stay with the topology process below (same
        # stream constants either way — see CohortScheduler._rng)
        scheduler = CohortScheduler(
            args.virtual_clients, args.clients, Pn, sampler=sampler,
            floor=floor, trace=trace, seed=0)
        acc.sampling_rate = args.clients / args.virtual_clients
        print(f"virtual population: K={args.virtual_clients} per server, "
              f"cohort L={args.clients} ({args.cohort})")

    async_drv = async_acc = None
    from repro.core.events import AsyncCohortDriver, parse_async_spec
    from repro.core.population import parse_cohort_spec
    async_spec = parse_async_spec(args.async_spec)
    if async_spec is not None:
        k_pop = args.virtual_clients or args.clients
        # the event layer drives the mesh step's cohort-weight path:
        # per-server buffered release gating with staleness weights, and
        # the matching per-server release accounting (docs/async.md).
        # The availability trace is applied exactly once — a scheduler
        # already thins the cohort at sampling time, so the driver only
        # applies it when no scheduler is active (which the --cohort
        # guard above reduces to the always-on trace).
        trace = ("always" if scheduler is not None
                 else parse_cohort_spec(args.cohort)[2])
        async_drv = AsyncCohortDriver(async_spec, Pn, args.clients, k_pop,
                                      trace=trace, seed=0)
        async_acc = mechanism_for(gfl_cfg).async_accountant(Pn)
        print(f"async event layer: {async_spec.to_spec()} "
              f"(per-server buffered releases, staleness alpha="
              f"{async_spec.alpha:g})")

    process = (steps_lib.make_topology_process(mesh, gfl_cfg)
               if gfl_cfg.fault != "none" else None)
    from repro.telemetry import (emit, session_from_config,
                                 telemetry_active, trace_span)
    with session_from_config(gfl_cfg), mesh:
        with trace_span("train_setup", arch=cfg.name, servers=Pn):
            step = jax.jit(steps_lib.make_train_step(model, gfl_cfg, mesh))
            state = steps_lib.init_train_state(model, gfl_cfg, mesh,
                                               rng_key())
        t0 = time.time()
        losses, first_step_s, t1 = [], None, None
        # cohort selection stream stays decoupled from the model-init seed
        sel_key = rng_key(1234)
        for i in range(args.steps):
            ids = weights = None
            q_round = None
            if scheduler is not None:
                sel = scheduler.select(jax.random.fold_in(sel_key, i), i)
                ids, weights, q_round = sel.client_idx, sel.weights, sel.q
            if async_drv is not None:
                aw, flushed, q_srv = async_drv.step(i, ids)
                weights = aw if weights is None else weights * aw
            batch = federated_token_batches(
                stream, seed=0, step=i, P=Pn, L=args.clients,
                per_client=args.per_client, seq_len=args.seq,
                client_ids=ids)
            if process is not None:
                real = process.realize(i)
                alive = (process.client_alive(i, args.clients)
                         if process.fault.client_dropout > 0 else None)
                state, metrics = step(state, batch, real.A, alive,
                                      cohort_weights=weights)
                if real.gap != 0.0 and i % max(args.steps // 10, 1) == 0:
                    print(f"  round {i}: spectral gap {real.gap:.3f}")
            else:
                state, metrics = step(state, batch, cohort_weights=weights)
            losses.append(metrics["loss"])
            if i == 0:   # compile + one step; the rest is steady state
                jax.block_until_ready(state)
                t1 = time.time()
                first_step_s = t1 - t0
            # one ledger release per protocol round, charged at THIS
            # round's realized rate (a running mean would under-report the
            # spend whenever q varies round to round — f(q) is convex-ish
            # increasing, so per-release rates must be recorded as drawn).
            # Under --async a server only releases when its buffer fills:
            # its own ledger advances on its own cadence.
            if async_acc is not None:
                async_acc.record_round(flushed, q_srv)
                eps = async_acc.epsilon()
            else:
                eps = acc.advance(1, q=q_round)
            if telemetry_active():   # the loss sync is on-path only
                rec = {"step": i, "loss": float(metrics["loss"]),
                       "seconds": time.time() - t0}
                if process is not None:
                    rec["gap"] = process.realize(i).gap
                emit("mesh", rec)
                if "update_norm" in metrics:
                    emit("step", {
                        "step": i + 1,
                        "update_norm": float(metrics["update_norm"]),
                        "param_norm": float(metrics["param_norm"])})
            if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
                amp = (f" eps_amp {acc.amplified_epsilon():.2f} "
                       f"(q~{scheduler.realized_q:.3g})"
                       if scheduler is not None and async_acc is None
                       else "")
                if async_acc is not None:
                    rel = async_acc.releases
                    amp = (f" eps_amp {async_acc.amplified_epsilon():.2f} "
                           f"rel {min(rel)}-{max(rel)}")
                print(f"step {i:4d} loss {float(metrics['loss']):.4f} "
                      f"eps {eps:.1f}{amp} ({time.time()-t0:.0f}s)")
        jax.block_until_ready(state)
        step_s = ((time.time() - t1) / (args.steps - 1)
                  if args.steps > 1 else None)
    if args.checkpoint:
        save_checkpoint(args.checkpoint,
                        jax.tree.map(lambda x: x[0], state.params),
                        step=args.steps)
        print(f"saved consensus checkpoint to {args.checkpoint}")
    return {"state": state,
            "losses": np.asarray(jax.device_get(losses), np.float32),
            "first_step_s": first_step_s, "step_s": step_s}


if __name__ == "__main__":
    main()
