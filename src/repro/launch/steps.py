"""Mesh-scale GFL training step and serving steps.

TRAINING (the paper's protocol, eqs. 6-8, at datacenter scale)
  - every param leaf has a leading server dim P sharded over the data(+pod)
    mesh axes; within a server, weights are tensor-parallel over "model";
  - client updates (6): lax.scan over the L client microbatch groups of each
    server, per-client gradients clipped to the paper's bound B
    (Assumption 3), accumulated into the server mean;
  - server aggregation (7): secure-agg pairwise masks cancel EXACTLY in the
    mean (eq. 23), so the aggregate is computed directly; the mask mechanics
    are exercised bit-level by the Pallas kernel + simulator paths;
  - server combination (8): ring-rotation collective over the server axes
    (see `_rotate_combine`) with graph-homomorphic Laplace noise (eq. 24):
    the rotating buffer carries (psi_m + g_m) exactly as the wire protocol
    does, and each server subtracts its own g_p at the end.

  Combine implementations (GFLConfig.combine_impl):
    dense    einsum over a gathered [P, ...] stack — semantic baseline, only
             viable for small models;
    rotate   P-1 ring collective_permutes, O(1) extra memory, works for ANY
             combination matrix A (weights indexed per rotation step);
    sparse   neighbour-only permutes for ring/torus graphs — the beyond-paper
             optimized path (collective bytes ~ degree/P of rotate's).

SERVING: consensus-model prefill / decode, no GFL protocol (params
replicated over data axes, TP over "model"); decode caches sharded per
`sharding.cache_specs`.

Privacy noise (which distribution, which level, whether it cancels) is owned
by the PrivacyMechanism resolved from GFLConfig.privacy — this module only
asks the mechanism for client/combine noise pytrees and applies the
cancellation structure its noise_profile() declares.  Non-cancelling client
noise is applied as a single variance-equivalent draw (sigma/sqrt(L))
instead of L per-client draws: at 47B params, L materialized noise pytrees
would not fit HBM, and the MSE analysis only sees the mean.  (DESIGN.md §7.)
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import rng_key
from repro.configs.base import GFLConfig, InputShape, ModelConfig
from repro.core.privacy.mechanism import RoundContext, mechanism_for
from repro.core.topology import combination_matrix
from repro.launch import sharding as shd
from repro.launch.mesh import num_servers, server_axes
from repro.models import Model
from repro.optim.clip import clip_by_global_norm


class TrainState(NamedTuple):
    params: dict
    step: jax.Array
    key: jax.Array


# ---------------------------------------------------------------------------
# combine implementations
# ---------------------------------------------------------------------------


def _kernel_dense_combine(A, psi, g):
    """Dense combine routed through the fused graph-combine Pallas kernel
    (eq. 8 + 24): each leaf is flattened to [P, D] and streamed through
    :func:`repro.kernels.ops.graph_combine` — one HBM pass per leaf instead
    of the gather -> noise-add -> einsum -> subtract chain.  Only the
    cancelling (graph-homomorphic) noise structure maps onto the kernel;
    ``make_train_step`` falls back to the einsum for everything else."""
    from repro.kernels import ops as kops
    Pn = jax.tree_util.tree_leaves(psi)[0].shape[0]

    def mix(x, noise):
        flat = kops.graph_combine(
            A, x.reshape(Pn, -1),
            None if noise is None else noise.reshape(Pn, -1))
        return flat.reshape(x.shape).astype(x.dtype)

    if g is None:
        return jax.tree.map(lambda x: mix(x, None), psi)
    return jax.tree.map(mix, psi, g)


def _dense_combine(A, psi, g, cancel: bool = True):
    """einsum baseline: w_p = sum_m A[m,p] psi_m + (A^T g)_p [- g_p].

    `cancel` applies the graph-homomorphic self-subtraction (eq. 24); it is
    driven by the mechanism's ``noise_profile().server_cancels_exactly``.
    """
    def combine(x):   # f32 mix: the eq.-24 noise cancels to f32 rounding
        return jnp.einsum("mp,m...->p...", A.astype(jnp.float32),
                          x.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)

    def mix(x, noise):
        mixed = combine(x + noise)
        if cancel:
            mixed = mixed - noise.astype(jnp.float32)
        return mixed.astype(x.dtype)
    if g is None:
        return jax.tree.map(lambda x: combine(x).astype(x.dtype), psi)
    return jax.tree.map(mix, psi, g)


def _make_shardmap_combine(mesh, cfg: ModelConfig, gfl: GFLConfig,
                           params_like):
    """shard_map ring-rotation / sparse combine over the server axes.

    Works per-leaf: each device holds its server's model-parallel shard of
    psi_p (+ its own noise g_p); rotating collective_permutes bring every
    other server's (psi_m + g_m) past each device, which accumulates
    a_mp-weighted contributions.  For `sparse` + ring graphs only the two
    neighbour exchanges run.

    The combination matrix is a replicated runtime ARGUMENT of the returned
    callable (weights are gathered per rotation step), so per-round
    effective matrices from the resilience runtime slot straight in: a dead
    link is a zero-weight permute.
    """
    saxes = server_axes(mesh)
    Pn = num_servers(mesh)

    leaf_paths = [
        "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(params_like)[0]
    ]
    treedef = jax.tree_util.tree_structure(params_like)
    model_axis = None if gfl.client_parallel else "model"
    specs = jax.tree_util.tree_unflatten(treedef, [
        shd.param_spec(ps, cfg, stacked=True, server_axes=saxes,
                       model_axis=model_axis)
        for ps in leaf_paths
    ])

    def my_server_idx():
        if len(saxes) == 1:
            return jax.lax.axis_index(saxes[0])
        # pod-major flattening: idx = pod * data_size + data
        return (jax.lax.axis_index(saxes[0]) * mesh.shape[saxes[1]]
                + jax.lax.axis_index(saxes[1]))

    def ring_perm():
        return [((i + 1) % Pn, i) for i in range(Pn)]  # recv from right

    def _rotate_combine_leaf(x, Aj):
        """x: local shard with leading server dim of size 1 (this server's
        psi_p + g_p).  Returns sum_m a_mp (psi_m + g_m) for this p.

        combine_wire="bf16": an optimization_barrier after every permute
        pins the rotating buffer to the parameter dtype — otherwise XLA
        hoists the f32 accumulation convert above the whole permute chain
        and doubles every wire transfer (§Perf hillclimb 1)."""
        p = my_server_idx()
        # combine_wire="bf16": accumulate in the param dtype so the leaf fn
        # contains NO converts for XLA to hoist — the permute chain stays at
        # 2 bytes/elem on the wire.  (An optimization_barrier variant keeps
        # f32 accumulation on TPU, but the CPU backend deletes barriers and
        # upcasts the chain — measured in EXPERIMENTS.md §Perf iter 1.)
        # combine_wire="f32": f32 accumulation, XLA upcasts the wire.
        wt = x.dtype if gfl.combine_wire == "bf16" else jnp.float32
        buf = x
        acc = (Aj[p, p].astype(wt) * x.astype(wt))
        for step in range(1, Pn):
            buf = jax.lax.ppermute(buf, saxes if len(saxes) > 1 else saxes[0],
                                   ring_perm())
            src = jnp.mod(p + step, Pn)   # after s left-rotations
            acc = acc + Aj[src, p].astype(wt) * buf.astype(wt)
        return acc.astype(x.dtype)

    def combine_fn(noisy_psi, Aj):
        return jax.tree.map(lambda x: _rotate_combine_leaf(x, Aj), noisy_psi)

    return jax.shard_map(combine_fn, mesh=mesh, in_specs=(specs, P()),
                         out_specs=specs)


def _make_sparse_combine(mesh, cfg: ModelConfig, gfl: GFLConfig,
                         params_like):
    """Neighbour-only combine for ring (1 server axis) / torus (pod x data).

    Collective bytes per leaf: deg * shard (vs (P-1) * shard for rotate).
    Requires A to be the Metropolis ring (single axis) or the product graph
    A_pod (x) A_ring (multi-pod).  On a single server axis the weights are
    gathered from the runtime A argument (so per-round effective matrices
    work: a dead neighbour link is a zero weight); the multi-pod product
    path derives its factor weights statically and therefore only supports
    the static base graph (make_train_step enforces this).
    """
    saxes = server_axes(mesh)
    Pn = num_servers(mesh)

    leaf_paths = [
        "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(params_like)[0]
    ]
    treedef = jax.tree_util.tree_structure(params_like)
    model_axis = None if gfl.client_parallel else "model"
    specs = jax.tree_util.tree_unflatten(treedef, [
        shd.param_spec(ps, cfg, stacked=True, server_axes=saxes,
                       model_axis=model_axis)
        for ps in leaf_paths
    ])

    def _combine_leaf(x, Aj):
        wt = x.dtype if gfl.combine_wire == "bf16" else jnp.float32
        if len(saxes) == 1:
            ax = saxes[0]
            n = mesh.shape[ax]
            p = jax.lax.axis_index(ax)
            left = jax.lax.ppermute(
                x, ax, [((i + 1) % n, i) for i in range(n)])
            acc = (Aj[p, p].astype(wt) * x.astype(wt)
                   + Aj[jnp.mod(p + 1, n), p].astype(wt) * left.astype(wt))
            if n > 2:  # on a 2-ring left == right: don't double-count
                right = jax.lax.ppermute(
                    x, ax, [((i - 1) % n, i) for i in range(n)])
                acc = acc + Aj[jnp.mod(p - 1, n), p].astype(wt) \
                    * right.astype(wt)
            return acc.astype(x.dtype)
        # product graph: mix along data ring, then along pod ring
        pod_ax, data_ax = saxes
        nd = mesh.shape[data_ax]
        npod = mesh.shape[pod_ax]
        # data-ring Metropolis weights for a ring of size nd
        from repro.core.topology import combination_matrix as _cm
        Ad = jnp.asarray(_cm("ring", nd), jnp.float32)
        Ap = jnp.asarray(_cm("ring", npod) if npod > 2
                         else np.full((2, 2), 0.5), jnp.float32)
        pd = jax.lax.axis_index(data_ax)
        left = jax.lax.ppermute(
            x, data_ax, [((i + 1) % nd, i) for i in range(nd)])
        acc = (Ad[pd, pd].astype(wt) * x.astype(wt)
               + Ad[jnp.mod(pd + 1, nd), pd].astype(wt) * left.astype(wt))
        if nd > 2:   # on a 2-ring left == right: don't double-count
            right = jax.lax.ppermute(
                x, data_ax, [((i - 1) % nd, i) for i in range(nd)])
            acc = acc + Ad[jnp.mod(pd - 1, nd), pd].astype(wt) \
                * right.astype(wt)
        y = acc.astype(x.dtype)          # data-mixed value, BEFORE pod mix:
        pp = jax.lax.axis_index(pod_ax)  # both pod permutes must carry y
        fwd = jax.lax.ppermute(
            y, pod_ax, [((i + 1) % npod, i) for i in range(npod)])
        acc = (Ap[pp, pp].astype(wt) * y.astype(wt)
               + Ap[jnp.mod(pp + 1, npod), pp].astype(wt)
               * fwd.astype(wt))
        if npod > 2:
            bwd = jax.lax.ppermute(
                y, pod_ax, [((i - 1) % npod, i) for i in range(npod)])
            acc = acc + Ap[jnp.mod(pp - 1, npod), pp].astype(wt) \
                * bwd.astype(wt)
        return acc.astype(x.dtype)

    def combine_fn(noisy_psi, Aj):
        return jax.tree.map(lambda x: _combine_leaf(x, Aj), noisy_psi)

    return jax.shard_map(combine_fn, mesh=mesh, in_specs=(specs, P()),
                         out_specs=specs)


def make_combination_matrix(mesh, gfl: GFLConfig) -> np.ndarray:
    """A for the mesh's server count; multi-pod uses the product graph
    A_pod (x) A_data so sparse combine factorizes over the two axes."""
    saxes = server_axes(mesh)
    if len(saxes) == 1:
        return combination_matrix(gfl.topology, mesh.shape[saxes[0]],
                                  rows=gfl.torus_rows, seed=gfl.topology_seed)
    npod = mesh.shape[saxes[0]]
    nd = mesh.shape[saxes[1]]
    Ad = combination_matrix(gfl.topology if gfl.topology != "torus" else "ring",
                            nd, seed=gfl.topology_seed)
    Ap = np.full((npod, npod), 1.0 / npod) if npod <= 2 \
        else combination_matrix("ring", npod)
    return np.kron(Ap, Ad)


def make_topology_process(mesh, gfl: GFLConfig):
    """The mesh run's fault process: per-round effective A_i + client
    participation masks over the mesh's base graph (product graph on
    multi-pod meshes).  Feed its realizations to the train step:

        proc = make_topology_process(mesh, gfl_cfg)
        real = proc.realize(step_idx)
        alive = (proc.client_alive(step_idx, L)
                 if proc.fault.client_dropout > 0 else None)
        state, metrics = step(state, batch, real.A, alive)
    """
    from repro.core.resilience import TopologyProcess
    return TopologyProcess(make_combination_matrix(mesh, gfl), gfl.fault,
                           seed=gfl.topology_seed)


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------


def make_train_step(model: Model, gfl: GFLConfig, mesh,
                    clients: int = 4,
                    remat_policy: str | None = None) -> Callable:
    """Build the jit-able GFL train step.

    params leaves: [P_servers, ...]; batch leaves: [P_servers, L, b, ...].
    Returns (state, batch[, A, client_alive, cohort_weights]) -> (state,
    metrics).

    The trailing arguments are the resilience / population hooks (all
    optional; defaults reproduce the static path exactly): ``A`` overrides
    the base combination matrix with a per-round effective matrix from
    :func:`make_topology_process` (dead links become zero-weight entries /
    permutes), and ``client_alive`` ([P, L] mask) applies mid-round client
    dropout — the aggregate renormalizes over survivors, which is exactly
    the dropout-safe secure-agg semantics since the mesh computes the
    aggregate directly (masks cancel; see docs/resilience.md).

    ``cohort_weights`` ([P, L]) is the population engine's unbiased
    ``1/(K pi_k)`` cohort reweighting (docs/population.md): each client's
    gradient is scaled by its weight BEFORE the per-client clip (so the
    contribution stays inside the grad_bound sensitivity ball the privacy
    calibration assumes; heavy weights saturate) and before the server
    mean — a non-uniformly-sampled cohort (importance sampling,
    availability traces) estimates the population update without bias up
    to that clipping.  Like the resilience hooks it is a traced runtime
    argument — one compilation serves every round's cohort."""
    from repro.core.resilience import parse_fault_spec
    from repro.core.resilience.runtime import ensure_dropout_safe
    from repro.telemetry import telemetry_active, trace_span

    cfg = model.cfg
    with trace_span("make_combination_matrix", combine=gfl.combine_impl):
        A = make_combination_matrix(mesh, gfl)
    Pn = num_servers(mesh)
    Aj = jnp.asarray(A, jnp.float32)

    fault = parse_fault_spec(gfl.fault)
    if fault.straggler > 0:
        raise ValueError(
            "straggler faults are simulator-only for now (they need the "
            "per-server psi cache of repro.core.resilience.runtime); mesh "
            "fault specs support links/outage/dropout components")
    if (fault.perturbs_topology and gfl.combine_impl == "sparse"
            and len(server_axes(mesh)) > 1):
        raise ValueError(
            "sparse combine on a multi-pod mesh derives its product-graph "
            "weights statically and cannot apply per-round link faults; "
            "use combine_impl='rotate' (or 'dense') with fault specs")

    acc_dtype = jnp.dtype(gfl.grad_acc_dtype)

    def client_mean_grads(w_p, batch_p, alive_p=None, weights_p=None):
        """(6)+(7): scan over L clients; per-client clip to B; mean.

        ``alive_p`` ([L] 0/1, optional): dropped clients contribute nothing
        and the mean renormalizes over the survivor count.  ``weights_p``
        ([L], optional): cohort importance weights, applied BEFORE the
        per-client clip — the clipped contribution stays inside the
        grad_bound ball the privacy calibration assumes (heavy weights
        saturate instead of inflating sensitivity), and the mean stays
        over L — resp. the survivor count — so the 1/(K pi) estimator of
        docs/population.md is unbiased up to that clipping."""
        scaled = alive_p is not None or weights_p is not None

        def body(acc, xs):
            if scaled:
                client_batch, w, a = xs
            else:
                client_batch, w, a = xs, None, None
            (loss, aux), grads = jax.value_and_grad(model.loss, has_aux=True)(
                w_p, client_batch, remat_policy=remat_policy)
            if w is not None:
                grads = jax.tree.map(
                    lambda g: g * w.astype(g.dtype), grads)
            if gfl.grad_bound > 0:
                grads, _ = clip_by_global_norm(grads, gfl.grad_bound)
            if a is None:
                acc = jax.tree.map(
                    lambda c, g: c + g.astype(acc_dtype), acc, grads)
            else:
                acc = jax.tree.map(
                    lambda c, g: c + g.astype(acc_dtype) * a.astype(acc_dtype),
                    acc, grads)
                loss = loss * a
            return acc, loss

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, acc_dtype), w_p)
        L = jax.tree_util.tree_leaves(batch_p)[0].shape[0]
        if scaled:
            a = jnp.ones((L,)) if alive_p is None else alive_p
            w = jnp.ones((L,)) if weights_p is None else weights_p
            xs = (batch_p, w, a)
        else:
            xs = batch_p
        acc, losses = jax.lax.scan(body, zeros, xs)
        if alive_p is None:
            mean_g = jax.tree.map(lambda c: (c / L).astype(jnp.float32), acc)
            return mean_g, losses.mean()
        n = jnp.maximum(alive_p.sum(), 1.0).astype(acc_dtype)
        mean_g = jax.tree.map(lambda c: (c / n).astype(jnp.float32), acc)
        return mean_g, losses.sum() / n.astype(losses.dtype)

    def client_parallel_grads(params, batch, alive=None, weights=None):
        """Small-model mode (§Perf hillclimb 3): ALL (server, client) grads
        computed concurrently — the L client dim is sharded over the
        "model" axis (params are replicated over it), turning the idle TP
        ranks of a too-small model into data parallelism.  Per-client
        clipping (Assumption 3) is preserved."""
        saxes_ = server_axes(mesh)
        da = saxes_ if len(saxes_) > 1 else saxes_[0]

        def one_client(w_p, client_batch):
            (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(
                w_p, client_batch, remat_policy=remat_policy)
            return grads, loss

        grads, losses = jax.vmap(lambda w_p, batch_p: jax.vmap(
            lambda cb: one_client(w_p, cb))(batch_p))(params, batch)
        # pin [P, L, ...] grads: P -> data axes, L -> model axis
        grads = jax.lax.with_sharding_constraint(
            grads, jax.tree.map(
                lambda g: NamedSharding(mesh, P(da, "model")), grads))
        if weights is not None:
            # cohort weights scale BEFORE the per-client clip (sensitivity
            # stays inside grad_bound — same ordering as client_mean_grads)
            wf = weights.astype(jnp.float32)
            grads = jax.tree.map(
                lambda g: g * wf.reshape(wf.shape + (1,) * (g.ndim - 2)
                                         ).astype(g.dtype), grads)
        if gfl.grad_bound > 0:
            # per-(server, client) global-norm clip over the param tree
            sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)),
                             axis=tuple(range(2, g.ndim)))
                     for g in jax.tree.leaves(grads))          # [P, L]
            coef = jnp.minimum(1.0, gfl.grad_bound
                               / jnp.maximum(jnp.sqrt(sq), 1e-12))
            grads = jax.tree.map(
                lambda g: (g * coef.reshape(coef.shape + (1,) * (g.ndim - 2))
                           .astype(g.dtype)), grads)
        if alive is None and weights is None:
            mean_g = jax.tree.map(
                lambda g: jnp.mean(g.astype(jnp.float32), axis=1), grads)
            return mean_g, losses.mean(axis=1)
        a = (jnp.ones(losses.shape, jnp.float32) if alive is None
             else alive.astype(jnp.float32))                  # [P, L]
        n = jnp.maximum(a.sum(axis=1), 1.0)                   # [P]
        mean_g = jax.tree.map(
            lambda g: (g.astype(jnp.float32)
                       * a.reshape(a.shape + (1,) * (g.ndim - 2))
                       ).sum(axis=1) / n.reshape((-1,) + (1,) * (g.ndim - 2)),
            grads)
        return mean_g, (losses * a).sum(axis=1) / n

    mech = mechanism_for(gfl)
    profile = mech.noise_profile()
    if fault.client_dropout > 0:
        ensure_dropout_safe(profile, where="mesh client dropout")

    def step_fn(state: TrainState, batch, A_round=None, client_alive=None,
                cohort_weights=None):
        key, k_noise, k_client = jax.random.split(state.key, 3)
        ctx = RoundContext(step=state.step)
        A_rt = Aj if A_round is None else jnp.asarray(A_round, jnp.float32)
        # the survivor-weighted / cohort-weighted mean is a DIFFERENT XLA
        # program (different fusion, ~1-ulp drift), so each is only traced
        # in when actually used — this keeps the zero-probability
        # resilience path and the uniform-cohort path bit-identical to the
        # static path
        alive = (None if client_alive is None or fault.client_dropout == 0
                 else jnp.asarray(client_alive, jnp.float32))
        weights = (None if cohort_weights is None
                   else jnp.asarray(cohort_weights, jnp.float32))

        # (6)+(7) per server, vmapped over the sharded server dim
        if gfl.client_parallel:
            mean_g, loss = client_parallel_grads(state.params, batch, alive,
                                                 weights)
        elif alive is None and weights is None:
            mean_g, loss = jax.vmap(client_mean_grads)(state.params, batch)
        elif weights is None:
            mean_g, loss = jax.vmap(client_mean_grads)(state.params, batch,
                                                       alive)
        elif alive is None:
            mean_g, loss = jax.vmap(
                lambda w_p, b_p, s_p: client_mean_grads(w_p, b_p, None, s_p)
            )(state.params, batch, weights)
        else:
            mean_g, loss = jax.vmap(client_mean_grads)(state.params, batch,
                                                       alive, weights)
        psi = jax.tree.map(
            lambda w, g: (w.astype(jnp.float32)
                          - gfl.mu * g).astype(w.dtype),
            state.params, mean_g)

        # client-level residual noise (mechanisms whose masks cancel
        # exactly return None; iid returns the variance-equivalent draw —
        # the O(mu^{-1}) term of Theorem 1).  Under dropout each server's
        # draw scales with ITS realized survivor count ([P] vector),
        # keeping the per-server 1/sqrt(L'_p) variance equivalence honest.
        if profile.client_sigma > 0:
            L = jax.tree_util.tree_leaves(batch)[0].shape[1]
            L_eff = (L if alive is None
                     else jnp.maximum(alive.sum(axis=1), 1.0))
            cg = mech.client_noise_tree(k_client, psi, L_eff, ctx)
            if cg is not None:
                psi = jax.tree.map(lambda x, n: x + n, psi, cg)

        # (8) with the mechanism's server-level noise
        g = (mech.combine_noise_tree(k_noise, psi, ctx)
             if profile.server_sigma > 0 else None)
        cancel = profile.server_cancels_exactly

        if gfl.combine_impl == "dense":
            # whole-run kernel switch: the cancelling noise structure maps
            # onto the fused Pallas combine (docs/kernels.md); iid (non-
            # cancelling) noise keeps the einsum's [P, P, D] edge draws
            if gfl.use_kernels and (g is None or cancel):
                new_params = _kernel_dense_combine(A_rt, psi, g)
            else:
                new_params = _dense_combine(A_rt, psi, g, cancel=cancel)
        else:
            maker = (_make_sparse_combine if gfl.combine_impl == "sparse"
                     else _make_shardmap_combine)
            combine = maker(mesh, cfg, gfl, state.params)
            if g is not None:
                # the rotating buffer carries (psi_m + g_m) exactly as the
                # wire protocol does; cancelling mechanisms subtract their
                # own g_p afterwards (eq. 24)
                noisy = jax.tree.map(lambda x, n: x + n, psi, g)
                mixed = combine(noisy, A_rt)
                if cancel:
                    new_params = jax.tree.map(
                        lambda m, n: (m.astype(jnp.float32)
                                      - n.astype(jnp.float32)).astype(m.dtype),
                        mixed, g)
                else:
                    new_params = mixed
            else:
                new_params = combine(psi, A_rt)

        metrics = {"loss": loss.mean(), "step": state.step}
        # read-only telemetry tap: the norm reductions are only traced in
        # when a session is active at build time — the step closure is
        # rebuilt per make_train_step call, so the off path compiles the
        # exact program it does today.  No io_callback here (callback
        # operands would fight SPMD sharding propagation on real meshes);
        # the launcher emits these host-side from the metrics dict.
        if telemetry_active():
            sq_upd = sq_par = jnp.zeros((), jnp.float32)
            for n, o in zip(jax.tree_util.tree_leaves(new_params),
                            jax.tree_util.tree_leaves(state.params)):
                d = n.astype(jnp.float32) - o.astype(jnp.float32)
                sq_upd = sq_upd + jnp.sum(d * d)
                sq_par = sq_par + jnp.sum(
                    n.astype(jnp.float32) * n.astype(jnp.float32))
            metrics["update_norm"] = jnp.sqrt(sq_upd)
            metrics["param_norm"] = jnp.sqrt(sq_par)
        return TrainState(new_params, state.step + 1, key), metrics

    return step_fn


def init_train_state(model: Model, gfl: GFLConfig, mesh, key) -> TrainState:
    """Per-server replicated init (all servers start from the same point).

    The P server copies are built in place with the train step's own
    parameter shardings, so each device holds only its servers' shards."""
    Pn = num_servers(mesh)
    _, shardings = params_specs(model, mesh, gfl_train=True,
                                client_parallel=gfl.client_parallel)

    def init(k):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (Pn,) + x.shape),
            model.init(k))

    params = jax.jit(init, out_shardings=shardings)(key)
    rep = NamedSharding(mesh, P())
    return TrainState(params, jax.device_put(jnp.zeros((), jnp.int32), rep),
                      jax.device_put(key, rep))


# ---------------------------------------------------------------------------
# serve steps
# ---------------------------------------------------------------------------


def make_prefill_step(model: Model) -> Callable:
    def prefill(params, batch):
        return model.prefill(params, batch)
    return prefill


def make_decode_step(model: Model) -> Callable:
    def decode(params, tokens, cache):
        return model.decode_step(params, tokens, cache)
    return decode


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs for AOT lowering; no allocation)
# ---------------------------------------------------------------------------


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def sanitize_spec(shape: tuple, spec: P, mesh) -> P:
    """Drop mesh axes from dims they don't divide evenly (e.g. phi3's
    2047-slot sliding-window ring cache can't be 16-way sequence-sharded).
    Explicit out_shardings require divisibility; replication is the safe
    fallback for such (always small) dims."""
    parts = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * len(shape)):
        if entry is None:
            parts.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        parts.append(entry if dim % size == 0 else None)
    return P(*parts)


def train_batch_shape(cfg: ModelConfig, shape: InputShape, n_servers: int,
                      clients: int = 4):
    """Leading dims [P, L, b] for the GFL batch."""
    per_server = shape.global_batch // n_servers
    L = min(clients, per_server)
    b = per_server // L
    return L, b


def input_specs(model: Model, shape: InputShape, mesh, *,
                gfl: GFLConfig | None = None, clients: int = 4) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this shape."""
    cfg = model.cfg
    S = shape.seq_len
    saxes = server_axes(mesh)

    def ns(spec):
        return NamedSharding(mesh, spec)

    if shape.kind == "train":
        Pn = num_servers(mesh)
        L, b = train_batch_shape(cfg, shape, Pn, clients)
        bspecs = shd.batch_specs(
            cfg, mesh, kind="train", gfl_train=True,
            client_parallel=bool(gfl and gfl.client_parallel))
        S_text = S - cfg.num_image_tokens if cfg.family == "vlm" else S
        batch = {
            "tokens": _sds((Pn, L, b, S_text), jnp.int32,
                           ns(bspecs["tokens"])),
            "labels": _sds((Pn, L, b, S_text), jnp.int32,
                           ns(bspecs["labels"])),
        }
        if cfg.family == "vlm":
            batch["image_embeds"] = _sds(
                (Pn, L, b, cfg.num_image_tokens, cfg.d_model),
                jnp.dtype(cfg.param_dtype), ns(bspecs["image_embeds"]))
        if cfg.family == "audio":
            batch["frames"] = _sds(
                (Pn, L, b, cfg.encoder_seq_len, cfg.d_model),
                jnp.dtype(cfg.param_dtype), ns(bspecs["frames"]))
        return batch

    B = shape.global_batch
    bspecs = shd.batch_specs(cfg, mesh, kind=shape.kind)
    if shape.kind == "prefill":
        S_text = S - cfg.num_image_tokens if cfg.family == "vlm" else S
        batch = {"tokens": _sds((B, S_text), jnp.int32, ns(bspecs["tokens"]))}
        if cfg.family == "vlm":
            batch["image_embeds"] = _sds(
                (B, cfg.num_image_tokens, cfg.d_model),
                jnp.dtype(cfg.param_dtype), ns(bspecs["image_embeds"]))
        if cfg.family == "audio":
            batch["frames"] = _sds((B, cfg.encoder_seq_len, cfg.d_model),
                                   jnp.dtype(cfg.param_dtype),
                                   ns(bspecs["frames"]))
        return batch

    # decode: tokens [B] + cache of S tokens
    shard_seq = B == 1
    cache = jax.eval_shape(lambda: model.init_cache(B, S))
    cspecs = shd.cache_specs(cfg, mesh, shard_seq=shard_seq)
    cache = {k: _sds(v.shape, v.dtype,
                     ns(sanitize_spec(v.shape, cspecs[k], mesh)))
             for k, v in cache.items()}
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    da = data_axes if len(data_axes) > 1 else data_axes[0]
    tok_spec = P(None) if B == 1 else P(da)
    return {
        "tokens": _sds((B,), jnp.int32, ns(tok_spec)),
        "cache": cache,
    }


def params_specs(model: Model, mesh, *, gfl_train: bool,
                 client_parallel: bool = False) -> tuple:
    """(ShapeDtypeStruct pytree, NamedSharding pytree) for the params."""
    cfg = model.cfg
    saxes = server_axes(mesh) if gfl_train else None
    shapes = jax.eval_shape(lambda k: model.init(k), rng_key())
    if gfl_train:
        Pn = num_servers(mesh)
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((Pn,) + s.shape, s.dtype), shapes)
    shardings = shd.params_shardings(
        shapes, cfg, mesh, server_axes=saxes,
        model_axis=None if client_parallel else "model")
    sds = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), shapes, shardings)
    return sds, shardings
