"""Fused round-fold kernel + whole-run ``use_kernels`` switch: parity of the
Pallas backend against the ref-jnp backend across mechanisms x dtypes x
padding edges, engine-level parity (``run_gfl`` / ``run_gfl_population`` /
``run_gfl_async``) of ``use_kernels=True`` vs ``False``, the sync-limit
bit-identity through the events engine under kernels, the block-size /
padding regression for odd D, and the flat-in-L secure-agg compile time."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import GFLConfig
from repro.core.simulate import generate_problem, run_gfl
from repro.core.topology import combination_matrix
from repro.kernels import ops, ref

_TOL = {jnp.float32: 3e-5, jnp.bfloat16: 3e-2}


def _inputs(P, L, D, dtype, key=0, per_client_base=False):
    k = jax.random.PRNGKey(key)
    w_shape = (P, L, D) if per_client_base else (P, D)
    w = jax.random.normal(k, w_shape).astype(dtype)
    grads = (jax.random.normal(jax.random.fold_in(k, 1), (P, L, D)) * 3
             ).astype(dtype)
    pre = jax.random.uniform(jax.random.fold_in(k, 2), (P, L),
                             minval=0.3, maxval=2.0)
    fold = jax.random.uniform(jax.random.fold_in(k, 3), (P, L))
    noise = (jax.random.normal(jax.random.fold_in(k, 4), (P, L, D)) * 0.3
             ).astype(dtype)
    seeds = (jnp.arange(P, dtype=jnp.uint32) * 31 + 7)
    return w, grads, pre, fold, noise, seeds


# ------------------------------------------------------- kernel-level parity


@pytest.mark.parametrize("mode", ["none", "mask", "laplace"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("P,L,D", [
    (8, 8, 512),     # aligned everywhere
    (3, 5, 130),     # P % 8 != 0, L % 8 != 0, D % 128 != 0
    (10, 7, 509),    # odd/prime D (the old _block_for pathology)
])
def test_round_fold_backend_parity(mode, dtype, P, L, D):
    w, grads, pre, fold, noise, seeds = _inputs(P, L, D, dtype)
    kw = dict(mu=0.1, bound=2.0, pre_w=pre, fold_w=fold, mode=mode,
              sigma=0.5, seeds=seeds if mode == "mask" else None,
              noise=noise if mode == "laplace" else None)
    psi_p, sq_p = ops.round_fold(w, grads, **kw)
    psi_r, sq_r = ops.round_fold(w, grads, backend="ref", **kw)
    tol = _TOL[dtype]
    np.testing.assert_allclose(np.asarray(psi_p, np.float32),
                               np.asarray(psi_r, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(sq_p), np.asarray(sq_r),
                               rtol=1e-3)


def test_round_fold_per_client_base():
    """Per-client stale bases [P, L, D] (the event engine's snapshots)."""
    w, grads, pre, fold, _, _ = _inputs(4, 6, 257, jnp.float32,
                                        per_client_base=True)
    a, _ = ops.round_fold(w, grads, mu=0.1, bound=1.5, pre_w=pre,
                          fold_w=fold)
    b, _ = ops.round_fold(w, grads, mu=0.1, bound=1.5, pre_w=pre,
                          fold_w=fold, backend="ref")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_round_fold_matches_unfused_semantics():
    """The fold equals the hand-written clip -> update -> weighted fold."""
    from repro.core.gfl import clip_to_bound
    P, L, D = 3, 4, 64
    w, grads, pre, fold, _, _ = _inputs(P, L, D, jnp.float32)
    psi, sq = ops.round_fold(w, grads, mu=0.2, bound=1.0, pre_w=pre,
                             fold_w=fold)

    def one(wp, gp, prew, fw):
        upd = jnp.stack([wp - 0.2 * clip_to_bound(prew[k] * gp[k], 1.0)
                         for k in range(L)])
        return (fw[:, None] * upd).sum(0) / fw.sum()

    exp = jax.vmap(one)(w, grads, pre, fold)
    np.testing.assert_allclose(np.asarray(psi), np.asarray(exp), atol=2e-5)
    np.testing.assert_allclose(np.asarray(sq),
                               np.asarray(jnp.sum(grads * grads, -1)),
                               rtol=1e-4)


def test_round_fold_mask_cancellation():
    """Uniform survivor weights: in-kernel mask streams cancel exactly —
    psi equals the mode="none" fold to float dust."""
    w, grads, _, _, _, seeds = _inputs(4, 6, 256, jnp.float32)
    base, _ = ops.round_fold(w, grads, mu=0.1, bound=2.0)
    masked, _ = ops.round_fold(w, grads, mu=0.1, bound=2.0, mode="mask",
                               sigma=1.0, seeds=seeds)
    np.testing.assert_allclose(np.asarray(masked), np.asarray(base),
                               atol=1e-4)


def test_round_fold_zero_fold_weight():
    """Zero total fold weight folds to zero (the empty-buffer contract)."""
    w, grads, _, _, _, _ = _inputs(2, 3, 128, jnp.float32)
    psi, _ = ops.round_fold(w, grads, mu=0.1, bound=1.0,
                            fold_w=jnp.zeros((2, 3)))
    np.testing.assert_array_equal(np.asarray(psi), 0.0)


# -------------------------------------------- block choice / padding (ops)


def test_block_choice_never_degenerate():
    """Odd/prime D pads UP to the 128 tile; blocks stay 128-aligned (the
    old ``_block_for`` heuristic collapsed to 1-wide grids)."""
    for d in (509, 1018, 1021, 130, 2):
        cands, d_pad = ops.block_candidates(d)
        assert d_pad % 128 == 0 and d_pad >= d
        assert all(c % 128 == 0 for c in cands)
        assert all(d_pad % c == 0 for c in cands)


def test_odd_d_509_regression():
    """D=509 through every wrapper: correct vs oracle, no degenerate grid."""
    k = jax.random.PRNGKey(0)
    g = jax.random.normal(k, (3, 509))
    np.testing.assert_allclose(np.asarray(ops.clip_accum(g, 1.0)),
                               np.asarray(ref.clip_accum_ref(g, 1.0)),
                               atol=1e-5)
    A = jnp.asarray(combination_matrix("ring", 5), jnp.float32)
    psi = jax.random.normal(k, (5, 509))
    gg = jax.random.normal(jax.random.fold_in(k, 1), (5, 509))
    np.testing.assert_allclose(np.asarray(ops.graph_combine(A, psi, gg)),
                               np.asarray(ref.graph_combine_ref(A.T, psi,
                                                                gg)),
                               atol=3e-5)


def test_autotune_caches_per_shape():
    ops.clear_autotune_cache()
    u = jax.random.normal(jax.random.PRNGKey(0), (4, 1024))
    ops.laplace_transform(u, 0.5)
    n = len(ops._AUTOTUNE_CACHE)
    assert n >= 1
    ops.laplace_transform(u * 2, 0.5)        # same shape -> cache hit
    assert len(ops._AUTOTUNE_CACHE) == n
    block = next(v for k, v in ops._AUTOTUNE_CACHE.items()
                 if k[0] == "laplace")
    assert block in (128, 256, 512, 1024)


@pytest.mark.parametrize("mode", ["none", "mask"])
def test_autotune_runs_candidates_inside_jit(mode):
    """The autotuner is reached while a jitted caller traces; its
    candidates must really run (not be traced into the caller), and a
    kernel body with constants must still trace."""
    from repro.kernels import round_fold as rf
    ops.clear_autotune_cache()
    ran = []

    def make_timed(block, d_pad):
        w, g = jnp.zeros((2, d_pad)), jnp.zeros((2, 8, d_pad))
        s = jnp.ones((2, 8))
        seeds = jnp.zeros((2,), jnp.uint32) if mode == "mask" else None

        def fn():
            out = rf.fold_apply(w, g, s, s, s, mode=mode, sigma=0.5,
                                seeds=seeds, block_d=block, interpret=True)
            ran.append(isinstance(out, jax.core.Tracer))
            return out
        return fn

    jax.jit(lambda x: x + ops.choose_block(
        "probe", 1000, shape_key=(mode,), make_timed=make_timed)[0])(0)
    assert len(ran) == 2 * len(ops.block_candidates(1000)[0])
    assert not any(ran)


def test_autotune_raises_when_no_candidate_runs():
    ops.clear_autotune_cache()

    def make_timed(block, d_pad):
        raise ValueError(f"block {block} refused")

    with pytest.warns(UserWarning, match="refused"), \
            pytest.raises(RuntimeError, match="no block_d candidate"):
        ops.choose_block("probe", 1000, make_timed=make_timed)


# ------------------------------------------------- gated combine (events)


def test_graph_combine_gate_cache():
    """In-kernel cached-psi re-announce == where() + plain combine."""
    P, D = 6, 384
    k = jax.random.PRNGKey(3)
    A = jnp.asarray(combination_matrix("ring", P), jnp.float32)
    psi = jax.random.normal(k, (P, D))
    g = jax.random.normal(jax.random.fold_in(k, 1), (P, D))
    cache = jax.random.normal(jax.random.fold_in(k, 2), (P, D))
    gate = jnp.asarray([1, 0, 1, 1, 0, 1], jnp.float32)
    out = ops.graph_combine(A, psi, g, cache=cache, gate=gate)
    psi_eff = jnp.where(gate[:, None] > 0, psi, cache)
    exp = ref.graph_combine_ref(A.T, psi_eff, g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=3e-5)
    # noise-free variant (g=None)
    out = ops.graph_combine(A, psi, None, cache=cache, gate=gate)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(A.T.astype(jnp.float32) @ psi_eff.astype(jnp.float32)),
        atol=3e-5)


# -------------------------------------------------- engine-level parity


@pytest.fixture(scope="module")
def problem():
    return generate_problem(jax.random.PRNGKey(0), P=4, K=6, N=20, M=2)


def _cfg(scheme, **kw):
    base = dict(num_servers=4, clients_per_server=6, privacy=scheme,
                sigma_g=0.3, mu=0.1, topology="ring", grad_bound=5.0)
    base.update(kw)
    return GFLConfig(**base)


@pytest.mark.parametrize("scheme", ["none", "iid_dp", "hybrid"])
def test_run_gfl_kernel_parity(problem, scheme):
    """Whole-run switch on the dense engine: bit-identical draws (iid noise
    comes from the reference sampler on the same keys; masks cancel), so
    trajectories agree to float reordering."""
    base = _cfg(scheme)
    kern = dataclasses.replace(base, use_kernels=True)
    m0, p0 = run_gfl(problem, base, iters=4, batch_size=5, seed=1)
    m1, p1 = run_gfl(problem, kern, iters=4, batch_size=5, seed=1)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p0), atol=1e-5)
    np.testing.assert_allclose(m1, m0, atol=1e-5)


@pytest.mark.parametrize("scheme", ["none", "iid_dp", "hybrid"])
def test_run_gfl_population_weighted_kernel_parity(problem, scheme):
    """Importance-sampled cohorts: pre-clip weights + norms feedback run
    through the kernel's norms pass."""
    from repro.core.population.engine import run_gfl_population
    base = _cfg(scheme, clients_sampled=3, cohort="importance")
    kern = dataclasses.replace(base, use_kernels=True)
    r0 = run_gfl_population(problem, base, iters=4, batch_size=5, seed=1)
    r1 = run_gfl_population(problem, kern, iters=4, batch_size=5, seed=1)
    np.testing.assert_allclose(np.asarray(r1.params), np.asarray(r0.params),
                               atol=1e-5)
    np.testing.assert_allclose(r1.q, r0.q)


@pytest.mark.parametrize("scheme", ["none", "iid_dp", "hybrid"])
def test_run_gfl_async_kernel_parity(problem, scheme):
    """Event engine with stale snapshots + staleness-weighted folds."""
    from repro.core.events.engine import run_gfl_async
    base = _cfg(scheme, async_spec="async:buffer=4,rate=3,"
                                   "latency=lognorm:0.5,max_stale=2")
    kern = dataclasses.replace(base, use_kernels=True)
    r0 = run_gfl_async(problem, base, ticks=5, batch_size=5, seed=1)
    r1 = run_gfl_async(problem, kern, ticks=5, batch_size=5, seed=1)
    np.testing.assert_allclose(np.asarray(r1.params), np.asarray(r0.params),
                               atol=1e-5)
    np.testing.assert_array_equal(r1.flushed, r0.flushed)
    np.testing.assert_allclose(r1.q, r0.q)


@pytest.mark.parametrize("scheme", ["none", "iid_dp", "hybrid"])
def test_async_sync_limit_bit_identity_with_kernels(problem, scheme):
    """use_kernels=True sync limit routes through the population engine's
    EXACT programs: bit-identical trajectories, by construction."""
    from repro.core.events.engine import run_gfl_async
    from repro.core.population.engine import run_gfl_population
    cfg = _cfg(scheme, clients_sampled=3, use_kernels=True,
               async_spec="async:buffer=3,rate=3,max_stale=0")
    ra = run_gfl_async(problem, cfg, ticks=4, batch_size=5, seed=2)
    rp = run_gfl_population(
        problem, dataclasses.replace(cfg, async_spec="none"),
        iters=4, batch_size=5, seed=2)
    assert np.array_equal(np.asarray(ra.params), np.asarray(rp.params))
    np.testing.assert_array_equal(np.asarray(ra.msd),
                                  np.asarray(rp.msd))


def test_scan_executors_accept_kernels(problem):
    """Whole-run lax.scan bodies trace the Pallas calls (population scan +
    async scan) and agree with the streaming loops."""
    from repro.core.events.engine import run_gfl_async
    from repro.core.population.engine import run_gfl_population
    cfg = _cfg("hybrid", clients_sampled=3, use_kernels=True, sigma_g=0.2)
    rs = run_gfl_population(problem, cfg, iters=3, batch_size=5, seed=3,
                            scan=True)
    rl = run_gfl_population(problem, cfg, iters=3, batch_size=5, seed=3)
    np.testing.assert_allclose(np.asarray(rs.params), np.asarray(rl.params),
                               atol=1e-6)
    cfga = dataclasses.replace(
        cfg, clients_sampled=0,
        async_spec="async:buffer=3,rate=3,latency=lognorm:0.4,max_stale=2")
    r2 = run_gfl_async(problem, cfga, ticks=4, batch_size=5, seed=4,
                       scan=True)
    r3 = run_gfl_async(problem, cfga, ticks=4, batch_size=5, seed=4)
    np.testing.assert_allclose(np.asarray(r2.params), np.asarray(r3.params),
                               atol=1e-6)


def test_run_gfl_dropout_kernel_parity(problem):
    """Client dropout: alive masks become fold weights, masks/noise fold at
    the survivor mean — parity against the dropout-safe reference hooks."""
    base = _cfg("hybrid", fault="dropout:0.4")
    kern = dataclasses.replace(base, use_kernels=True)
    m0, p0 = run_gfl(problem, base, iters=4, batch_size=5, seed=5)
    m1, p1 = run_gfl(problem, kern, iters=4, batch_size=5, seed=5)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p0), atol=1e-5)


# ------------------------------------------- secure-agg compile-flat in L


@pytest.mark.parametrize("L", [8, 64])
def test_secure_agg_trace_cost(L, request):
    """The O(L) fori_loop mask accumulation keeps trace/compile time FLAT
    in the cohort size: L=64 must stay within 2x of L=8 (the unrolled pair
    loop was quadratic — 2016 streams at L=64)."""
    from repro.kernels import secure_agg as sagg

    def lower(L):
        upd = jax.ShapeDtypeStruct((L, 256), jnp.float32)
        sd = jax.ShapeDtypeStruct((1,), jnp.uint32)
        fn = jax.jit(lambda u, s: sagg.secure_agg_mean(
            u, s, scale=0.5, block_d=128, interpret=True))
        t0 = time.perf_counter()
        fn.lower(upd, sd)
        return time.perf_counter() - t0

    lower(4)                      # warm the tracing machinery once
    times = {l: min(lower(l) for _ in range(3)) for l in (8, 64)}
    assert times[64] < 2.0 * times[8] + 0.05, times


def test_mesh_kernel_dense_combine_matches_einsum():
    """launch/steps.py routes the mesh's dense combine through the fused
    kernel per leaf (flatten -> graph_combine -> reshape), matching the
    einsum baseline incl. bf16 leaves and the g=None (noise-free) path."""
    from repro.launch.steps import _dense_combine, _kernel_dense_combine
    P = 6
    A = jnp.asarray(combination_matrix("ring", P), jnp.float32)
    k = jax.random.PRNGKey(0)
    psi = {"a": jax.random.normal(k, (P, 3, 7)).astype(jnp.bfloat16),
           "b": jax.random.normal(jax.random.fold_in(k, 1), (P, 11))}
    g = {"a": (jax.random.normal(jax.random.fold_in(k, 2), (P, 3, 7)) * 0.3
               ).astype(jnp.bfloat16),
         "b": jax.random.normal(jax.random.fold_in(k, 3), (P, 11)) * 0.3}
    want = _dense_combine(A, psi, g, cancel=True)
    got = _kernel_dense_combine(A, psi, g)
    for leaf in psi:
        tol = 2e-2 if psi[leaf].dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(got[leaf], np.float32),
                                   np.asarray(want[leaf], np.float32),
                                   atol=tol)
    want0 = _dense_combine(A, psi, None)
    got0 = _kernel_dense_combine(A, psi, None)
    for leaf in psi:
        np.testing.assert_allclose(np.asarray(got0[leaf], np.float32),
                                   np.asarray(want0[leaf], np.float32),
                                   atol=2e-2)


def test_round_pipeline_traffic_halved():
    """The analytic HBM accounting (the BENCH_kernels.json criterion): the
    fused pipeline does <= 1/2 the gradient-scale HBM round trips of the
    reference chain for both privacy modes — and for the paper's hybrid
    (mask) scheme the full byte total is <= 1/2 as well (laplace's
    parity-preserving pre-drawn noise operand is counted honestly on the
    fused side: 4 vs 8 [P, L, D] passes, byte ratio -> 0.5 from above as
    the [P, D] terms vanish)."""
    from repro.launch.roofline import round_pipeline_traffic
    for mode in ("mask", "laplace"):
        for P, L, D in ((10, 8, 4096), (16, 64, 1 << 20)):
            ref_b = round_pipeline_traffic(P, L, D, mode=mode, fused=False)
            fus_b = round_pipeline_traffic(P, L, D, mode=mode, fused=True)
            assert (fus_b["pld_passes"]
                    <= 0.5 * ref_b["pld_passes"]), (mode, P, L, D)
            if mode == "mask":
                assert fus_b["total"] <= 0.5 * ref_b["total"], (P, L, D)


def test_secure_agg_l64_matches_plain_mean():
    """L=64 (previously 2016 unrolled pair streams) now traces instantly
    and still cancels exactly."""
    upd = jax.random.normal(jax.random.PRNGKey(0), (64, 256))
    out = ops.secure_agg_mean(upd, jnp.array([3], jnp.uint32), scale=0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(upd.mean(0)),
                               atol=2e-4)
