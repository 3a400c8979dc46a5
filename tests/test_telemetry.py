"""Telemetry layer tests (docs/observability.md).

The two-sided contract: ``telemetry=off`` is bit-identical to an
uninstrumented run on every engine (the off path never inserts a
callback or changes a carry), and ``telemetry=on`` observes without
perturbing — same msd/params, with schema-valid records flowing to the
sinks.  Plus the building blocks: schema registry, sinks, span tracer,
the mergeable quantile sketch and the inspector CLI.
"""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import GFLConfig
from repro.core.events import run_gfl_async
from repro.core.population import SyntheticPopulation, run_gfl_population
from repro.core.simulate import generate_problem, run_gfl
from repro.telemetry import (
    MetricsStream,
    QuantileSketch,
    RunLog,
    SchemaError,
    emit,
    get_schema,
    list_schemas,
    session,
    telemetry_active,
    trace_span,
    validate_record,
)
from tests.hypothesis_compat import given, settings, st

REPO_ROOT = Path(__file__).resolve().parents[1]


# ------------------------------------------------------------------ schema

def test_schemas_registered_and_validate():
    names = set(list_schemas())
    assert {"round", "step", "privacy", "kernel", "mesh"} <= names
    validate_record("round", {"round": 0, "msd": 0.5, "engine": "population"})
    with pytest.raises(SchemaError):
        validate_record("round", {"round": 0, "bogus_field": 1.0})
    with pytest.raises(SchemaError):
        validate_record("round", {"msd": 0.5})      # index missing
    with pytest.raises(SchemaError):
        validate_record("no_such_stream", {"x": 1})
    assert get_schema("privacy").index == "step"


# ---------------------------------------------------------------- sessions

def test_emit_is_noop_without_session():
    assert not telemetry_active()
    emit("round", {"round": 0, "bogus_field": 1.0})  # not even validated


def test_emit_host_and_in_graph():
    with session("memory") as sess:
        assert telemetry_active()
        emit("round", {"round": 0, "msd": 1.0, "engine": "test"})

        @jax.jit
        def f(x):
            emit("step", {"step": 0, "msd": x})
            return x * 2

        def body(c, x):
            emit("step", {"step": c, "msd": x})
            return c + 1, x

        f(jnp.float32(3.0))
        jax.lax.scan(body, jnp.int32(1), jnp.arange(3, dtype=jnp.float32))
        jax.effects_barrier()
        assert len(sess.memory_records("round")) == 1
        steps = sess.memory_records("step")
        assert len(steps) == 4
        assert all(r["stream"] == "step" and "t_wall" in r for r in steps)
    assert not telemetry_active()


def test_nested_session_is_passthrough():
    with session("memory") as outer:
        with session("memory") as inner:
            assert inner is outer
            emit("round", {"round": 0, "msd": 0.0})
        assert telemetry_active()       # inner exit must not close outer
        assert len(outer.memory_records("round")) == 1


def test_metrics_stream_accumulates_in_scan():
    ms = MetricsStream("step", cumulative={"events_total": "events"})
    with session("memory") as sess:
        def body(carry, x):
            c, acc = carry
            acc = ms.tap(acc, {"step": c, "events": x})
            return (c + 1, acc), x

        jax.lax.scan(body, (jnp.int32(0), ms.init()),
                     jnp.array([2, 3, 4], jnp.int32))
        jax.effects_barrier()
        recs = sess.memory_records("step")
    assert [r["events"] for r in recs] == [2, 3, 4]
    assert [r["events_total"] for r in recs] == [2, 5, 9]


def test_trace_span_writes_chrome_json(tmp_path):
    trace = tmp_path / "t.trace.json"
    with session("memory", trace_path=trace):
        with trace_span("outer", detail="x"):
            with trace_span("inner"):
                pass
    doc = json.loads(trace.read_text())
    events = doc["traceEvents"]
    assert {e["name"] for e in events} >= {"outer", "inner"}
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0 and "ts" in e
    # no session -> null span, no crash
    with trace_span("nobody"):
        pass


# ------------------------------------------------------------------- sinks

def test_jsonl_and_csv_sinks(tmp_path):
    jl = tmp_path / "run.jsonl"
    cb = tmp_path / "run"
    with session(f"jsonl:{jl}+csv:{cb}"):
        emit("round", {"round": 0, "msd": 0.25, "engine": "test"})
        emit("round", {"round": 1, "msd": 0.125, "engine": "test"})
        emit("privacy", {"step": 1, "eps": float("inf"), "delta": 0.0})
    recs = [json.loads(ln) for ln in jl.read_text().splitlines()]
    assert len(recs) == 3
    for r in recs:
        validate_record(r["stream"],
                        {k: v for k, v in r.items()
                         if k not in ("stream", "run", "t_wall")})
    assert recs[2]["eps"] == float("inf")
    csv_round = tmp_path / "run.round.csv"
    lines = csv_round.read_text().splitlines()
    assert lines[0].startswith("run,t_wall,round,engine")
    assert len(lines) == 3


def test_console_sink_runs(capfd):
    with session("console:1"):
        emit("round", {"round": 0, "msd": 0.5, "q": 0.1, "engine": "t"})
        emit("round", {"round": 1, "msd": 0.25, "q": 0.1, "engine": "t"})
    cap = capfd.readouterr()
    out = cap.out + cap.err        # console sink renders on stderr
    assert "msd" in out and "round" in out


def test_console_every_arg_decimates(capfd):
    from repro.telemetry.sinks import ConsoleSink, sink_from_spec
    sink = sink_from_spec("console:3")
    assert isinstance(sink, ConsoleSink) and sink.every == 3

    with session("console:3"):
        for i in range(7):
            emit("round", {"round": i, "msd": float(100 + i),
                           "engine": "t"})
    cap = capfd.readouterr()
    out = cap.out + cap.err
    # only rounds 2 and 5 (the 3rd and 6th records) render
    assert "102" in out and "105" in out
    assert "101" not in out and "104" not in out and "106" not in out


def test_bad_sink_spec_rejected():
    with pytest.raises(ValueError):
        with session("carrier_pigeon"):
            pass


# ------------------------------------------------- buffered flush / profile

def test_metrics_stream_buffered_matches_per_round(tmp_path):
    """flush_every=3 must deliver record-for-record what flush_every=1
    does (including the drained partial buffer at the tail)."""
    xs = jnp.arange(1, 8, dtype=jnp.int32)        # 7 rows: 2 full + 1 part

    def collect(flush_every):
        ms = MetricsStream("step", cumulative={"events_total": "events"},
                           fields=("step", "events", "events_total"),
                           flush_every=flush_every)
        with session("memory") as sess:
            def body(carry, x):
                c, acc = carry
                acc = ms.tap(acc, {"step": c, "events": x})
                return (c + 1, acc), x

            (_, acc), _ = jax.lax.scan(body, (jnp.int32(0), ms.init()), xs)
            jax.effects_barrier()
            ms.drain(acc)
            recs = sess.memory_records("step")
        return [{k: r[k] for k in ("step", "events", "events_total")}
                for r in recs]

    assert collect(1) == collect(3)


def test_metrics_stream_buffered_requires_fields():
    with pytest.raises(ValueError):
        MetricsStream("step", flush_every=4)


def test_flush_every_env(monkeypatch):
    from repro.telemetry import flush_every_from_env
    monkeypatch.delenv("REPRO_TELEMETRY_FLUSH_EVERY", raising=False)
    assert flush_every_from_env() == 1
    monkeypatch.setenv("REPRO_TELEMETRY_FLUSH_EVERY", "8")
    assert flush_every_from_env() == 8
    monkeypatch.setenv("REPRO_TELEMETRY_FLUSH_EVERY", "junk")
    assert flush_every_from_env() == 1


def test_profile_stream_attributes_compile(tmp_path):
    with session("memory", profile=True) as sess:
        @jax.jit
        def f(x):
            return x * 2 + 1

        with trace_span("fresh_jit", tag="t"):
            jax.block_until_ready(f(jnp.arange(101, dtype=jnp.float32)))
    recs = sess.memory_records("profile")
    assert len(recs) == 1
    r = recs[0]
    validate_record("profile", {k: v for k, v in r.items()
                                if k not in ("stream", "run", "t_wall",
                                             "phase_args")})
    assert r["phase"] == "fresh_jit"
    assert r["compiles"] >= 1 and r["retraces"] >= 1
    assert r["compile_s"] > 0.0
    assert r["wall_s"] >= r["compile_s"]
    assert r["execute_s"] >= 0.0 and r["callback_s"] >= 0.0


def test_profile_off_by_default():
    with session("memory") as sess:
        with trace_span("plain"):
            pass
    assert sess.memory_records("profile") == []


def test_trace_span_reaches_profiler_without_session(tmp_path):
    """With no telemetry session, a ``trace_span`` inside a jax.profiler
    trace is written into it as a host event of that name, with its
    arguments, on the profiler's clock; outside any trace and session it
    is one reusable no-op."""
    from jax.profiler import ProfileData
    assert not telemetry_active()
    assert trace_span("a") is trace_span("b", k=1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace_span("gfl.probe", round=3):
            jax.block_until_ready(jnp.arange(8) + 1)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    events = [e for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name == "gfl.probe"]
    assert len(events) == 1
    assert events[0].duration_ns > 0
    assert str(dict(events[0].stats)["round"]) == "3"


def test_profile_counts_cache_loads_apart_from_compiles(tmp_path):
    """A program loaded from the persistent compile cache is a
    ``cache_loads``, not a ``compiles``, in the ``profile`` stream."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path), 0, 0)):
        jax.config.update(k, v)
    cc.reset_cache()
    try:
        x = jnp.arange(37, dtype=jnp.float32)
        with session("memory", profile=True) as sess:
            for phase in ("cold", "warm"):
                # a fresh closure each time: traced and lowered again, and
                # the second time loaded from the cache the first one filled
                f = jax.jit(lambda v: jnp.sin(v) * 3 + 0.5)
                with trace_span(phase):
                    jax.block_until_ready(f(x))
        recs = {r["phase"]: r for r in sess.memory_records("profile")}
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()
    for r in recs.values():
        validate_record("profile", {k: v for k, v in r.items()
                                    if k not in ("stream", "run", "t_wall")})
        assert r["retraces"] >= 1 and r["lowerings"] == 1
    assert (recs["cold"]["compiles"], recs["cold"]["cache_loads"]) == (1, 0)
    assert (recs["warm"]["compiles"], recs["warm"]["cache_loads"]) == (0, 1)


def test_round_phases_are_named_spans(tmp_path):
    """The input build and the accountant run in ``gfl.input`` and
    ``gfl.accountant`` spans that carry the round they belong to."""
    from repro.core.privacy.mechanism import mechanism_for
    from repro.data import TokenStream, federated_token_batches
    trace = tmp_path / "t.trace.json"
    acc = mechanism_for(GFLConfig(privacy="hybrid")).accountant()
    acc.advance(4)
    with session("memory", trace_path=trace):
        federated_token_batches(TokenStream(vocab=64), seed=0, step=4, P=1,
                                L=2, per_client=1, seq_len=8)
        acc.advance(1)
    events = {e["name"]: e for e in
              json.loads(trace.read_text())["traceEvents"]}
    assert events["gfl.input"]["args"] == {"round": 4}
    assert events["gfl.accountant"]["args"] == {"round": 4}


# ------------------------------------------------------------------ sketch

def _rank_error(data, est, q):
    data = np.sort(np.asarray(data))
    rank = np.searchsorted(data, est) / max(len(data) - 1, 1)
    return abs(rank - q)


def test_sketch_rank_error_vs_numpy():
    rng = np.random.default_rng(0)
    data = rng.normal(size=5000)
    sk = QuantileSketch(k=128)
    sk.extend(data)
    for q in (0.1, 0.5, 0.9, 0.99):
        assert _rank_error(data, sk.quantile(q), q) < 0.05, q
    assert sk.min == data.min() and sk.max == data.max()


def test_sketch_merge_invariance():
    rng = np.random.default_rng(1)
    a, b = rng.normal(size=3000), rng.normal(loc=2.0, size=2000)
    both = np.concatenate([a, b])
    sa, sb = QuantileSketch(k=128), QuantileSketch(k=128)
    sa.extend(a)
    sb.extend(b)
    merged = sa.merge(sb)
    for q in (0.25, 0.5, 0.75):
        assert _rank_error(both, merged.quantile(q), q) < 0.08, q


def test_sketch_serialization_roundtrip():
    sk = QuantileSketch(k=16)
    sk.extend(range(100))
    back = QuantileSketch.from_dict(sk.to_dict())
    assert back.quantile(0.5) == sk.quantile(0.5)
    assert back.min == sk.min and back.max == sk.max


@settings(max_examples=25, deadline=None)
@given(st.lists(st.floats(min_value=-1e6, max_value=1e6,
                          allow_nan=False), min_size=2, max_size=400),
       st.integers(min_value=1, max_value=399))
def test_sketch_merge_matches_bulk(values, cut):
    """Property: splitting a stream at any point and merging the two
    sketches bounds the same quantiles as sketching the whole stream."""
    cut = min(cut, len(values) - 1)
    bulk = QuantileSketch(k=64)
    bulk.extend(values)
    left, right = QuantileSketch(k=64), QuantileSketch(k=64)
    left.extend(values[:cut])
    right.extend(values[cut:])
    merged = left.merge(right)
    for q in (0.0, 0.5, 1.0):
        assert _rank_error(values, merged.quantile(q), q) <= \
            _rank_error(values, bulk.quantile(q), q) + 0.25


# ------------------------------------------------------------------ runlog

def test_runlog_rows_and_stack():
    log = RunLog("test_engine", stream="round")
    log.row(0, msd=1.0, gap=None)           # None values dropped
    log.row(1, msd=0.5, gap=0.3)
    assert log.column("msd") == [1.0, 0.5]
    assert log.stack("gap").shape == (1,)
    assert log.stack("nothing") is None


def test_runlog_extend_arrays_validates_lengths():
    log = RunLog("test_engine")
    with pytest.raises(ValueError):
        log.extend_arrays({"msd": np.zeros(3), "q": np.zeros(4)})


# -------------------------------------------------- engine bit-identity

def _pop_cfg(privacy, **kw):
    return GFLConfig(num_servers=3, clients_per_server=20,
                     clients_sampled=4, topology="ring", privacy=privacy,
                     sigma_g=0.1, mu=0.1, grad_bound=10.0, **kw)


@pytest.mark.parametrize("privacy", ["none", "iid_dp", "hybrid"])
@pytest.mark.parametrize("scan", [False, True])
def test_population_off_identical_and_on_pure(privacy, scan):
    pop = SyntheticPopulation(3, 20, mode="hetero", N=30, M=2, data_seed=0)
    kw = dict(iters=4, batch_size=5, seed=0, scan=scan)
    base = run_gfl_population(pop, _pop_cfg(privacy), **kw)
    off = run_gfl_population(pop, _pop_cfg(privacy, telemetry="off"), **kw)
    with session("memory") as sess:
        on = run_gfl_population(pop, _pop_cfg(privacy, telemetry="memory"),
                                **kw)
        recs = sess.memory_records("round")
    np.testing.assert_array_equal(np.asarray(base.msd), np.asarray(off.msd))
    np.testing.assert_array_equal(np.asarray(base.params),
                                  np.asarray(off.params))
    np.testing.assert_array_equal(np.asarray(base.msd), np.asarray(on.msd))
    np.testing.assert_array_equal(np.asarray(base.params),
                                  np.asarray(on.params))
    # result views and the stream agree row for row
    msd_stream = [r["msd"] for r in recs if "msd" in r]
    np.testing.assert_allclose(np.asarray(on.msd), msd_stream)


@pytest.mark.parametrize("privacy", ["none", "iid_dp", "hybrid"])
def test_dense_engine_off_identical(privacy):
    prob = generate_problem(jax.random.PRNGKey(0), P=3, K=8, N=30, M=2)
    cfg_off = GFLConfig(num_servers=3, clients_per_server=8,
                        topology="ring", privacy=privacy, sigma_g=0.1,
                        mu=0.1, grad_bound=10.0)
    msd0, p0 = run_gfl(prob, cfg_off, iters=3, batch_size=4, seed=0)
    with session("memory"):
        cfg_on = GFLConfig(**{**cfg_off.__dict__, "telemetry": "memory"})
        msd1, p1 = run_gfl(prob, cfg_on, iters=3, batch_size=4, seed=0)
    np.testing.assert_array_equal(np.asarray(msd0), np.asarray(msd1))
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))


@pytest.mark.parametrize("scan", [False, True])
def test_async_engine_off_identical_and_streams(scan):
    pop = SyntheticPopulation(3, 24, mode="hetero", N=30, M=2, data_seed=0)
    spec = "async:buffer=4,rate=4,latency=exp:0.7,max_stale=2"
    kw = dict(ticks=5, batch_size=5, seed=0, scan=scan)
    off = run_gfl_async(pop, _pop_cfg("hybrid", async_spec=spec), **kw)
    with session("memory") as sess:
        on = run_gfl_async(pop, _pop_cfg("hybrid", async_spec=spec,
                                         telemetry="memory"), **kw)
        rounds = sess.memory_records("round")
        privacy = sess.memory_records("privacy")
    np.testing.assert_array_equal(np.asarray(off.msd), np.asarray(on.msd))
    np.testing.assert_array_equal(np.asarray(off.params),
                                  np.asarray(on.params))
    np.testing.assert_array_equal(off.q, on.q)
    np.testing.assert_array_equal(off.staleness, on.staleness)
    np.testing.assert_array_equal(off.flushed, on.flushed)
    assert len(rounds) == 5
    # view satellite: AsyncRunResult fields ARE the stream's rows
    np.testing.assert_allclose(np.asarray(on.msd),
                               [r["msd"] for r in rounds])
    np.testing.assert_array_equal(
        on.flushed.astype(np.int32),
        np.asarray([r["flushed"] for r in rounds], np.int32))
    assert privacy, "async accounting must emit the privacy stream"
    assert {r["server"] for r in privacy} >= {"server0"}
    for r in privacy:
        assert r["eps"] >= 0 or r["eps"] == float("inf")


def test_population_kernels_off_identical():
    pop = SyntheticPopulation(3, 20, mode="hetero", N=30, M=2, data_seed=0)
    kw = dict(iters=3, batch_size=5, seed=0, scan=False)
    off = run_gfl_population(pop, _pop_cfg("hybrid", use_kernels=True), **kw)
    with session("memory"):
        on = run_gfl_population(
            pop, _pop_cfg("hybrid", use_kernels=True, telemetry="memory"),
            **kw)
    np.testing.assert_array_equal(np.asarray(off.msd), np.asarray(on.msd))
    np.testing.assert_array_equal(np.asarray(off.params),
                                  np.asarray(on.params))


# -------------------------------------------------------- inspector CLI

def _run_inspect(args):
    return subprocess.run(
        [sys.executable, "-m", "repro.telemetry.inspect"] + args,
        cwd=REPO_ROOT, capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu", "HOME": "/tmp"})


def test_inspector_cli_on_engine_output(tmp_path):
    jl = tmp_path / "run.jsonl"
    trace = tmp_path / "run.trace.json"
    pop = SyntheticPopulation(3, 20, mode="hetero", N=30, M=2, data_seed=0)
    with session(f"jsonl:{jl}", trace_path=trace):
        run_gfl_population(pop, _pop_cfg("hybrid", telemetry="jsonl"),
                           iters=3, batch_size=5, seed=0, scan=True)
    proc = _run_inspect([str(jl), "--trace", str(trace), "--tail", "2"])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "privacy" in proc.stdout and "eps" in proc.stdout
    assert "valid Chrome trace" in proc.stdout


def test_inspector_cli_rejects_garbage(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"stream": "round", "bogus_field": 3}\nnot json\n')
    proc = _run_inspect([str(bad)])
    assert proc.returncode == 1
