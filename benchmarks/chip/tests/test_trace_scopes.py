"""Device time by program and scope (``trace_scopes.py``), on a small trace
recorded on a TPU v5e (``record_scoped_trace.py``): three rounds of a
jitted step with ``gfl.client_grads`` around a ``lax.scan`` and
``gfl.combine`` after it, each fed by an eager draw inside the program's
``gfl.input`` span, each round inside a ``bench.round`` host span; with
the step's compiled HLO text beside it."""
import os

import pytest

import trace_reduce
import trace_scopes

DATA = os.path.join(os.path.dirname(__file__), "data")
TRACE = os.path.join(DATA, "scoped.xplane.pb")
HLO = os.path.join(DATA, "scoped.hlo.txt")


@pytest.fixture(scope="module")
def scoped():
    with open(HLO) as f:
        return trace_scopes.reduce_scoped(TRACE, f.read())


def test_exclusive_time_counts_nested_time_once():
    ivs = [(0, 10), (1, 3), (2, 3), (5, 8), (12, 14), (13, 20)]
    assert trace_scopes.exclusive_ns(ivs) == [5, 1, 1, 3, 1, 7]
    assert sum(trace_scopes.exclusive_ns(ivs)) == sum(
        e - s for s, e in trace_reduce._union(ivs))


def test_hlo_scopes_of_a_compiled_step():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x, w):
        with jax.named_scope("gfl.client_grads"):
            acc, _ = jax.lax.scan(
                lambda c, xi: (c + jnp.tanh(xi @ w).sum(), None), 0.0, x)
        with jax.named_scope("gfl.combine"):
            return w * acc

    text = step.lower(jnp.ones((3, 8, 8)), jnp.ones((8, 8))).compile(
    ).as_text()
    module, scopes = trace_scopes.hlo_scopes(text)
    assert module == "jit_step"
    assert set(scopes.values()) == {"gfl.client_grads", "gfl.combine"}
    # the loop itself sits in the scope it was written in
    whiles = [n for n in scopes if n.startswith("while")]
    assert whiles and all(scopes[n] == "gfl.client_grads" for n in whiles)


def test_the_reduction_keeps_what_trace_reduce_gives(scoped):
    plain = trace_reduce.reduce_trace(TRACE)
    assert scoped.summary.busy_s == plain.busy_s
    assert scoped.summary.top_ops(10) == plain.top_ops(10)
    assert scoped.summary.idle_gaps(10) == plain.idle_gaps(10)


def test_device_scopes_sum_to_busy_with_the_while_once(scoped):
    busy = scoped.busy_s
    assert sum(s for _, s in scoped.device_scopes()) == pytest.approx(
        busy, rel=1e-9)
    assert sum(scoped.phase_seconds().values()) == pytest.approx(
        busy, rel=1e-9)
    # top_ops counts the scan's while and its body again ...
    assert sum(s for _, s in scoped.summary.top_ops(100)) > 1.2 * busy
    # ... its exclusive time is what the body leaves of it
    ops = scoped.summary.ops
    whiles = [i for i, o in enumerate(ops) if o.opcode == "while"]
    assert len(whiles) == 3
    for i in whiles:
        w = ops[i]
        body = sum(o.dur for o in ops if o is not w and o.device == w.device
                   and w.start <= o.start and o.start + o.dur
                   <= w.start + w.dur)
        assert 0 < scoped.exclusive[i] == pytest.approx(w.dur - body)


def test_ops_take_their_program_and_scope(scoped):
    assert scoped.step_program == "jit_step"
    keys = {k for k, _ in scoped.device_scopes()}
    assert {"jit_step/gfl.client_grads", "jit_step/gfl.combine"} <= keys
    others = {p for p in scoped.program if p != "jit_step"}
    assert others and "unknown" not in others
    # the eager draw's programs are read for no scope
    assert all(s == trace_scopes.UNSCOPED
               for p, s in zip(scoped.program, scoped.scope)
               if p != "jit_step")
    steps = [(o, s) for o, p, s in zip(scoped.summary.ops, scoped.program,
                                       scoped.scope) if p == "jit_step"]
    assert {s for o, s in steps if o.opcode == "while"} == {
        "gfl.client_grads"}
    phase = scoped.phase_seconds()
    assert phase["input"] > 0 and phase["client_grads"] > 0
    assert phase["protocol"] > 0 and phase["unscoped"] == 0
    assert scoped.top_unscoped() == []


def test_idle_gaps_by_program_span(scoped):
    spans = scoped.program_spans
    assert [n for n, _, _ in spans] == ["gfl.input"] * 3
    rounds = scoped.summary.host_spans
    assert all(any(rs <= s and e <= re_ for _, rs, re_ in rounds)
               for _, s, e in spans)
    gaps = dict(scoped.idle_gaps_program())
    assert set(gaps) <= {"gfl.input", "bench.round", "host:none"}
    assert gaps["gfl.input"] > 0
    idle = scoped.summary.window_s - scoped.busy_s
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-9)
    assert sum(s for _, s in scoped.summary.idle_gaps()) == pytest.approx(
        idle, rel=1e-9)
