"""Device time by program and by the program's named scopes.

:mod:`trace_reduce` gives busy and idle time, time per instruction and the
idle gaps under the benchmark's own ``bench.*`` spans.  This module adds,
from the same ``.xplane.pb`` and the compiled HLO text of the step:

- each device op's *program*: the event of the device plane's
  ``XLA Modules`` line (``jit_<fn>(<id>)``) open when the op started;
- each op's *scope*: the first ``gfl.*`` component of the ``op_name``
  that the step's compiled HLO gives the instruction of that name (a
  fusion without an ``op_name`` takes the first ``gfl.*`` scope inside
  the computation it calls).  Scopes are read for the step's program
  only; the instruction names of other programs mean nothing here;
- each op's *exclusive* time: the part of its interval in which no op
  that started later on the same chip was still running.  An enclosing
  ``while`` keeps only what its body leaves, so every busy nanosecond is
  counted once and the exclusive times sum to ``busy_s``;
- the program's own host spans (``gfl.*``, e.g. ``gfl.input`` around the
  input build), in a list beside the benchmark's, and the idle gaps by the
  innermost of them.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from dataclasses import dataclass, field

from trace_reduce import DEVICE_PLANE, TraceSummary, reduce_trace

SCOPE = re.compile(r"\b(gfl\.[A-Za-z_]+)")
PROGRAM_SPAN = "gfl."
#: the step's scopes that make up the GFL protocol around the clients
PROTOCOL = ("gfl.clip", "gfl.client_mean", "gfl.update", "gfl.privatize",
            "gfl.combine")
UNSCOPED = "unscoped"
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = (.*)$")
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+) .*\{\s*$")
OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
CALLS = re.compile(r"calls=%([^\s,]+)")
MODULE = re.compile(r"^HloModule ([^\s,]+)")


def hlo_scopes(hlo_text: str) -> tuple[str, dict[str, str]]:
    """The module name of a compiled HLO text and, per instruction name,
    the first ``gfl.*`` scope of its ``op_name`` (instructions under no
    scope are left out)."""
    module = ""
    own: dict[str, str] = {}
    calls: dict[str, str] = {}
    inside: dict[str, str] = {}       # computation -> its first scope
    comp = None
    for line in hlo_text.splitlines():
        m = MODULE.match(line)
        if m:
            module = m.group(1)
            continue
        m = COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = OP_NAME.search(rest)
        s = SCOPE.search(op.group(1)) if op else None
        if s:
            own[name] = s.group(1)
            if comp is not None and (comp not in inside
                                     or line.lstrip().startswith("ROOT")):
                inside[comp] = s.group(1)
        else:
            c = CALLS.search(rest)
            if c:
                calls[name] = c.group(1)
    for name, comp in calls.items():
        if comp in inside:
            own[name] = inside[comp]
    return module, own


def _program(event_name: str) -> str:
    """``jit_step_fn(1234)`` -> ``jit_step_fn``."""
    return event_name.split("(", 1)[0]


def exclusive_ns(intervals: list[tuple[float, float]]) -> list[float]:
    """Per interval, the time in which it is the latest-started of those
    still open: nested intervals count once, in the innermost."""
    excl = [0.0] * len(intervals)
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    stack: list[int] = []
    cur = float("-inf")

    def run_to(t: float) -> None:
        nonlocal cur
        while stack:
            top = stack[-1]
            end = intervals[top][1]
            if end > t:
                break
            excl[top] += max(0.0, end - cur)
            cur = max(cur, end)
            stack.pop()
        if stack and t > cur:
            excl[stack[-1]] += t - cur
        cur = max(cur, t)

    for i in order:
        run_to(intervals[i][0])
        stack.append(i)
    run_to(float("inf"))
    return excl


@dataclass
class ScopedTrace:
    summary: TraceSummary
    step_program: str                   # the compiled step's module name
    program: list[str]                  # per op of summary.ops
    scope: list[str]                    # per op; UNSCOPED when none
    exclusive: list[float]              # ns, per op
    program_spans: list[tuple[str, float, float]] = field(
        default_factory=list)

    @property
    def busy_s(self) -> float:
        return self.summary.busy_s

    def _per_chip(self, ns: float) -> float:
        return ns * 1e-9 / max(self.summary.n_devices, 1)

    def device_scopes(self, n: int | None = None) -> list[list]:
        """Exclusive device seconds by ``<program>/<scope>``, per chip,
        largest first; they sum to ``busy_s``."""
        tot: dict[str, float] = {}
        for p, s, x in zip(self.program, self.scope, self.exclusive):
            tot[f"{p}/{s}"] = tot.get(f"{p}/{s}", 0.0) + x
        rows = sorted(tot.items(), key=lambda kv: -kv[1])
        return [[k, self._per_chip(ns)] for k, ns in rows[:n]]

    def phase_seconds(self) -> dict[str, float]:
        """Exclusive device seconds, per chip, of the round's phases:
        ``input`` (ops under ``gfl.input`` and ops of any program other
        than the step), ``client_grads``, ``protocol`` (the step's
        :data:`PROTOCOL` scopes), and ``unscoped`` (the step's ops under
        no ``gfl.*`` scope).  They sum to ``busy_s``."""
        out = dict.fromkeys(("input", "client_grads", "protocol",
                             "unscoped"), 0.0)
        for p, s, x in zip(self.program, self.scope, self.exclusive):
            if s == "gfl.input" or p != self.step_program:
                out["input"] += x
            elif s == "gfl.client_grads":
                out["client_grads"] += x
            elif s in PROTOCOL:
                out["protocol"] += x
            else:
                out["unscoped"] += x
        return {k: self._per_chip(v) for k, v in out.items()}

    def top_unscoped(self, n: int = 10) -> list[list]:
        """The step's ops under no scope, by exclusive seconds per chip."""
        tot: dict[str, float] = {}
        for o, p, s, x in zip(self.summary.ops, self.program, self.scope,
                              self.exclusive):
            if p == self.step_program and s == UNSCOPED:
                key = re.sub(r"\.\d+$", "", o.name)
                tot[key] = tot.get(key, 0.0) + x
        rows = sorted(tot.items(), key=lambda kv: -kv[1])
        return [[k, self._per_chip(ns)] for k, ns in rows[:n]]

    def idle_gaps_program(self, n: int = 10) -> list[list]:
        """Idle time of the first chip by the innermost span open at each
        gap's midpoint, program spans (``gfl.*``) and the benchmark's
        alike: a program span opens inside the benchmark's span around
        the same call, so it names the gap where it is open."""
        both = self.summary.host_spans + self.program_spans
        return dataclasses.replace(self.summary,
                                   host_spans=both).idle_gaps(n)


def reduce_scoped(path: str, hlo_text: str,
                  devices: set[int] | None = None) -> ScopedTrace:
    """:func:`trace_reduce.reduce_trace` of ``path`` with each op's
    program, scope and exclusive time; ``hlo_text`` is the compiled HLO
    of the step whose ops are read for scopes."""
    from jax.profiler import ProfileData

    summary = reduce_trace(path, devices)
    step_program, scopes = hlo_scopes(hlo_text)
    modules: dict[int, list[tuple[float, float, str]]] = {}
    program_spans: list[tuple[str, float, float]] = []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules.setdefault(int(m.group(1)), []).extend(
                        (float(e.start_ns), float(e.start_ns + e.duration_ns),
                         _program(e.name)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_SPAN):
                        program_spans.append(
                            (e.name, float(e.start_ns),
                             float(e.start_ns + e.duration_ns)))
    for mods in modules.values():
        mods.sort()
    starts = {d: [s for s, _, _ in mods] for d, mods in modules.items()}
    program, scope = [], []
    for o in summary.ops:
        # a chip runs one program at a time: the last to start before the op
        i = bisect.bisect_right(starts.get(o.device, []), o.start) - 1
        mod = modules[o.device][i] if i >= 0 else None
        p = mod[2] if mod is not None and o.start < mod[1] else "unknown"
        program.append(p)
        scope.append(scopes.get(o.name, UNSCOPED) if p == step_program
                     else UNSCOPED)
    exclusive = [0.0] * len(summary.ops)
    for d in summary.busy_ns:
        idx = [i for i, o in enumerate(summary.ops) if o.device == d]
        ex = exclusive_ns([(summary.ops[i].start,
                            summary.ops[i].start + summary.ops[i].dur)
                           for i in idx])
        for i, x in zip(idx, ex):
            exclusive[i] = x
    return ScopedTrace(summary, step_program, program, scope, exclusive,
                       program_spans)
