"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

A TPU trace holds one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops``
line has one event per executed HLO instruction, named by the instruction's
text (``%name = shape opcode(operands), ...``), and host planes whose lines
hold the benchmark's own ``bench.*`` spans.  Both are on one clock in
nanoseconds.  This module finds them by those names and gives busy and idle
time, time per instruction and per opcode, and the idle gaps with what the
host was doing in them.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPCODE = re.compile(r"= .*? ([a-z][a-z0-9\-]*)\(")
NAME = re.compile(r"^%([^ ]+) = ")
WINDOW_SPAN = "bench.window"


@dataclass
class Op:
    device: int
    name: str      # instruction name without the leading '%'
    opcode: str
    text: str      # the whole instruction text
    start: float   # ns
    dur: float     # ns


@dataclass
class TraceSummary:
    n_devices: int
    window: tuple[float, float]             # ns, on the trace's clock
    ops: list[Op] = field(default_factory=list)
    busy_ns: dict[int, float] = field(default_factory=dict)
    host_spans: list[tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        return sum(self.busy_ns.values()) * 1e-9 / max(self.n_devices, 1)

    def seconds(self, pred) -> float:
        """Summed device time of the ops ``pred`` accepts, per chip."""
        return sum(o.dur for o in self.ops if pred(o)) * 1e-9 / max(
            self.n_devices, 1)

    def top_ops(self, n: int = 10) -> list[list]:
        tot: dict[str, float] = {}
        for o in self.ops:
            key = re.sub(r"\.\d+$", "", o.name)
            tot[key] = tot.get(key, 0.0) + o.dur
        k = max(self.n_devices, 1)
        return [[name, ns * 1e-9 / k] for name, ns in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle time of the first chip, summed by the innermost host span
        open at each gap's midpoint (``host:none`` where none was)."""
        dev = min(self.busy_ns) if self.busy_ns else 0
        ivs = _union([(o.start, o.start + o.dur) for o in self.ops
                      if o.device == dev])
        lo, hi = self.window
        gaps, cur = [], lo
        for s, e in ivs:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            gaps.append((cur, hi))
        tot: dict[str, float] = {}
        for s, e in gaps:
            mid = 0.5 * (s + e)
            open_ = [(hs, nm) for nm, hs, he in self.host_spans
                     if hs <= mid <= he and nm != WINDOW_SPAN]
            name = max(open_)[1] if open_ else "host:none"
            tot[name] = tot.get(name, 0.0) + (e - s)
        return [[name, ns * 1e-9] for name, ns in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_trace(path: str, devices: set[int] | None = None) -> TraceSummary:
    """Read ``path``; keep the chips in ``devices`` (all when None).

    The window is the ``bench.window`` host span when the trace holds one,
    else from the first device op to the last; ops are clipped to it."""
    from jax.profiler import ProfileData

    pdata = ProfileData.from_file(path)
    raw: list[Op] = []
    spans: list[tuple[str, float, float]] = []
    seen: set[int] = set()
    for plane in pdata.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if devices is not None and dev not in devices:
                continue
            seen.add(dev)
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    text = e.name
                    nm = NAME.match(text)
                    op = OPCODE.search(text)
                    raw.append(Op(dev, nm.group(1) if nm else text,
                                  op.group(1) if op else "", text,
                                  float(e.start_ns), float(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.start_ns + e.duration_ns)))
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if win:
        lo, hi = win[0]
    elif raw:
        lo = min(o.start for o in raw)
        hi = max(o.start + o.dur for o in raw)
    else:
        lo = hi = 0.0
    ops = []
    for o in raw:
        s, e = max(o.start, lo), min(o.start + o.dur, hi)
        if e > s:
            o.start, o.dur = s, e - s
            ops.append(o)
    busy = {d: 0.0 for d in seen}
    for d in seen:
        busy[d] = sum(e - s for s, e in _union(
            [(o.start, o.start + o.dur) for o in ops if o.device == d]))
    return TraceSummary(n_devices=len(seen), window=(lo, hi), ops=ops,
                        busy_ns=busy, host_spans=spans)
