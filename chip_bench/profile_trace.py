"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

A trace is read into flat :class:`Event` records (plane, line, name, start
and duration in ns); on the TPU the host's and the devices' events share
one clock.  The window is the host span ``bench.window`` that the harness
opens around the measured requests.  On each device plane:

* the ``XLA Ops`` line holds the operations the chip's cores run, control
  flow (``while``, ``conditional``) enclosing its body: their union inside
  the window is the device's busy time, the rest of the window its idle
  time, and each operation's own time is its span less its children's;
* the ``Async XLA Ops`` line holds copies and collectives in flight beside
  the cores' work; the union of the collectives on both lines is the
  device's exchange time.

Each idle gap is named by the innermost host event in flight at its middle
(a thread serving a request first), which says what the host was doing
while the device waited.
"""
from __future__ import annotations

import dataclasses
import re

__all__ = ["Event", "DeviceTime", "TraceSummary", "load_xplane", "summarize",
           "op_name", "COLLECTIVE"]

WINDOW_SPAN = "bench.window"
REQUEST_SPAN = "bench.spmv"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = "/host:CPU"
#: Opcodes of the operations that move data between chips.
COLLECTIVE = re.compile(r"^(all-to-all|all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute)")
_HLO = re.compile(r"^%?([\w.\-]+) = .*?(?<![\w\-])([a-z][a-z0-9\-]*)\(")


def op_name(text: str) -> tuple:
    """(instruction, opcode) of a device event named by its HLO text, as
    ``"%fusion.17 = f32[120000]{0} fusion(...)"``; other names are kept
    whole, with no opcode."""
    m = _HLO.match(text)
    return (m.group(1), m.group(2)) if m else (text, "")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: int          # ns
    dur: int            # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class DeviceTime:
    """One device's operations inside the window."""

    plane: str
    busy: list          # merged [start, end) intervals of XLA Ops, ns
    op_ns: dict         # "instruction (opcode)" -> own ns in the window
    collective: list    # merged intervals of collectives, both lines

    @property
    def busy_ns(self) -> int:
        return _length(self.busy)

    @property
    def collective_ns(self) -> int:
        return _length(self.collective)


@dataclasses.dataclass
class TraceSummary:
    window: tuple       # (start, end) ns on the trace's clock
    devices: list       # DeviceTime, one per device plane
    host: list          # host Events, for naming idle gaps

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def busiest(self) -> DeviceTime:
        return max(self.devices, key=lambda d: d.busy_ns)

    def idle_gaps(self, dev: DeviceTime, top: int | None = None) -> list:
        """[(name, ns)] of the ``top`` longest stretches of the window
        (all where None) with no operation running on ``dev``, longest
        first.  Only those are named: naming scans every host event, and
        a window of thousands of requests has thousands of gaps."""
        gaps, t = [], self.window[0]
        for s, e in dev.busy + [(self.window[1], self.window[1])]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        return [(self._host_doing((s + t) // 2), t - s) for s, t in gaps]

    def _host_doing(self, t: int) -> str:
        inside = [e for e in self.host if e.start <= t < e.end]
        serving = {e.line for e in inside if e.name == REQUEST_SPAN}
        if serving:
            inside = [e for e in inside if e.line in serving]
        return min(inside, key=lambda e: e.dur).name if inside else "none"

    def breakdown(self, top: int = 10) -> dict:
        """The busiest device's operations with the most own time and its
        longest idle gaps, in seconds."""
        dev = self.busiest()
        ops = sorted(dev.op_ns.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
                "idle_gaps": [[n, ns / 1e9]
                              for n, ns in self.idle_gaps(dev, top)]}


def load_xplane(path) -> list:
    """Every event of an ``.xplane.pb`` file as :class:`Event` records."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns)))
    return out


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _merge(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def _own_time(ops) -> dict:
    """Own ns per operation of nested [(start, end, name)] spans: each span
    less the spans directly inside it."""
    own: dict = {}
    stack: list = []                    # [end, name] of enclosing spans
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] = own.get(stack[-1][1], 0) - (e - s)
        own[name] = own.get(name, 0) + (e - s)
        stack.append([e, name])
    return own


def summarize(events) -> TraceSummary:
    """The window and, per device, its busy intervals, each operation's
    own time and its collective intervals."""
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"the trace holds {len(spans)} {WINDOW_SPAN!r} "
                         "spans, expected 1")
    w0, w1 = spans[0].start, spans[0].end
    ops: dict = {}
    collective: dict = {}
    for e in events:
        if not DEVICE_PLANE.match(e.plane) or e.line not in (OPS_LINE,
                                                             ASYNC_LINE):
            continue
        s, t = max(e.start, w0), min(e.end, w1)
        if t <= s:
            continue
        inst, opcode = op_name(e.name)
        if COLLECTIVE.match(opcode):
            collective.setdefault(e.plane, []).append((s, t))
        if e.line == OPS_LINE:
            ops.setdefault(e.plane, []).append(
                (s, t, f"{inst} ({opcode})" if opcode else inst))
    if not ops:
        raise ValueError("no device operation ran inside the window")
    devices = [DeviceTime(plane, _merge((s, t) for s, t, _ in ops[plane]),
                          _own_time(ops[plane]),
                          _merge(collective.get(plane, [])))
               for plane in sorted(ops)]
    host = [e for e in events
            if e.plane == HOST_PLANE and e.dur > 0 and e.end > w0
            and e.start < w1 and e.name != WINDOW_SPAN]
    return TraceSummary((w0, w1), devices, host)
