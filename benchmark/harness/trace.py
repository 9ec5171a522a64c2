"""The reduction from a profiler trace (``*.xplane.pb``) to numbers.

Every device metric of the benchmark comes through here, so that each PR
computes the same number in the same way: the union of device-op
intervals (busy), its complement inside the window (idle gaps, labelled
by the benchmark span that covers them), per-op self time, and sums over
ops whose names match a pattern.

What a v5e trace looks like under jax 0.9.0 (looked at by hand with
``benchmark/tools/trace_inventory.py`` before the readers were written):
one plane per chip named ``/device:TPU:<n>`` with the lines ``XLA
Modules`` (one event per executable launch, named
``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one event per executed HLO op,
NAMED BY ITS WHOLE HLO INSTRUCTION, shapes and layouts included, e.g.
``%hist_round_tpu.8 = (f32[144,7140]{...}, ...) custom-call(...)``;
control flow such as ``while`` and ``conditional`` encloses its body's
ops) and ``Async XLA Ops`` (copy-start/-done, async slices and
collectives, which overlap the ops line); the host plane ``/host:CPU``
holds one line per thread, where ``jax.profiler.TraceAnnotation`` spans
appear under their own names. All planes share one clock. Busy time is
the ops line's; the async line is read only for collectives."""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .spans import SPAN_PREFIX

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"
# HLO collectives as the ops line names them (async pairs included:
# all-reduce-start / all-reduce-done)
COLLECTIVE_OPS = (r"^%?(all-reduce|reduce-scatter|all-gather|all-to-all|"
                  r"collective-permute)")

Interval = Tuple[float, float]  # [start, end) in ns


@dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    end: float  # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class DevicePlane:
    index: int
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)
    async_ops: List[Event] = field(default_factory=list)


# ------------------------------------------------------------- intervals
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def complement(cover: Sequence[Interval], lo: float, hi: float
               ) -> List[Interval]:
    """[lo, hi) less a sorted disjoint ``cover``."""
    out, at = [], lo
    for s, e in cover:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """Parts of sorted disjoint ``a`` not covered by sorted disjoint
    ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            k += 1
        out.extend(complement(b[j:k], s, e))
    return out


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Self time (ns) by op name: an event's duration less the events it
    encloses, so a ``while`` does not count its body twice."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [event, child_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][0].end <= upto:
            ev, child = stack.pop()
            out[ev.name] = out.get(ev.name, 0.0) + max(ev.dur - child, 0.0)
            if stack:
                stack[-1][1] += ev.dur

    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        close(ev.start)
        stack.append([ev, 0.0])
    close(float("inf"))
    return out


def leaf_events(events: Sequence[Event]) -> List[Event]:
    """Events that enclose no other event (the ops that do the work)."""
    out: List[Event] = []
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    for i, ev in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt.start >= ev.end or nxt.end > ev.end:
            out.append(ev)
    return out


def short_name(instruction: str) -> str:
    """An HLO instruction cut to what identifies it: name, result shape
    and opcode, without layouts or operands."""
    head = re.match(r"^(.*?\s[\w-]+)\(", instruction)
    text = head.group(1) if head else instruction
    return re.sub(r"\{[^{}]*\}", "", text)[:160]


# ------------------------------------------------------------- the trace
class TraceView:
    """One trace, clipped to the benchmark's ``window`` span."""

    def __init__(self, devices: Dict[int, DevicePlane],
                 host_spans: List[Tuple[str, float, float]]):
        self.devices = devices
        self.host_spans = host_spans
        self._busy: Dict[int, List[Interval]] = {}
        win = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
        if win:
            self.lo, self.hi = win[0][0], win[-1][1]
        else:  # a recorded fixture without the span: the ops' extent
            evs = [e for d in devices.values() for e in d.ops]
            self.lo = min((e.start for e in evs), default=0.0)
            self.hi = max((e.end for e in evs), default=0.0)

    # -- loading
    @classmethod
    def from_file(cls, path: Path) -> "TraceView":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(str(path))
        devices: Dict[int, DevicePlane] = {}
        spans: List[Tuple[str, float, float]] = []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                dp = devices.setdefault(int(m.group(2)),
                                        DevicePlane(int(m.group(2))))
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        for ev in line.events:
                            dp.ops.append(Event(
                                ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
                    elif line.name in (MODULES_LINE, ASYNC_LINE):
                        (dp.modules if line.name == MODULES_LINE
                         else dp.async_ops).extend(
                            Event(ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns)
                            for ev in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            spans.append((
                                ev.name[len(SPAN_PREFIX):], ev.start_ns,
                                ev.start_ns + ev.duration_ns))
        spans.sort(key=lambda s: (s[1], -s[2]))
        return cls(devices, spans)

    @classmethod
    def newest_under(cls, trace_dir: Path) -> Optional["TraceView"]:
        found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
        return cls.from_file(found[-1]) if found else None

    # -- whole-window numbers
    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy(self, dev: int) -> List[Interval]:
        if dev not in self._busy:
            self._busy[dev] = union(clip(
                ((e.start, e.end) for e in self.devices[dev].ops),
                self.lo, self.hi))
        return self._busy[dev]

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips used."""
        if not self.devices:
            return 0.0
        return sum(total(self.busy(i)) for i in self.devices) / 1e9 / len(
            self.devices)

    def busy_in(self, lo: float, hi: float) -> float:
        """Busy seconds inside [lo, hi) ns, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(total(clip(self.busy(i), lo, hi))
                   for i in self.devices) / 1e9 / len(self.devices)

    def spans_named(self, name: str) -> List[Interval]:
        return clip(((s, e) for n, s, e in self.host_spans if n == name),
                    self.lo, self.hi)

    # -- sums over matching ops
    def ops_matching(self, dev: int, pattern: str) -> List[Event]:
        """Ops of chip ``dev`` inside the window whose name (the whole
        HLO instruction) matches ``pattern``."""
        rx = re.compile(pattern)
        events = self.devices[dev].ops
        names = {n for n in {e.name for e in events} if rx.search(n)}
        return [e for e in events if e.name in names
                and e.end > self.lo and e.start < self.hi]

    def op_seconds(self, pattern: str) -> Tuple[float, int]:
        """(seconds, events) of ops matching ``pattern``, averaged over
        the chips (each chip runs the same program)."""
        if not self.devices:
            return 0.0, 0
        evs = [self.ops_matching(i, pattern) for i in self.devices]
        n = len(self.devices)
        return (sum(e.dur for es in evs for e in es) / 1e9 / n,
                sum(len(es) for es in evs) // n)

    def _collective(self, dev: int) -> List[Interval]:
        """Intervals of chip ``dev`` in which a collective was in flight
        (ops line and async line together)."""
        rx = re.compile(COLLECTIVE_OPS)
        d = self.devices[dev]
        return union(clip(((e.start, e.end) for e in d.ops + d.async_ops
                           if rx.search(e.name)), self.lo, self.hi))

    def collective_s(self) -> float:
        """Seconds with a collective in flight, averaged over the chips."""
        return sum(total(self._collective(i)) for i in self.devices
                   ) / 1e9 / max(len(self.devices), 1)

    def exposed_collective_s(self) -> float:
        """Seconds in which a chip had a collective in flight and ran no
        other leaf op, averaged over the chips."""
        rx = re.compile(COLLECTIVE_OPS)
        exposed = 0.0
        for i, d in self.devices.items():
            work = union(clip(((e.start, e.end) for e in leaf_events(d.ops)
                               if not rx.search(e.name)),
                              self.lo, self.hi))
            exposed += total(subtract(self._collective(i), work))
        return exposed / 1e9 / max(len(self.devices), 1)

    # -- the breakdown
    def top_ops(self, k: int = 10) -> List[List]:
        """[[name, seconds]] of the ops with most self time, averaged
        over the chips."""
        acc: Dict[str, float] = {}
        for d in self.devices.values():
            inside = [e for e in d.ops
                      if e.end > self.lo and e.start < self.hi]
            for name, ns in self_times(inside).items():
                acc[name] = acc.get(name, 0.0) + ns
        n = max(len(self.devices), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[short_name(name), ns / 1e9 / n] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """[[label, seconds]]: idle seconds of the first chip inside the
        window, summed by what the host was doing (the innermost
        benchmark span covering each piece of a gap, with where in that
        span the piece lies: before its first device op, after its last,
        or between two)."""
        if not self.devices:
            return []
        dev = min(self.devices)
        busy = self.busy(dev)
        gaps = complement(busy, self.lo, self.hi)
        acc: Dict[str, float] = {}
        for s, e, label in _label_pieces(gaps, self.host_spans, busy):
            acc[label] = acc.get(label, 0.0) + (e - s)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[label, ns / 1e9] for label, ns in top]


def _flatten(spans: Sequence[Tuple[str, float, float]]
             ) -> List[Tuple[float, float, int]]:
    """Disjoint pieces (start, end, index of the innermost span covering
    the piece): where spans overlap, the one that started last wins."""
    points = sorted({p for _, s, e in spans for p in (s, e)})
    order = sorted(range(len(spans)), key=lambda i: spans[i][1])
    out: List[Tuple[float, float, int]] = []
    active: List[int] = []
    nxt = 0
    for a, b in zip(points, points[1:]):
        while nxt < len(order) and spans[order[nxt]][1] <= a:
            active.append(order[nxt])
            nxt += 1
        active = [i for i in active if spans[i][2] > a]
        if active:
            out.append((a, b, max(active, key=lambda i: spans[i][1])))
    return out


def _label_pieces(gaps: Sequence[Interval],
                  host_spans: Sequence[Tuple[str, float, float]],
                  busy: Sequence[Interval]):
    """Yield (start, end, label) pieces covering ``gaps``."""
    spans = [s for s in host_spans if s[0] != WINDOW_SPAN]
    pieces = _flatten(spans)
    starts = [s for s, _ in busy]
    ends = [e for _, e in busy]

    def any_busy(lo: float, hi: float) -> bool:
        i = bisect.bisect_right(ends, lo)
        return i < len(starts) and starts[i] < hi

    pi = 0
    for gs, ge in gaps:
        at = gs
        while pi < len(pieces) and pieces[pi][1] <= gs:
            pi += 1
        j = pi
        while j < len(pieces) and pieces[j][0] < ge:
            ps, pe, idx = pieces[j]
            lo, hi = max(ps, gs), min(pe, ge)
            if lo > at:
                yield at, lo, "no benchmark span"
            name, ss, se = spans[idx]
            before = any_busy(ss, lo)
            after = any_busy(hi, se)
            where = ("between device ops" if before and after
                     else "before first device op" if after
                     else "after last device op" if before
                     else "no device op in span")
            yield lo, hi, f"{name}: {where}"
            at = hi
            j += 1
        if at < ge:
            yield at, ge, "no benchmark span"
