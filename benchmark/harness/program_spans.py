"""The program's own spans in the profiler's trace, and the chip's idle
time inside the job spans split by them.

``lightgbm_tpu.timer.Timer.scope`` opens every instrumented host region
as a ``TraceAnnotation`` named ``lgbm:<name>``, so a traced run holds
the program's layer boundaries as host events on the device ops' clock,
nested on the thread that called ``lgb.train``. ``TraceView`` keeps only
the harness's own ``bench:`` events, so this module reads the newest
``*.xplane.pb`` of the cell again for the ``lgbm:`` events of the thread
line that holds the ``bench:job`` spans.

Idle is what ``engine.host_ms_per_tree`` counts: job wall time less the
time an op ran, averaged over the chips. Every instant of a job belongs
to its innermost program span (``trace._flatten``), every span to one
PART: the outermost of its enclosing spans that names one (so a part
includes its children), else ``other``. The parts are disjoint and cover
the jobs, so they sum to the job's idle time exactly. A program without
the spans (a parent commit) yields nothing, and every reader built on
this returns ``None`` for it."""

from __future__ import annotations

import bisect
import functools
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from . import cellrun
from . import trace as T
from .spans import SPAN_PREFIX

PROGRAM_PREFIX = "lgbm:"  # lightgbm_tpu.timer.TRACE_PREFIX
JOB_SPAN = "job"
OTHER = "other"
# part -> the spans that open it (names as docs/OBSERVABILITY.md pins them)
PARTS: Dict[str, Tuple[str, ...]] = {
    "booster_init": ("engine.booster_init",),
    "fused_start": ("boosting.fused_start",),
    "dispatch": ("fused dispatch",),
    "collect": ("fused collect (readback)",
                "materialize host trees (readback)"),
}
_PART_OF = {name: part for part, names in PARTS.items() for name in names}
NO_SPAN = "(no program span)"

Span = Tuple[str, float, float]  # name without the prefix, start, end (ns)


@dataclass
class Row:
    """One line of the table: all spans of one name inside the jobs."""

    name: str
    calls: int = 0
    wall_s: float = 0.0
    self_s: float = 0.0  # wall less the spans it encloses
    idle_s: float = 0.0  # idle while it was the innermost span


@dataclass
class Split:
    idle_s: Dict[str, float]  # by part, OTHER included: sums to job_idle_s
    job_idle_s: float
    jobs: int
    rows: List[Row]  # most idle first; the last is NO_SPAN
    trace_bytes: int = 0  # size of the trace file the spans came from

    @property
    def uncovered_idle_s(self) -> float:
        """Idle inside the jobs while no program span was open."""
        return self.rows[-1].idle_s


class _Cover:
    """Sorted disjoint intervals; the length of their part inside any
    [lo, hi) by bisection (a trace holds 1e5 busy intervals)."""

    def __init__(self, intervals: Sequence[T.Interval]):
        self.starts = [s for s, _ in intervals]
        self.ends = [e for _, e in intervals]
        self.cum = [0.0]
        for s, e in intervals:
            self.cum.append(self.cum[-1] + (e - s))

    def _upto(self, x: float) -> float:
        i = bisect.bisect_right(self.starts, x)
        if i == 0:
            return 0.0
        return self.cum[i - 1] + min(x, self.ends[i - 1]) - self.starts[i - 1]

    def within(self, lo: float, hi: float) -> float:
        return self._upto(hi) - self._upto(lo)


def attribute(jobs: Sequence[T.Interval], spans: Sequence[Span],
              busy: Sequence[Sequence[T.Interval]]) -> Split:
    """Split the idle time inside ``jobs`` by program span and by part.
    ``busy`` holds, per chip, the sorted disjoint intervals in which an
    op ran; ``spans`` are the program's spans of the jobs' thread."""
    covers = [_Cover(b) for b in busy]

    def idle(lo: float, hi: float) -> float:
        ran = sum(c.within(lo, hi) for c in covers) / max(len(covers), 1)
        return (hi - lo) - ran

    inside: List[Span] = []
    for lo, hi in jobs:
        inside.extend((n, max(s, lo), min(e, hi)) for n, s, e in spans
                      if min(e, hi) > max(s, lo))
    inside.sort(key=lambda sp: (sp[1], -sp[2]))

    rows: Dict[str, Row] = {}
    part: List[str] = []  # per span of `inside`
    stack: List[int] = []
    for i, (name, s, e) in enumerate(inside):
        while stack and inside[stack[-1]][2] <= s:
            stack.pop()
        above = part[stack[-1]] if stack else OTHER
        part.append(above if above != OTHER else _PART_OF.get(name, OTHER))
        stack.append(i)
        row = rows.setdefault(name, Row(name))
        row.calls += 1
        row.wall_s += (e - s) / 1e9

    job_idle = sum(idle(lo, hi) for lo, hi in jobs)
    by_part = dict.fromkeys(list(PARTS) + [OTHER], 0.0)
    covered = covered_idle = 0.0
    for a, b, i in T._flatten(inside):
        gap = idle(a, b)
        rows[inside[i][0]].self_s += (b - a) / 1e9
        rows[inside[i][0]].idle_s += gap / 1e9
        by_part[part[i]] += gap
        covered += b - a
        covered_idle += gap
    by_part[OTHER] += job_idle - covered_idle
    bare = Row(NO_SPAN,
               self_s=(T.total(jobs) - covered) / 1e9,
               idle_s=(job_idle - covered_idle) / 1e9)
    return Split(
        idle_s={p: ns / 1e9 for p, ns in by_part.items()},
        job_idle_s=job_idle / 1e9, jobs=len(jobs),
        rows=sorted(rows.values(), key=lambda r: -r.idle_s) + [bare])


def newest_trace(trace_dir: Path) -> Optional[Path]:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


@functools.lru_cache(maxsize=2)
def _read(path: str, _mtime_ns: int) -> Tuple[Span, ...]:
    from jax.profiler import ProfileData

    job = SPAN_PREFIX + JOB_SPAN
    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events
                      if e.name == job or e.name.startswith(PROGRAM_PREFIX)]
            if any(n == job for n, _, _ in events):
                out.extend((n[len(PROGRAM_PREFIX):], s, e)
                           for n, s, e in events if n != job)
    return tuple(sorted(out, key=lambda sp: (sp[1], -sp[2])))


def program_spans(path: Path) -> Tuple[Span, ...]:
    """The ``lgbm:`` events of the thread line(s) holding ``bench:job``
    spans, outermost first where they start together. One read per
    trace file, whichever reader asks first."""
    return _read(str(path), path.stat().st_mtime_ns)


def split_for(inp) -> Optional[Split]:
    """The split for a run's ``LayerInput``; ``None`` without a device
    trace, without job spans, or without program spans in the trace."""
    if inp.trace is None:
        return None
    jobs = inp.trace.spans_named(JOB_SPAN)
    path = newest_trace(cellrun.trace_dir(inp.cell))
    if not jobs or path is None:
        return None
    spans = program_spans(path)
    if not spans:
        return None
    split = attribute(jobs, spans,
                      [inp.trace.busy(i) for i in sorted(inp.trace.devices)])
    split.trace_bytes = path.stat().st_size
    return split


def idle_ms_per_tree(inp, part: str) -> Optional[float]:
    """What the five ``*.idle_*_ms_per_tree`` readers return."""
    trees = inp.rec.obs.get("trees")
    split = split_for(inp) if trees else None
    if split is None:
        return None
    return split.idle_s[part] / trees * 1e3


def table(split: Split) -> List[str]:
    """The lines the traced run logs: every program span inside the job
    spans, most idle first."""
    share = (split.uncovered_idle_s / split.job_idle_s * 100
             if split.job_idle_s else 0.0)
    out = [f"idle inside the {split.jobs} job spans by program span "
           f"({split.job_idle_s:.4f} s; {share:.2f}% of it under no "
           f"program span; trace file {split.trace_bytes} bytes):",
           f"  {'span':<40}{'calls':>6}{'wall s':>10}{'self s':>10}"
           f"{'idle s':>10}"]
    out += [f"  {r.name:<40}{r.calls:>6}{r.wall_s:>10.4f}{r.self_s:>10.4f}"
            f"{r.idle_s:>10.4f}" for r in split.rows]
    out.append("  by part: " + ", ".join(
        f"{p} {s:.4f}" for p, s in split.idle_s.items()))
    return out
