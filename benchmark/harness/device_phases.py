"""Device time by the program's own phases.

``lightgbm_tpu.timer.device_phase`` opens ``jax.named_scope("lgbm.<phase>")``
around the sections of a boosting round, so every HLO instruction of the
compiled module carries the phase it was traced under in its
``metadata.op_name``. A v5e trace names an op by its HLO instruction,
and the trace file embeds the module that ran (``harness/xmeta.py``):
the join from a device event to the program's phase needs no event of
its own. The whole rule, in order, and nothing else:

(a) each ``XLA Ops`` event inside the window belongs to the ``XLA
    Modules`` event that encloses it in time on the same chip, hence to
    one embedded module (by name);
(b) its instruction is the name its event starts with, ``^%?([\\w.\\-]+) =``;
(c) its phase is the LAST match of ``lgbm\\.([a-z_.]+)`` in the
    instruction's ``op_name`` (scopes nest and the innermost names the
    op; a search, because under ``vmap`` the stack prints
    ``vmap(lgbm.learner.split_search)``);
(d) an instruction without a phase of its own that calls computations (a
    fusion, a ``call``) takes the phase that ALL phased instructions
    inside its called computations share, else its called computation's
    ROOT's;
(e) an instruction still without one (a compiler-made ``reduce-window``,
    a layout copy) takes the phase its operands' producers in the same
    computation have, by any rule, if all that have one agree (a
    cumulative sum becomes pad, copy, reduce-window, slice: the chain is
    followed back to the traced producer); its time is flagged
    ``inherited``. (d) and (e) pass over ``while`` and
    ``conditional``: their bodies are phases of their own, and their own
    time is loop plumbing;
(f) an instruction still without one takes the phase that the callers of
    its computation have by (c) (a ``while`` over its body and condition,
    a ``conditional`` over its branches, a ``call``), the nearest
    enclosing caller that has one, if all callers agree: the compiler
    expands ONE traced gather, scatter or sort into a loop whose body
    carries no ``op_name`` while the loop keeps the traced op's (the rank
    cell's gradient: 178k such events a tree); also ``inherited``;
(g) anything else is ``unscoped``: reported, never folded into a
    neighbour;
(h) for EVERY fusion, whichever rule named it, if the phased
    instructions inside carry more than one phase its time is ALSO
    booked in the side table ``mixed``, by the tuple of those phases;
(i) time is ``trace.self_times`` of the events clipped to the window (a
    ``while`` does not count its body twice), averaged over the chips.

Events that the histogram kernels' pattern matches belong to NO phase
whatever scope they were traced under: they are
``learner.hist_ms_per_tree`` already. So the phases, ``unscoped`` and the
kernels PARTITION the window's busy time:
    sum of the phase metrics + learner.hist_ms_per_tree
        = boosting.device_ms_per_tree.

A program whose modules hold no ``lgbm.`` token (a parent commit; an
executable loaded from a compile cache that an older program filled: the
cache key strips debug info) yields ``None`` from every reader, never 0."""

from __future__ import annotations

import bisect
import functools
import re
import time
from collections import Counter
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

from . import cellrun, program_spans, xmeta
from . import trace as T

PHASE_TOKEN = re.compile(r"lgbm\.([a-z_.]+)")  # timer.DEVICE_PREFIX
INSTRUCTION = re.compile(r"^%?([\w.\-]+) =")
UNSCOPED = "unscoped"
CONTROL_FLOW = ("while", "conditional")


def phase_of(op_name: str) -> Optional[str]:
    """Rule (c): the innermost phase of a JAX name stack."""
    found = PHASE_TOKEN.findall(op_name)
    return found[-1] if found else None


class ModulePhases:
    """Rules (c) to (f) and (h) over one embedded module."""

    def __init__(self, module: xmeta.Module):
        self.m = module
        self._own: Dict[str, Optional[str]] = {}
        self._inside: Dict[str, FrozenSet[str]] = {}
        self._around: Dict[int, Optional[str]] = {}
        self._resolved: Dict[str, Tuple[str, bool]] = {}
        self._callers: Dict[int, List[xmeta.Instruction]] = {}
        for inst in module.instructions.values():
            for comp in inst.called:
                self._callers.setdefault(comp, []).append(inst)

    def around(self, computation: int) -> Optional[str]:
        """Rule (f): the phase by (c) of the nearest enclosing callers
        of ``computation``, if they agree."""
        if computation not in self._around:
            self._around[computation] = None  # an entry has no caller
            found = {phase_of(c.op_name) or self.around(c.computation)
                     for c in self._callers.get(computation, ())}
            if len(found) == 1:
                self._around[computation], = found
        return self._around[computation]

    def inside(self, name: str) -> FrozenSet[str]:
        """Phases of the phased instructions inside the computations
        ``name`` calls (through nested fusions and calls)."""
        if name not in self._inside:
            self._inside[name] = frozenset()  # a cycle cannot occur; guard
            out = set()
            for comp in self.m.instructions[name].called:
                for inner in self.m.computations.get(comp, ()):
                    own = phase_of(self.m.instructions[inner].op_name)
                    out |= {own} if own else self.inside(inner)
            self._inside[name] = frozenset(out)
        return self._inside[name]

    def own(self, name: str) -> Optional[str]:
        """Rules (c) and (d)."""
        if name not in self._own:
            inst = self.m.instructions.get(name)
            phase = phase_of(inst.op_name) if inst else None
            if (inst and phase is None and inst.called
                    and inst.opcode not in CONTROL_FLOW):
                inside = self.inside(name)
                if len(inside) == 1:
                    phase, = inside
                elif inside:
                    root = self.m.roots.get(inst.called[0])
                    phase = self.own(root) if root else None
            self._own[name] = phase
        return self._own[name]

    def resolve(self, name: str) -> Tuple[str, bool]:
        """(phase or UNSCOPED, inherited by rule (e) or (f))."""
        if name not in self._resolved:
            # a module lists a computation's instructions operands first:
            # taken in that order, every producer is resolved before its
            # user and a long unnamed chain never becomes a deep recursion
            inst = self.m.instructions.get(name)
            for other in (self.m.computations.get(inst.computation, ())
                          if inst else (name,)):
                if other not in self._resolved:
                    self._resolved[other] = (UNSCOPED, False)  # no cycle
                    self._resolved[other] = self._resolve(other)
        return self._resolved[name]

    def _resolve(self, name: str) -> Tuple[str, bool]:
        phase = self.own(name)
        if phase:
            return phase, False
        inst = self.m.instructions.get(name)
        if inst is None:
            return UNSCOPED, False
        if inst.opcode not in CONTROL_FLOW:  # (e) passes over those
            producers = {self.resolve(o)[0] for o in inst.operands}
            producers.discard(UNSCOPED)
            if len(producers) == 1:
                return producers.pop(), True
        around = self.around(inst.computation)
        return (around, True) if around else (UNSCOPED, False)

    def mixed(self, name: str) -> Optional[Tuple[str, ...]]:
        """Rule (h)."""
        inst = self.m.instructions.get(name)
        if inst is None or inst.opcode != "fusion":
            return None
        inside = self.inside(name)
        return tuple(sorted(inside)) if len(inside) > 1 else None


@dataclass
class Row:
    phase: str
    events: float = 0.0
    ns: float = 0.0
    inherited_ns: float = 0.0
    est_bytes: float = 0.0  # XLA's bytes_accessed x executions
    ops: Dict[str, float] = field(default_factory=dict)  # short name: ns


@dataclass
class Table:
    """Everything per chip (sums over the chips / chips), ns."""

    rows: Dict[str, Row]  # by phase; UNSCOPED always present
    kernels_ns: float  # the histogram kernels' events
    self_ns: float  # all op self time in the window
    joined_ns: float  # ... whose event named an embedded instruction
    no_op_name_ns: float  # ... of it, instructions without an op_name
    mixed: Dict[Tuple[str, ...], float]
    has_tokens: bool  # some embedded module holds an lgbm. token
    chips: int
    busy_s: float = 0.0
    trace_bytes: int = 0
    read_s: float = 0.0

    def ns(self, phase: str) -> float:
        row = self.rows.get(phase)
        return row.ns if row else 0.0


def _enclosing(modules: Sequence[T.Event], ops: Sequence[T.Event]
               ) -> List[Optional[str]]:
    """Rule (a): per op, the name of the module event around it."""
    mods = sorted(modules, key=lambda e: e.start)
    starts = [m.start for m in mods]
    out: List[Optional[str]] = []
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        out.append(mods[i].name if i >= 0 and mods[i].end >= op.end
                   else None)
    return out


def attribute(devices: Dict[int, T.DevicePlane], lo: float, hi: float,
              modules: Dict[str, xmeta.Module], kernel_pattern: str,
              est_bytes: Optional[Dict[str, float]] = None) -> Table:
    """The table of one trace window. ``devices`` as ``TraceView`` holds
    them; ``modules`` as ``xmeta.read_modules`` returns them."""
    kernel = re.compile(kernel_pattern)
    est_bytes = est_bytes or {}
    phases = {name: ModulePhases(m) for name, m in modules.items()}
    rows: Dict[str, Row] = {UNSCOPED: Row(UNSCOPED)}
    mixed: Dict[Tuple[str, ...], float] = {}
    kernels = total = joined = bare = 0.0
    for plane in devices.values():
        ops = [T.Event(e.name, max(e.start, lo), min(e.end, hi))
               for e in plane.ops if e.end > lo and e.start < hi]
        by_module: Dict[Optional[str], List[T.Event]] = {}
        for op, mod in zip(ops, _enclosing(plane.modules, ops)):
            by_module.setdefault(mod, []).append(op)
        for mod, events in by_module.items():
            count = Counter(e.name for e in events)
            ph = phases.get(mod)
            for name, ns in T.self_times(events).items():
                total += ns
                m = INSTRUCTION.match(name)
                inst = ph.m.instructions.get(m.group(1)) if ph and m else None
                if inst is not None:
                    joined += ns
                    bare += 0.0 if inst.op_name else ns
                if kernel.search(name):
                    kernels += ns
                    continue
                phase, inherited = UNSCOPED, False
                if inst is not None:
                    phase, inherited = ph.resolve(inst.name)
                    pair = ph.mixed(inst.name)
                    if pair:
                        mixed[pair] = mixed.get(pair, 0.0) + ns
                row = rows.setdefault(phase, Row(phase))
                row.events += count[name]
                row.ns += ns
                row.inherited_ns += ns if inherited else 0.0
                if inst is None or inst.opcode not in CONTROL_FLOW:
                    row.est_bytes += est_bytes.get(name, 0.0) * count[name]
                short = T.short_name(name)
                row.ops[short] = row.ops.get(short, 0.0) + ns
    n = max(len(devices), 1)
    for row in rows.values():
        row.events /= n
        row.ns /= n
        row.inherited_ns /= n
        row.est_bytes /= n
        row.ops = {k: v / n for k, v in row.ops.items()}
    return Table(
        rows=rows, kernels_ns=kernels / n, self_ns=total / n,
        joined_ns=joined / n, no_op_name_ns=bare / n,
        mixed={k: v / n for k, v in mixed.items()},
        has_tokens=any(phase_of(i.op_name) for m in modules.values()
                       for i in m.instructions.values()),
        chips=len(devices))


@functools.lru_cache(maxsize=2)
def _read(path: str, _mtime_ns: int):
    buf = Path(path).read_bytes()  # once: a rank cell's trace is 160 MB
    return xmeta.parse_modules(buf), xmeta.bytes_accessed(buf)


@functools.lru_cache(maxsize=2)
def _table(path: str, mtime_ns: int, view: T.TraceView,
           kernel_pattern: str) -> Table:
    t0 = time.perf_counter()
    modules, est_bytes = _read(path, mtime_ns)
    table = attribute(view.devices, view.lo, view.hi, modules,
                      kernel_pattern, est_bytes)
    table.busy_s = view.busy_s()
    table.trace_bytes = Path(path).stat().st_size
    table.read_s = time.perf_counter() - t0
    return table


def table_of(path: Path, view: T.TraceView, kernel_pattern: str) -> Table:
    """The table of the trace file ``path``, whose events ``view`` holds.
    One read per file and view, whichever reader asks first."""
    return _table(str(path), path.stat().st_mtime_ns, view, kernel_pattern)


def table_for(inp) -> Optional[Table]:
    """The table for a run's ``LayerInput``; ``None`` without a device
    trace, or where no embedded module holds a phase."""
    if inp.trace is None:
        return None
    path = program_spans.newest_trace(cellrun.trace_dir(inp.cell))
    if path is None:
        return None
    pattern = inp.plugin("rooflines", "hist_round").KERNEL_PATTERN
    table = table_of(path, inp.trace, pattern)
    return table if table.has_tokens else None


def ms_per_tree(inp, phase: str) -> Optional[float]:
    """What the thirteen ``*_ms_per_tree`` phase readers return."""
    trees = inp.rec.obs.get("trees")
    table = table_for(inp) if trees else None
    if table is None:
        return None
    return table.ns(phase) / 1e6 / trees


def lines(table: Table, trees: float = 1.0, top: int = 5) -> List[str]:
    """The lines a traced run logs (ms per tree; with ``trees`` 1, ms
    in the window)."""
    def ms(ns: float) -> float:
        return ns / 1e6 / trees

    named = sum(r.ns for r in table.rows.values()) - table.ns(UNSCOPED)
    out = [
        f"device time by program phase, ms per tree over {trees:g} trees "
        f"on {table.chips} chip(s): busy {table.busy_s * 1e3 / trees:.3f} "
        f"= histogram kernels {ms(table.kernels_ns):.3f} + phases "
        f"{ms(named):.3f} + unscoped {ms(table.ns(UNSCOPED)):.3f} (sum of "
        f"self times {ms(table.self_ns):.3f}); "
        f"{ms(table.joined_ns):.3f} joined to an embedded instruction, "
        f"{ms(table.no_op_name_ns):.3f} of it without op_name; trace file "
        f"{table.trace_bytes} bytes read in {table.read_s:.2f} s",
        f"  {'phase':<24}{'events':>9}{'ms':>11}{'% busy':>8}"
        f"{'inherited':>11}{'est GB/s':>10}",
    ]
    busy_ns = table.busy_s * 1e9 or 1.0
    for row in sorted(table.rows.values(), key=lambda r: -r.ns):
        rate = row.est_bytes / row.ns if row.ns else 0.0  # B/ns = GB/s
        out.append(
            f"  {row.phase:<24}{row.events / trees:>9.1f}{ms(row.ns):>11.3f}"
            f"{row.ns / busy_ns * 100:>8.2f}{ms(row.inherited_ns):>11.3f}"
            f"{rate:>10.1f}")
        for name, ns in sorted(row.ops.items(), key=lambda kv: -kv[1])[:top]:
            out.append(f"      {ms(ns):>9.3f}  {name}")
    for pair, ns in sorted(table.mixed.items(), key=lambda kv: -kv[1]):
        out.append(f"  mixed {' + '.join(pair)}: {ms(ns):.3f}")
    return out
