"""Retrace guard: fail fast on unexpected jit recompiles + tracer leaks.

Every perf regression class this repo has hit so far — executable
bloat, fused-step cache staleness, per-iteration retraces from an
unhashable static or a drifting shape — shows up FIRST as an
unexpected jit cache miss. This module counts them:

- globally, through a `jax.monitoring` duration-event listener
  (`/jax/core/compile/jaxpr_trace_duration` fires once per trace,
  `backend_compile_duration` once per XLA compile);
- per entry point, through the `_cache_size()` of jitted callables.

`retrace_guard` is a context manager; `tests/conftest.py` wires it in
as the `retrace_guard` pytest fixture. `jax.checking_leaks` (tracer
leak detection) can be enabled on the same guard.

    with retrace_guard(entry_points=[grow_tree_rounds], max_retraces=1):
        train_two_iterations()   # second iteration must reuse the trace

The listener counts for the whole process lifetime once installed (an
int increment per trace/compile event — events fire per compilation,
not per dispatch, so the idle cost is nil): guards read deltas, and
`compile_counters()` exposes the running totals to the run manifest
(obs/manifest.py). It also keeps the SECONDS JAX hands it with each
event — tracing, lowering, backend compile, persistent-cache
retrieval — so a set-up time can be split into what it was spent on.
Install happens on the first guard or explicitly via
`ensure_installed()` (cli.py does this when a manifest or profile is
requested, so the counts cover the run from the start).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple


class RetraceError(AssertionError):
    """An entry point retraced (or the process compiled) more than the
    guard allows."""


_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# duration events whose seconds are kept, by their compile_counters() key
_SECONDS_KEYS = {
    _TRACE_EVENT: "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    _COMPILE_EVENT: "backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
}

_lock = threading.Lock()
_installed = False
_counters: Dict[str, int] = {_TRACE_EVENT: 0, _COMPILE_EVENT: 0}
_seconds: Dict[str, float] = dict.fromkeys(_SECONDS_KEYS, 0.0)
# per (thread, event): (start, seconds) of the regions counted so far
# that a region still open may enclose. JAX fires the trace event for
# every jitted function traced INSIDE another trace too, inner before
# outer (a training step's inner regions summed to twice its wall
# time), so when an enclosing region arrives the regions it holds are
# taken back out: the total is the union, wall seconds on that thread.
# One entry per top-level region stays behind — per compilation, not
# per dispatch.
_counted: Dict[Tuple[int, str], List[Tuple[float, float]]] = {}


def _listener(event: str, duration: float, **kwargs: Any) -> None:
    if event not in _seconds:
        return
    # the event fires as its region ends
    start = time.perf_counter() - duration
    with _lock:
        if event in _counters:
            _counters[event] += 1
        inner = _counted.setdefault((threading.get_ident(), event), [])
        while inner and inner[-1][0] >= start:
            _seconds[event] -= inner.pop()[1]
        _seconds[event] += duration
        inner.append((start, duration))


def _install() -> None:
    global _installed
    with _lock:
        if _installed:
            return
        import jax

        jax.monitoring.register_event_duration_secs_listener(_listener)
        _installed = True


def ensure_installed() -> None:
    """Start counting trace/compile events now (idempotent). Call early
    when compile counts should cover the whole run — the manifest's
    numbers only include events after installation."""
    _install()


def compile_counters() -> Dict[str, float]:
    """Process-lifetime (since install) jaxpr-trace and backend-compile
    event totals, and the seconds of tracing (`trace_s`), lowering to
    MLIR (`lower_s`), backend compile (`backend_compile_s`) and
    persistent-cache retrieval (`cache_load_s`) — the run manifest's
    compile section. JAX fires the backend-compile event around
    compile-or-load-from-cache, so `backend_compiles` includes the
    cache hits and `backend_compile_s` includes `cache_load_s`: the
    difference is what XLA / Mosaic really compiled."""
    with _lock:
        out: Dict[str, float] = {
            "jaxpr_traces": _counters[_TRACE_EVENT],
            "backend_compiles": _counters[_COMPILE_EVENT],
            "listener_installed": int(_installed),
        }
        for event, key in _SECONDS_KEYS.items():
            out[key] = _seconds[event]
        return out


def _cache_size(fn: Any) -> Optional[int]:
    """Trace-cache entry count of a jitted callable (None if the
    callable exposes no cache — plain functions pass through)."""
    size = getattr(fn, "_cache_size", None)
    if callable(size):
        try:
            return int(size())
        except Exception:  # noqa: BLE001 — cache introspection only
            return None
    return None


class GuardReport:
    """Mutable result the context manager fills at exit."""

    def __init__(self) -> None:
        self.traces = 0
        self.compiles = 0
        self.per_entry: Dict[str, int] = {}

    def __repr__(self) -> str:
        return (
            f"GuardReport(traces={self.traces}, compiles={self.compiles}, "
            f"per_entry={self.per_entry})"
        )


@contextlib.contextmanager
def retrace_guard(
    entry_points: Sequence[Any] = (),
    max_retraces: int = 0,
    check_leaks: bool = False,
    what: str = "guarded region",
) -> Iterator[GuardReport]:
    """Fail with RetraceError when jit caches miss more than allowed.

    entry_points: jitted callables — each one's `_cache_size()` may
        grow by at most `max_retraces` inside the guard. With no entry
        points, the GLOBAL trace count is bounded instead (any jit
        tracing anywhere counts, including first-call traces — use
        entry points after a warmup call for precise contracts).
    check_leaks: also run the body under `jax.checking_leaks()` so
        tracers escaping a trace raise immediately. The leak-check
        config is part of the jit cache key, so cached entry points
        RETRACE by design under it — raise max_retraces accordingly
        when combining it with entry_points.
    """
    import jax

    _install()
    report = GuardReport()
    names: List[str] = []
    before_entry: List[Optional[int]] = []
    for fn in entry_points:
        names.append(getattr(fn, "__name__", repr(fn)))
        before_entry.append(_cache_size(fn))
    with _lock:
        before = dict(_counters)
    try:
        ctx = jax.checking_leaks() if check_leaks else contextlib.nullcontext()
        with ctx:
            yield report
    finally:
        with _lock:
            report.traces = _counters[_TRACE_EVENT] - before[_TRACE_EVENT]
            report.compiles = (
                _counters[_COMPILE_EVENT] - before[_COMPILE_EVENT]
            )
    offenders: List[str] = []
    for fn, name, b in zip(entry_points, names, before_entry):
        after = _cache_size(fn)
        if b is None or after is None:
            continue
        grew = after - b
        report.per_entry[name] = grew
        if grew > max_retraces:
            offenders.append(
                f"{name}: {grew} new trace-cache entr"
                f"{'y' if grew == 1 else 'ies'} (allowed {max_retraces})"
            )
    # checking_leaks alters the trace-context cache key, forcing fresh
    # traces by design — the global bound only means something without it
    if not entry_points and not check_leaks \
            and report.traces > max_retraces:
        offenders.append(
            f"global: {report.traces} jaxpr traces "
            f"(allowed {max_retraces})"
        )
    if offenders:
        raise RetraceError(
            f"unexpected retrace in {what}: " + "; ".join(offenders)
            + " — a shape/dtype/static argument is drifting between "
            "calls, or a traced value is used as a cache key"
        )
