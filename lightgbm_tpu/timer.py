"""Named per-phase accumulating timers (the reference's USE_TIMETAG
subsystem: Timer/FunctionTimer, utils/common.h:979-1043, global_timer
printed at exit, per-phase instrumentation across the tree learner and
network layers — SURVEY §5).

TPU adaptation: phases are HOST-side regions (dispatch, collect,
binning, eval). Device work inside jit is asynchronous, so a scope that
must include device completion passes `block=True` to synchronize
before stopping the clock (used by bench/profilers, off in production
paths).

Every scope is also a `jax.profiler.TraceAnnotation` named
`lgbm:<name>`: while a profiler session is active (`jax.profiler.
start_trace`, the `profile_dir` param, the benchmark's `--trace 1`) it
is a host event in the profiler's own trace, on the device ops' clock,
nested under the scopes that enclose it on its thread. The session is
the switch; with none active an annotation costs well under a
microsecond and records nothing.

Scopes are HOST spans. DEVICE work is named from inside the program by
`device_phase` (below): a `jax.named_scope` whose name rides the
compiled module's `op_name` metadata, which the profiler's trace embeds
beside the ops it timed. No scope here opens one: the compiled module
does not depend on whether a timer is enabled.

Enable summary-at-exit with env LIGHTGBM_TPU_TIMETAG=1 (the analog of
the reference's compile-time USE_TIMETAG), with the `timetag` config /
CLI param, or at runtime via `global_timer.enable()` — unlike the
reference's compile-time flag, timing can be turned on and off without
restarting the process.

While an obs.tracing recorder is active, every scope additionally
records a Chrome trace-event span (the recorder installs itself here
through `set_trace_sink`), so the phase table, the recorder's timeline
and the profiler's host events all carry the same names.
"""

from __future__ import annotations

import atexit
import os
import threading
import time
from contextlib import contextmanager
from typing import Callable, Dict, Iterator, List, Optional

# prefix of this program's host events in a profiler trace ("bench:" is
# the benchmark harness's)
TRACE_PREFIX = "lgbm:"

# prefix of this program's DEVICE phases: the name-stack entries that
# `device_phase` puts into the `op_name` of every op traced inside it
DEVICE_PREFIX = "lgbm."
# the whole vocabulary (docs/OBSERVABILITY.md "Device phases" says where
# each opens and which benchmark metric reads it). Scopes nest; the
# innermost names the op.
DEVICE_PHASES = (
    "objective.gradients",
    "learner.quantize",
    "learner.select",
    "learner.route",
    "learner.hist",
    "parallel.reduce",
    "learner.subtract",
    "learner.split_search",
    "learner.pool_write",
    "boosting.renew",
    "boosting.score_update",
    "metrics.valid_eval",
)


def device_phase(name: str):
    """`jax.named_scope("lgbm.<name>")`: every op TRACED inside carries
    the phase in its `op_name`, the name stack XLA keeps as metadata of
    the compiled module. No op, no barrier, the same executable. A
    profiler trace embeds the module it ran (plane `/host:metadata`),
    so a trace's `XLA Ops` events, which are named by HLO instruction,
    map back to the phase that traced them
    (`benchmark/harness/device_phases.py`). A name outside
    `DEVICE_PHASES` fails where the scope is opened, at trace time."""
    import jax

    if name not in DEVICE_PHASES:
        raise KeyError(f"unknown device phase {name!r}; the vocabulary "
                       f"is timer.DEVICE_PHASES")
    return jax.named_scope(DEVICE_PREFIX + name)

# active span sinks: obs.tracing installs `(name, start_s, dur_s) ->
# None` here while recording, and obs.recorder adds its per-round
# phase accumulator alongside (module attributes, not Timer fields, so
# the subscribers observe every Timer instance). `set_trace_sink`
# keeps its original single-slot semantics for obs.tracing; extra
# subscribers ride `add_trace_sink`/`remove_trace_sink`.
_trace_sinks: tuple = ()
_primary_sink: Optional[Callable[[str, float, float], None]] = None


def set_trace_sink(
    sink: Optional[Callable[[str, float, float], None]]
) -> None:
    """Install (or clear, with None) the span recorder scopes report
    to. Owned by obs.tracing; exposed here so timer stays a leaf
    module with no obs import. Replaces only the slot it owns — sinks
    added through add_trace_sink are unaffected."""
    global _trace_sinks, _primary_sink
    sinks = [s for s in _trace_sinks if s is not _primary_sink]
    _primary_sink = sink
    if sink is not None:
        sinks.append(sink)
    _trace_sinks = tuple(sinks)


def add_trace_sink(sink: Callable[[str, float, float], None]) -> None:
    """Subscribe an additional span sink (obs.recorder's per-round
    phase accumulator); idempotent."""
    global _trace_sinks
    if sink not in _trace_sinks:
        _trace_sinks = _trace_sinks + (sink,)


def remove_trace_sink(sink: Callable[[str, float, float], None]) -> None:
    # equality, not identity: a bound method is a fresh object on each
    # attribute access, so `is` would never match the stored sink
    global _trace_sinks
    _trace_sinks = tuple(s for s in _trace_sinks if s != sink)


def _sync_devices() -> None:
    """Barrier: wait for completion of all work dispatched so far on
    EVERY local device (the old hack synced one op on the default
    device only — a sharded computation's other shards kept running).
    Each device executes its stream in order, so blocking on a tiny
    computation enqueued per device flushes everything before it."""
    import jax

    jax.effects_barrier()
    for d in jax.local_devices():
        (jax.device_put(0, d) + 0).block_until_ready()


class Timer:
    """Accumulating named stopwatches (reference utils/common.h:979)."""

    def __init__(self) -> None:
        self._acc: Dict[str, float] = {}
        self._cnt: Dict[str, int] = {}
        self.enabled = os.environ.get("LIGHTGBM_TPU_TIMETAG", "") not in ("", "0")
        self._summary_at_exit = self.enabled

    def enable(self, summary_at_exit: bool = True) -> None:
        """Turn timing on at runtime (config/CLI `timetag` hook); the
        at-exit summary registers once."""
        self.enabled = True
        if summary_at_exit and not self._summary_at_exit:
            self._summary_at_exit = True
            atexit.register(self.print_summary)

    def disable(self) -> None:
        self.enabled = False

    @contextmanager
    def scope(self, name: str, block: bool = False) -> Iterator[None]:
        """Time a region; with block=True waits for completion of all
        dispatched device work (every local device) before stopping
        the clock, so the region includes its dispatched work.

        The region is always a `TraceAnnotation` (module docstring);
        the stopwatch and the sinks run only when the timer is enabled
        or a sink is installed. A scope is a HOST span and names no
        device work: what is traced inside it compiles to the same
        module, `op_name`s included, whether the timer is on or off
        (device work is named by `device_phase`)."""
        import jax

        with jax.profiler.TraceAnnotation(TRACE_PREFIX + name):
            sinks = _trace_sinks
            if not self.enabled and not sinks:
                yield
                return
            t0 = time.perf_counter()
            yield
            if block:
                _sync_devices()
            dt = time.perf_counter() - t0
            if self.enabled:
                self._acc[name] = self._acc.get(name, 0.0) + dt
                self._cnt[name] = self._cnt.get(name, 0) + 1
            for sink in sinks:
                sink(name, t0, dt)

    def add(self, name: str, seconds: float,
            start: Optional[float] = None) -> None:
        """Record an externally-timed region: accumulates like scope()
        and reports to the active trace sink (`start` is the region's
        time.perf_counter() start, for span placement). The region is
        over when this is called, so it cannot be a `TraceAnnotation`:
        it does not appear in a profiler trace (one caller, the eager
        loop's score update)."""
        if self.enabled:
            self._acc[name] = self._acc.get(name, 0.0) + seconds
            self._cnt[name] = self._cnt.get(name, 0) + 1
        sinks = _trace_sinks
        if sinks:
            if start is None:
                start = time.perf_counter() - seconds
            for sink in sinks:
                sink(name, start, seconds)

    def summary(self) -> Dict[str, tuple]:
        return {
            k: (self._acc[k], self._cnt[k])
            for k in sorted(self._acc, key=lambda k: -self._acc[k])
        }

    def print_summary(self) -> None:
        """common.h:1012 — per-phase totals at exit."""
        from . import log

        if not self._acc:
            return
        log.info("LightGBM-TPU phase timings:")
        for name, (acc, cnt) in self.summary().items():
            log.info(f"  {name}: {acc:.3f}s ({cnt} calls)")

    def reset(self) -> None:
        self._acc.clear()
        self._cnt.clear()


global_timer = Timer()

if global_timer.enabled:
    atexit.register(global_timer.print_summary)


def enable_timetag() -> None:
    """Config/CLI hook (`timetag=true`): turn on the global phase timer
    mid-process (engine.train and cli.main both route here)."""
    global_timer.enable()


class LatencyStats:
    """Latency/throughput counters for serving paths.

    Unlike Timer scopes (accumulating host-region stopwatches for
    training phases), serving needs DISTRIBUTION statistics — a p99
    regression hides completely in an accumulated total. Keeps a ring
    of the most recent `window` request latencies plus lifetime count /
    row totals; `snapshot()` derives mean/p50/p95/p99 over the ring and
    rows/sec over the lifetime. Thread-safe: the serving server and the
    microbatch worker observe from different threads.
    """

    def __init__(self, window: int = 2048) -> None:
        self._window = int(window)
        self._ring: List[float] = []
        self._pos = 0
        self._count = 0
        self._rows = 0
        self._total_s = 0.0
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()

    def observe(self, seconds: float, rows: int = 1) -> None:
        with self._lock:
            if len(self._ring) < self._window:
                self._ring.append(float(seconds))
            else:
                self._ring[self._pos] = float(seconds)
                self._pos = (self._pos + 1) % self._window
            self._count += 1
            self._rows += int(rows)
            self._total_s += float(seconds)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            ring = sorted(self._ring)
            count, rows, total = self._count, self._rows, self._total_s
            uptime = time.perf_counter() - self._t0

        def pct(p: float) -> float:
            if not ring:
                return 0.0
            return ring[min(len(ring) - 1, int(p * (len(ring) - 1) + 0.5))]

        # mean over the same ring the percentiles cover — a lifetime
        # mean would stay inflated by cold-start outliers forever and
        # read as mean >> p99 on a warmed-up server
        mean = sum(ring) / len(ring) if ring else 0.0
        return {
            "count": count,
            "rows": rows,
            "mean_ms": round(1e3 * mean, 4),
            "p50_ms": round(1e3 * pct(0.50), 4),
            "p95_ms": round(1e3 * pct(0.95), 4),
            "p99_ms": round(1e3 * pct(0.99), 4),
            "rows_per_sec": round(rows / uptime, 2) if uptime > 0 else 0.0,
            "busy_frac": round(total / uptime, 4) if uptime > 0 else 0.0,
        }

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
            self._pos = 0
            self._count = 0
            self._rows = 0
            self._total_s = 0.0
            self._t0 = time.perf_counter()


_latency: Dict[str, LatencyStats] = {}
_latency_lock = threading.Lock()


def latency_stats(name: str, model: Optional[str] = None) -> LatencyStats:
    """Named process-global LatencyStats (one per serving entry point,
    mirroring global_timer's named-scope registry). Each named ring
    registers itself on the obs metrics registry at creation, so
    `/metrics` scrapes and `ModelRegistry.stats()` read the SAME
    object — one source of truth for serving latency. ``model`` tags
    the exported series with a ``{model=...}`` label (fleet tenants;
    docs/OBSERVABILITY.md)."""
    with _latency_lock:
        created = name not in _latency
        if created:
            _latency[name] = LatencyStats()
        stats = _latency[name]
    if created:
        from .obs.metrics import register_latency_collector

        register_latency_collector(name, stats, model=model)
    return stats


def latency_summary() -> Dict[str, Dict[str, float]]:
    with _latency_lock:
        return {k: v.snapshot() for k, v in sorted(_latency.items())}
