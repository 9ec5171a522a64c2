"""Host spans and observations of one run, written by the benchmark's
own drivers around their calls into the program.

A span is kept in memory on the host's monotonic clock and, at the same
time, written into the profiler's trace as a ``TraceAnnotation`` named
``bench:<name>`` so that a traced run has the drivers' spans on the
device trace's clock (that is what labels the idle gaps). Observations
are whatever a driver read from the program's counters or from its own
clock, by name; per-layer metric readers look them up and return
nothing when a name is absent."""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Iterator, List, Tuple

SPAN_PREFIX = "bench:"


class Recorder:
    def __init__(self) -> None:
        self.spans: List[Tuple[str, float, float]] = []
        self.obs: Dict[str, Any] = {}

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def seconds(self, name: str) -> float:
        """Total seconds of every span called ``name`` (0.0 if none)."""
        return sum(t1 - t0 for n, t0, t1 in self.spans if n == name)

    def observe(self, **kv: Any) -> None:
        self.obs.update(kv)


def counter_totals(names: Tuple[str, ...]) -> Dict[str, float]:
    """Sum over labels of each named counter family of the program's
    metrics registry (obs/metrics.py); a family that never ticked is 0."""
    from lightgbm_tpu.obs.metrics import default_registry

    snap = default_registry().snapshot()
    return {n: float(sum(snap.get(n, {}).values())) for n in names}
