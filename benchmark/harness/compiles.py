"""Compiles and cache loads, counted from JAX's monitoring events.

The program's own counter (``analysis.retrace.compile_counters``) ticks
once per backend-compile event, and JAX fires that event around
``compile_or_get_cached`` — so it also ticks when the executable came out
of the persistent cache. The benchmark needs the two apart: a REAL
compile inside the window means a shape was not warmed up and the run
is not valid; a cache LOAD inside the window means the program traced
and lowered a step again and fetched its executable from disk (what a
new data-parallel Booster does on every ``lgb.train``: its fused step
is never memoized), which is recurring work that belongs to the job.
This module adds the one missing count (persistent-cache hits)."""

from __future__ import annotations

import threading
from typing import Dict

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_lock = threading.Lock()
_hits = 0
_installed = False


def _on_event(event: str, **_kw) -> None:
    global _hits
    if event == _HIT_EVENT:
        with _lock:
            _hits += 1


def install() -> None:
    """Start counting (idempotent); also starts the program's counter."""
    global _installed
    import jax
    from lightgbm_tpu.analysis.retrace import ensure_installed

    ensure_installed()
    with _lock:
        if _installed:
            return
        _installed = True
    jax.monitoring.register_event_listener(_on_event)


def counts() -> Dict[str, int]:
    """{"compiles": executables really compiled, "cache_loads":
    executables loaded from the persistent cache} since ``install``."""
    from lightgbm_tpu.analysis.retrace import compile_counters

    with _lock:
        hits = _hits
    return {"compiles": compile_counters()["backend_compiles"] - hits,
            "cache_loads": hits}


def delta(before: Dict[str, int]) -> Dict[str, int]:
    now = counts()
    return {k: now[k] - before[k] for k in now}
