"""Capped-exponential retry/backoff — the ONE copy in the repo.

The fleet scrape (obs/aggregate.pull_snapshot), the gateway's backend
attempts and the multi-host cluster join
(parallel/multihost.init_distributed) need the identical policy for
the identical reason — a transient connect failure must not condemn a
whole run on first strike. This module factors the schedule and the
retry loop so there is exactly one implementation.

Pure stdlib with no package-relative imports, so host-only processes
(the gateway) can use it without importing jax.
"""

from __future__ import annotations

import time
from typing import Callable, Optional, Sequence, Tuple, Type


def backoff_delay(attempt: int, base_s: float = 0.5,
                  cap_s: float = 120.0) -> float:
    """Delay before retry number ``attempt`` (1-based): base * 2^(n-1),
    capped. attempt=1 -> base, attempt=2 -> 2*base, ..."""
    if attempt < 1:
        raise ValueError(f"attempt is 1-based, got {attempt}")
    return min(float(base_s) * (2.0 ** (attempt - 1)), float(cap_s))


def full_jitter_delay(attempt: int, base_s: float = 0.5,
                      cap_s: float = 120.0,
                      rand: Optional[Callable[[], float]] = None) -> float:
    """AWS-style "full jitter" on the same capped-exponential
    schedule: uniform in [0, backoff_delay(attempt)]. Decorrelates
    retry storms — N clients that failed together do NOT retry
    together (the serving gateway's retry policy; tests pass a seeded
    ``rand`` for determinism)."""
    if rand is None:
        import random

        rand = random.random
    return rand() * backoff_delay(attempt, base_s, cap_s)


def retry_call(
    fn: Callable,
    *,
    retries: int = 3,
    base_s: float = 0.5,
    cap_s: float = 120.0,
    retry_on: Tuple[Type[BaseException], ...] = (OSError,),
    retriable: Optional[Callable[[BaseException], bool]] = None,
    describe: str = "operation",
    on_retry: Optional[Callable[[int, float, BaseException], None]] = None,
    sleep: Callable[[float], None] = time.sleep,
):
    """Call ``fn()``; on a retriable failure sleep the capped-exponential
    delay and try again, up to ``retries`` additional attempts.

    A failure is retried when it is an instance of ``retry_on`` AND
    (when given) ``retriable(exc)`` returns True — the predicate is how
    pull_snapshot retries transient URLErrors but not HTTP 4xx, which
    would fail identically forever. The last failure propagates
    unchanged so callers keep their typed exceptions. ``on_retry``
    observes each scheduled retry (attempt number, delay, exception) —
    loggers hook in there; this module deliberately has none.
    """
    attempt = 0
    while True:
        attempt += 1
        try:
            return fn()
        except retry_on as e:  # noqa: PERF203 — retry loop by design
            if attempt > retries or (retriable is not None
                                     and not retriable(e)):
                raise
            delay = backoff_delay(attempt, base_s, cap_s)
            if on_retry is not None:
                on_retry(attempt, delay, e)
            sleep(delay)


def delays(retries: int, base_s: float = 0.5,
           cap_s: float = 120.0) -> Sequence[float]:
    """The full schedule as a list (for logs/tests): retries=3,
    base_s=10 -> [10.0, 20.0, 40.0]."""
    return [backoff_delay(a, base_s, cap_s) for a in range(1, retries + 1)]
