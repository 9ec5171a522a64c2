"""Bucket-batched scoring dispatcher + thread-safe microbatch queue.

Serving traffic arrives in arbitrary batch sizes; a jit cache keyed on
raw shapes would compile once per distinct size (the classic shape-
churn retrace). The dispatcher pads every request up to a small fixed
ladder of row counts, so the number of XLA compiles is bounded by the
ladder length — a contract the retrace guard asserts in
tests/test_serving.py across a 100-request mixed-size sequence
(analysis/retrace.py). Oversized batches are chunked into max-bucket
pieces, so no request shape ever escapes the ladder.

``warmup()`` precompiles every bucket up front (scoring zeros), moving
all compile latency out of the serving path — the analog of the
reference's SingleRowPredictor being built once per model
(c_api.cpp:66), but per shape instead of per row.

``MicroBatcher`` is the queueing half: callers ``submit()`` rows from
any thread and get a Future; a single worker drains the queue,
coalesces pending requests into one padded device call, and fans the
rows of the result back out. Under concurrent small-batch load this
turns q tiny dispatches into one bucket-sized dispatch.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from .. import log
from ..config import DEFAULT_SERVE_BUCKETS as DEFAULT_BUCKETS
from ..obs.metrics import (
    record_bucket_dispatch,
    record_coalesce,
    record_host_fallback,
    record_queue_depth,
    record_serve_rejection,
)
from ..resilience.errors import (
    DeadlineExceeded,
    InjectedFault,
    QueueOverflow,
    ShutdownError,
)
from ..resilience.faultinject import fault_point
from ..timer import latency_stats


class BucketDispatcher:
    """Pads requests to a fixed shape ladder and scores on device."""

    def __init__(self, forest, buckets: Sequence[int] = DEFAULT_BUCKETS,
                 name: str = "serve", model: Optional[str] = None):
        if not buckets:
            raise ValueError("need at least one bucket size")
        n_dev = max(int(getattr(forest, "num_devices", 1)), 1)
        # every rung must shard evenly over the mesh row axis
        aligned = sorted({
            ((max(int(b), 1) + n_dev - 1) // n_dev) * n_dev for b in buckets
        })
        if list(aligned) != sorted(int(b) for b in buckets):
            log.warning(
                f"serving buckets {sorted(int(b) for b in buckets)} "
                f"realigned to {aligned} (mesh of {n_dev} devices needs "
                "row counts divisible by the device count)"
            )
        self.buckets: Tuple[int, ...] = tuple(aligned)
        self.forest = forest
        self.name = name
        # model tags this entry's /metrics series with {model=...}
        # (fleet tenants set it; docs/OBSERVABILITY.md cardinality note)
        self._stats = latency_stats(name, model=model)
        # degradation path (docs/RESILIENCE.md): when the device_put
        # fault site fires, a chunk is rescored by the host tree-walker
        # instead of failing the request. The registry installs this as
        # a closure over the source Booster: (chunk (n,F) f32, start,
        # end) -> (summed raw margins (n,K), leaf indices (n,T) with
        # the used range at columns [start*K, end*K)). None = fail fast.
        self.host_fallback: Optional[
            Callable[[np.ndarray, int, int],
                     Tuple[np.ndarray, np.ndarray]]
        ] = None
        self._fallback_warned = False

    # ------------------------------------------------------------------
    def bucket_for(self, n: int) -> int:
        """Smallest rung >= n, else the largest (caller chunks)."""
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def warmup(self, num_features: Optional[int] = None) -> None:
        """Precompile every rung (zeros through the real entry point).

        num_features defaults to the forest's widest referenced feature
        + 1 — pass the true dataset width when it is larger, otherwise
        the serving path would compile again on the first real batch.
        """
        import jax.numpy as jnp

        F = max(self.forest.max_feature + 1, 1) \
            if num_features is None else int(num_features)
        tw = np.ones(self.forest.num_trees, np.float32)
        for b in self.buckets:
            score, _leaf = self.forest.apply(
                jnp.zeros((b, F), jnp.float32), tw
            )
            score.block_until_ready()

    # ------------------------------------------------------------------
    def _bucketed_chunks(self, X: np.ndarray, tw: np.ndarray,
                         start: int = 0, end: int = 0):
        """Yield (score (n,K), leaf (n,T)) per max-bucket chunk, each
        scored at its padded ladder shape — EVERY device call in the
        dispatcher goes through here, so no request shape escapes the
        ladder (the bounded-compiles contract covers pred_leaf too).

        An injected device fault (the ``device_put`` fault site)
        degrades THAT chunk to the host tree-walker when
        ``host_fallback`` is installed: slower, metric-counted, warned
        once — but the request still answers (parity is
        regression-tested in tests/test_resilience.py). ONLY the
        injected fault degrades: anything the scorer itself raises — a
        lowering or Mosaic/XLA compile error above all — reaches the
        caller, so a scorer that cannot run on the chip fails loudly
        instead of answering every request from the host."""
        import jax.numpy as jnp

        N = X.shape[0]
        top = self.buckets[-1]
        pos = 0
        while pos < N:
            chunk = X[pos: pos + top]
            rows = chunk.shape[0]
            b = self.bucket_for(rows)
            record_bucket_dispatch(self.name, b, rows)
            if rows < b:
                chunk = np.concatenate(
                    [chunk, np.zeros((b - rows, X.shape[1]), np.float32)]
                )
            try:
                fault_point("device_put")
            except InjectedFault:
                if self.host_fallback is None:
                    raise
                if not self._fallback_warned:
                    self._fallback_warned = True
                    log.warning(
                        f"device scoring fault on entry "
                        f"{self.name!r}; degrading faulted chunks to "
                        "the host tree-walker (slower; counted in "
                        "lgbmtpu_serve_host_fallback_total)"
                    )
                record_host_fallback(self.name)
                s, lf = self.host_fallback(chunk[:rows], start, end)
                out = (
                    np.asarray(s, np.float32),
                    np.asarray(lf)[:rows],
                )
            else:
                score, leaf = self.forest.apply(jnp.asarray(chunk), tw)
                out = np.asarray(score)[:rows], np.asarray(leaf)[:rows]
            yield out
            pos += top

    def _prep(self, X, start_iteration: int, num_iteration: int):
        X = np.asarray(X, np.float32)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        self.forest._check_width(X)
        tw, start, end = self.forest._tree_weights(
            start_iteration, num_iteration
        )
        return X, tw, start, end

    def score_raw(self, X: np.ndarray, start_iteration: int = 0,
                  num_iteration: int = -1) -> np.ndarray:
        """(K, N) raw margins via bucket-padded device calls."""
        X, tw, start, end = self._prep(X, start_iteration, num_iteration)
        if X.shape[0] == 0:  # filtered-empty request, not an error
            return np.zeros((self.forest.num_class, 0), np.float64)
        t0 = time.perf_counter()
        outs = [s for s, _ in self._bucketed_chunks(X, tw, start, end)]
        out = np.concatenate(outs).T.astype(np.float64)  # (K, N)
        if self.forest.average_output and end > start:
            out /= end - start
        self._stats.observe(time.perf_counter() - t0, X.shape[0])
        return out

    def predict_leaf(self, X: np.ndarray, start_iteration: int = 0,
                     num_iteration: int = -1) -> np.ndarray:
        """(N, used_trees) leaf indices through the same bucket ladder
        (a raw-shape forest.apply here would reintroduce the per-shape
        compile churn the ladder exists to bound)."""
        X, tw, start, end = self._prep(X, start_iteration, num_iteration)
        K = self.forest.num_class
        if X.shape[0] == 0:
            return np.zeros((0, (end - start) * K), np.int64)
        t0 = time.perf_counter()
        leaves = [lf for _, lf in self._bucketed_chunks(X, tw, start, end)]
        out = np.concatenate(leaves)[:, start * K: end * K]
        self._stats.observe(time.perf_counter() - t0, X.shape[0])
        return out.astype(np.int64)

    def predict_contrib(self, X: np.ndarray, start_iteration: int = 0,
                        num_iteration: int = -1) -> np.ndarray:
        """(N, K*(F+1)) SHAP contributions (Booster pred_contrib
        layout) through the ladder. Contrib intermediates scale with
        rows x trees x leaves x path length, so the contrib ladder is
        capped at ``CONTRIB_MAX_ROWS`` — large requests chunk through
        the capped top rung. No host fallback: a device fault fails
        the explanation request (scoring traffic is the degradation-
        protected path; explanations re-raise)."""
        import jax.numpy as jnp

        X, tw, start, end = self._prep(X, start_iteration, num_iteration)
        F = X.shape[1]
        K = self.forest.num_class
        if X.shape[0] == 0:
            return np.zeros((0, K * (F + 1)), np.float64)
        t0 = time.perf_counter()
        top = min(self.buckets[-1], CONTRIB_MAX_ROWS)
        rungs = [b for b in self.buckets if b <= top] or [top]
        outs = []
        N, pos = X.shape[0], 0
        while pos < N:
            chunk = X[pos: pos + top]
            rows = chunk.shape[0]
            b = next((r for r in rungs if rows <= r), rungs[-1])
            record_bucket_dispatch(f"{self.name}:contrib", b, rows)
            if rows < b:
                chunk = np.concatenate(
                    [chunk, np.zeros((b - rows, F), np.float32)]
                )
            out = self.forest.apply_contrib(jnp.asarray(chunk), tw)
            outs.append(np.asarray(out)[:rows])
            pos += top
        out = np.concatenate(outs).astype(np.float64)
        if self.forest.average_output and end > start:
            out /= end - start
        self._stats.observe(time.perf_counter() - t0, N)
        return out

    def stats(self) -> dict:
        return self._stats.snapshot()


# cap on rows per device TreeSHAP call: contrib intermediates are
# (rows, trees, leaves, path) tensors, ~leaves x path larger per row
# than scoring — the top scoring rung would not fit comfortably
CONTRIB_MAX_ROWS = 256


class MicroBatcher:
    """Thread-safe request queue in front of one or more
    BucketDispatchers.

    submit(rows) -> Future resolving to that request's (n, K) scores.
    One worker thread PER DISPATCHER drains a shared queue: everything
    pending (up to the largest bucket) coalesces into a single padded
    device call. With replica dispatchers this is the continuous-
    batching front: while replica 0's batch is in flight on its
    device, replica 1's worker is already coalescing and admitting the
    next batch — requests never wait for a previous batch to land
    (docs/SERVING.md "Fleet serving").

    Overload handling (docs/RESILIENCE.md "Serving degradation"):

    - ``queue_cap`` bounds the ROWS admitted to the queue; a submit
      past the cap fast-fails with :class:`QueueOverflow` in the
      caller's thread (the HTTP transport maps it to 503 +
      Retry-After) instead of growing an unbounded backlog whose tail
      latency is already hopeless.
    - ``deadline_s`` (per-instance default, overridable per submit)
      bounds time-in-queue: the worker sweeps expired requests on
      every drain and fails them with :class:`DeadlineExceeded` (HTTP
      504) without spending a device call on them. A request already
      coalesced into a device call is never cancelled.
    - ``close()`` fails everything still queued with
      :class:`ShutdownError` — a shutdown must never leave a caller
      blocked forever on ``Future.result()``.
    """

    def __init__(self, dispatcher, max_delay_s: float = 0.002,
                 deadline_s: float = 0.0,
                 queue_cap: int = 0):
        # a single dispatcher (anything duck-typing BucketDispatcher)
        # or a list/tuple of replicas sharing identical model + ladder
        # (the registry builds the replica list)
        if isinstance(dispatcher, (list, tuple)):
            self.dispatchers: Tuple[BucketDispatcher, ...] = tuple(dispatcher)
        else:
            self.dispatchers = (dispatcher,)
        if not self.dispatchers:
            raise ValueError("MicroBatcher needs at least one dispatcher")
        self.dispatcher = self.dispatchers[0]  # primary (stats, width)
        self.max_delay_s = float(max_delay_s)
        self.deadline_s = float(deadline_s)  # 0 = no default deadline
        self.queue_cap = int(queue_cap)      # rows; 0 = unbounded
        # entries are (X, future, expiry | None) in monotonic time
        self._pending: List[Tuple[np.ndarray, Future,
                                  Optional[float]]] = []
        self._pending_rows = 0
        self._cond = threading.Condition()
        self._closed = False
        self._workers = [
            threading.Thread(
                target=self._run, args=(d,),
                name=f"lgb-serve-microbatch-{i}", daemon=True,
            )
            for i, d in enumerate(self.dispatchers)
        ]
        for w in self._workers:
            w.start()

    def submit(self, X: np.ndarray,
               deadline_s: Optional[float] = None) -> Future:
        """Queue rows for coalesced default-parameter scoring; resolves
        to that request's (n, K) RAW margins. Non-default scoring
        options (truncation, pred_leaf) go through the dispatcher
        directly — requests in one coalesced batch must share one
        parameter set. ``deadline_s`` overrides the instance default
        (<= 0 disables the deadline for this request)."""
        X = np.asarray(X, np.float32)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        # validate in the submitter's thread: a malformed request must
        # fail ITS caller, never the innocent requests it would have
        # been coalesced with
        self.dispatcher.forest._check_width(X)
        dl = self.deadline_s if deadline_s is None else float(deadline_s)
        expiry = time.monotonic() + dl if dl > 0 else None
        fut: Future = Future()
        try:
            with self._cond:
                if self._closed:
                    raise ShutdownError("MicroBatcher is closed")
                # admission control: reject while a backlog exists (a
                # single request larger than the cap is still admitted
                # into an EMPTY queue — it chunks through the ladder)
                if (self.queue_cap > 0 and self._pending
                        and self._pending_rows + X.shape[0]
                        > self.queue_cap):
                    raise QueueOverflow(
                        f"microbatch queue full "
                        f"({self._pending_rows} rows queued, "
                        f"cap {self.queue_cap})"
                    )
                self._pending.append((X, fut, expiry))
                self._pending_rows += X.shape[0]
                depth = len(self._pending)
                self._cond.notify()
        except QueueOverflow:
            # counter outside the condition: the metrics registry has
            # its own lock and must not nest under the queue's
            record_serve_rejection(self.dispatcher.name, "overloaded")
            raise
        record_queue_depth(self.dispatcher.name, depth)
        return fut

    def close(self) -> None:
        """Stop the worker and fail anything still pending with
        ShutdownError. The worker drains the queue on the way out; the
        explicit sweep below only matters when it cannot finish within
        the join timeout (e.g. wedged mid-device-call) — futures must
        fail, not hang their callers forever."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        for w in self._workers:
            w.join(timeout=5)
        with self._cond:
            leftovers = self._pending
            self._pending = []
            self._pending_rows = 0
        for _, fut, _ in leftovers:  # outside the lock: may run callbacks
            if not fut.done():
                fut.set_exception(
                    ShutdownError("MicroBatcher closed before scoring")
                )

    # ------------------------------------------------------------------
    def _sweep_expired_locked(
        self, now: float
    ) -> List[Tuple[np.ndarray, Future, Optional[float]]]:
        """Pop expired entries (caller holds the condition; the popped
        futures are failed OUTSIDE the lock — done-callbacks may run)."""
        expired = [e for e in self._pending
                   if e[2] is not None and now >= e[2]]
        if expired:
            # both callers hold self._cond (the _locked suffix is the
            # contract; the per-function lint cannot see the call sites)
            self._pending = [e for e in self._pending  # lint: allow[unlocked-write]
                             if e[2] is None or now < e[2]]
            self._pending_rows = sum(  # lint: allow[unlocked-write]
                e[0].shape[0] for e in self._pending
            )
        return expired

    def _run(self, dispatcher: BucketDispatcher) -> None:
        top = dispatcher.buckets[-1]
        while True:
            expired: List[Tuple[np.ndarray, Future, Optional[float]]] = []
            batch: List[Tuple[np.ndarray, Future]] = []
            rows = 0
            with self._cond:
                while not self._pending and not self._closed:
                    self._cond.wait()
                if self._closed and not self._pending:
                    return
                expired = self._sweep_expired_locked(time.monotonic())
                # brief linger so near-simultaneous submitters coalesce
                if (len(self._pending) == 1
                        and self._pending[0][0].shape[0] < top
                        and not self._closed):
                    self._cond.wait(self.max_delay_s)
                    expired += self._sweep_expired_locked(
                        time.monotonic()
                    )
                if self._pending:
                    # coalesce only same-width requests (widths >= the
                    # model's widest feature are all valid, so a mixed
                    # queue would break np.concatenate); stragglers
                    # stay pending for the next drain
                    width = self._pending[0][0].shape[1]
                    while (self._pending and rows < top
                           and self._pending[0][0].shape[1] == width):
                        X, fut, _ = self._pending.pop(0)
                        self._pending_rows -= X.shape[0]
                        batch.append((X, fut))
                        rows += X.shape[0]
                depth = len(self._pending)
            for _, fut, _ in expired:
                record_serve_rejection(dispatcher.name, "deadline")
                if not fut.done():
                    fut.set_exception(DeadlineExceeded(
                        "request expired in the microbatch queue"
                    ))
            if not batch:
                continue
            record_queue_depth(dispatcher.name, depth)
            record_coalesce(dispatcher.name, len(batch), rows)
            try:
                Xall = np.concatenate([x for x, _ in batch]) \
                    if len(batch) > 1 else batch[0][0]
                out = dispatcher.score_raw(Xall)  # (K, N)
                pos = 0
                for X, fut in batch:
                    n = X.shape[0]
                    fut.set_result(out[:, pos: pos + n].T)  # (n, K)
                    pos += n
            except Exception as e:  # noqa: BLE001 — fan the error out
                for _, fut in batch:
                    if not fut.done():
                        fut.set_exception(e)
