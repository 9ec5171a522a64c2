"""Thread-safe metrics registry: counters / gauges / histograms with
labels, plus Prometheus text exposition.

The reference's observability is a timer table printed at exit
(utils/common.h:979 USE_TIMETAG) — enough for a batch trainer, not for
a serving system or for tracking throughput round-over-round. This
registry is the production analog: any module records named metrics
(host-side only — NEVER from inside traced code; the no-callback jaxpr
contract in analysis/jaxpr_audit.py stays the proof), and exporters
read one consistent snapshot:

- ``render_prometheus()`` — text exposition (format 0.0.4), served
  from the serving HTTP transport's ``/metrics`` route (server.py);
- ``snapshot()`` — plain dicts for the run manifest (manifest.py) and
  tests.

Collectors bridge existing stat objects without duplicating state:
``timer.LatencyStats`` registers a collector that derives its samples
from the SAME ring ``ModelRegistry.stats()`` reports, so the
percentile a scrape sees and the percentile the stats op returns can
never disagree (the one-source-of-truth contract, parity-tested in
tests/test_obs.py).

Cost model: recording is a dict upsert under a per-metric lock —
nanoseconds against the ms-scale regions being counted. When the
registry is disabled (env LIGHTGBM_TPU_METRICS=0, or ``disable()``)
every record call is a single attribute check.
"""

from __future__ import annotations

import math
import os
import threading
from typing import Any, Callable, Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

# default histogram bucket bounds (seconds-flavored, Prometheus style)
DEFAULT_TIME_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)


class Sample(NamedTuple):
    """One exposition sample (collectors yield these)."""

    name: str
    kind: str  # "counter" | "gauge"
    help: str
    labels: Tuple[Tuple[str, str], ...]
    value: float


def _escape_label(v: str) -> str:
    return (
        str(v).replace("\\", r"\\").replace('"', r"\"").replace("\n", r"\n")
    )


def _fmt(v: float) -> str:
    if v != v:  # NaN
        return "NaN"
    if v in (math.inf, -math.inf):
        return "+Inf" if v > 0 else "-Inf"
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def _render_labels(labels: Tuple[Tuple[str, str], ...]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_escape_label(v)}"' for k, v in labels)
    return "{" + inner + "}"


class _Metric:
    """Base: one named metric family with a fixed label-name set."""

    kind = "untyped"

    def __init__(self, name: str, help_text: str,
                 label_names: Sequence[str], registry: "MetricsRegistry"):
        self.name = name
        self.help = help_text
        self.label_names = tuple(label_names)
        self._registry = registry
        self._lock = threading.Lock()
        self._values: Dict[Tuple[str, ...], Any] = {}

    def _key(self, labels: Dict[str, Any]) -> Tuple[str, ...]:
        if set(labels) != set(self.label_names):
            raise ValueError(
                f"metric {self.name!r} takes labels {self.label_names}, "
                f"got {tuple(sorted(labels))}"
            )
        return tuple(str(labels[k]) for k in self.label_names)

    def _pairs(self, key: Tuple[str, ...]) -> Tuple[Tuple[str, str], ...]:
        return tuple(zip(self.label_names, key))

    def clear(self) -> None:
        with self._lock:
            self._values.clear()


class Counter(_Metric):
    """Monotonically increasing count."""

    kind = "counter"

    def inc(self, value: float = 1.0, **labels: Any) -> None:
        if not self._registry.enabled:
            return
        if value < 0:
            raise ValueError(f"counter {self.name!r} cannot decrease")
        k = self._key(labels)
        with self._lock:
            self._values[k] = self._values.get(k, 0.0) + float(value)

    def value(self, **labels: Any) -> float:
        k = self._key(labels)
        with self._lock:
            return float(self._values.get(k, 0.0))

    def samples(self) -> List[Sample]:
        with self._lock:
            items = sorted(self._values.items())
        return [
            Sample(self.name, self.kind, self.help, self._pairs(k), v)
            for k, v in items
        ]


class Gauge(_Metric):
    """Point-in-time value (queue depth, trees/s, ...)."""

    kind = "gauge"

    def set(self, value: float, **labels: Any) -> None:
        if not self._registry.enabled:
            return
        k = self._key(labels)
        with self._lock:
            self._values[k] = float(value)

    def inc(self, value: float = 1.0, **labels: Any) -> None:
        if not self._registry.enabled:
            return
        k = self._key(labels)
        with self._lock:
            self._values[k] = self._values.get(k, 0.0) + float(value)

    def dec(self, value: float = 1.0, **labels: Any) -> None:
        self.inc(-value, **labels)

    def value(self, **labels: Any) -> float:
        k = self._key(labels)
        with self._lock:
            return float(self._values.get(k, 0.0))

    samples = Counter.samples  # same flat shape


class Histogram(_Metric):
    """Cumulative-bucket histogram (Prometheus semantics)."""

    kind = "histogram"

    def __init__(self, name: str, help_text: str,
                 label_names: Sequence[str], registry: "MetricsRegistry",
                 buckets: Sequence[float] = DEFAULT_TIME_BUCKETS):
        super().__init__(name, help_text, label_names, registry)
        self.buckets = tuple(sorted(float(b) for b in buckets))
        if not self.buckets:
            raise ValueError(f"histogram {name!r} needs at least one bucket")

    def observe(self, value: float, **labels: Any) -> None:
        if not self._registry.enabled:
            return
        k = self._key(labels)
        v = float(value)
        with self._lock:
            state = self._values.get(k)
            if state is None:
                state = {"counts": [0] * len(self.buckets),
                         "sum": 0.0, "count": 0}
                self._values[k] = state
            for i, b in enumerate(self.buckets):
                if v <= b:
                    state["counts"][i] += 1
            state["sum"] += v
            state["count"] += 1

    def state(self, **labels: Any) -> Dict[str, Any]:
        k = self._key(labels)
        with self._lock:
            s = self._values.get(k)
            if s is None:
                return {"counts": [0] * len(self.buckets),
                        "sum": 0.0, "count": 0}
            return {"counts": list(s["counts"]), "sum": s["sum"],
                    "count": s["count"]}

    def samples(self) -> List[Sample]:
        with self._lock:
            items = sorted(
                (k, {"counts": list(s["counts"]), "sum": s["sum"],
                     "count": s["count"]})
                for k, s in self._values.items()
            )
        out: List[Sample] = []
        for k, s in items:
            pairs = self._pairs(k)
            cum = 0
            for b, c in zip(self.buckets, s["counts"]):
                cum = c  # counts are already cumulative per-bucket
                out.append(Sample(
                    self.name + "_bucket", self.kind, self.help,
                    pairs + (("le", _fmt(b)),), float(cum),
                ))
            out.append(Sample(
                self.name + "_bucket", self.kind, self.help,
                pairs + (("le", "+Inf"),), float(s["count"]),
            ))
            out.append(Sample(self.name + "_sum", self.kind, self.help,
                              pairs, float(s["sum"])))
            out.append(Sample(self.name + "_count", self.kind, self.help,
                              pairs, float(s["count"])))
        return out


class MetricsRegistry:
    """Named metric families + scrape-time collectors."""

    def __init__(self, enabled: Optional[bool] = None):
        self._lock = threading.Lock()
        self._metrics: Dict[str, _Metric] = {}
        self._collectors: List[Callable[[], Iterable[Sample]]] = []
        if enabled is None:
            enabled = os.environ.get(
                "LIGHTGBM_TPU_METRICS", "1"
            ) not in ("0", "false", "off")
        self.enabled = bool(enabled)

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    # ------------------------------------------------------------------
    def _get_or_create(self, cls, name: str, help_text: str,
                       labels: Sequence[str], **kw) -> _Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help_text, labels, self, **kw)
                self._metrics[name] = m
                return m
        if not isinstance(m, cls) or m.label_names != tuple(labels):
            raise ValueError(
                f"metric {name!r} already registered as {m.kind} with "
                f"labels {m.label_names}"
            )
        return m

    def counter(self, name: str, help_text: str = "",
                labels: Sequence[str] = ()) -> Counter:
        return self._get_or_create(Counter, name, help_text, labels)

    def gauge(self, name: str, help_text: str = "",
              labels: Sequence[str] = ()) -> Gauge:
        return self._get_or_create(Gauge, name, help_text, labels)

    def histogram(self, name: str, help_text: str = "",
                  labels: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_TIME_BUCKETS
                  ) -> Histogram:
        return self._get_or_create(Histogram, name, help_text, labels,
                                   buckets=buckets)

    def register_collector(
        self, fn: Callable[[], Iterable[Sample]]
    ) -> None:
        """Register a scrape-time sample source (e.g. a LatencyStats
        bridge). The callable runs on every render/snapshot."""
        with self._lock:
            if fn not in self._collectors:
                self._collectors.append(fn)

    def unregister_collector(
        self, fn: Callable[[], Iterable[Sample]]
    ) -> None:
        with self._lock:
            if fn in self._collectors:
                self._collectors.remove(fn)

    # ------------------------------------------------------------------
    def samples(self) -> List[Sample]:
        """Every current sample (metrics + collectors) — the public
        scrape view obs.aggregate serializes for host-side fleet
        merging (each Sample carries its kind, so the merger knows
        counters sum and gauges don't)."""
        return self._all_samples()

    def _all_samples(self) -> List[Sample]:
        with self._lock:
            metrics = list(self._metrics.values())
            collectors = list(self._collectors)
        out: List[Sample] = []
        for m in metrics:
            out.extend(m.samples())
        for fn in collectors:
            try:
                out.extend(fn())
            except Exception as e:  # noqa: BLE001 — one bad collector must not kill the scrape
                from .. import log

                log.warning(f"metrics collector {fn!r} failed: {e}")
        return out

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """{metric name: {rendered label string: value}} over every
        metric and collector — the manifest/test view."""
        out: Dict[str, Dict[str, float]] = {}
        for s in self._all_samples():
            out.setdefault(s.name, {})[_render_labels(s.labels)] = s.value
        return out

    def render_prometheus(self) -> str:
        """Text exposition format 0.0.4 (one scrape body)."""
        samples = self._all_samples()
        # group by family: histogram sample names share the base
        # metric's HELP/TYPE header
        by_family: "Dict[str, List[Sample]]" = {}
        family_meta: Dict[str, Tuple[str, str]] = {}
        for s in samples:
            fam = s.name
            for suffix in ("_bucket", "_sum", "_count"):
                if s.kind == "histogram" and fam.endswith(suffix):
                    fam = fam[: -len(suffix)]
                    break
            by_family.setdefault(fam, []).append(s)
            family_meta.setdefault(fam, (s.kind, s.help))
        lines: List[str] = []
        for fam in sorted(by_family):
            kind, help_text = family_meta[fam]
            if help_text:
                lines.append(f"# HELP {fam} {help_text}")
            lines.append(f"# TYPE {fam} {kind}")
            for s in by_family[fam]:
                lines.append(
                    f"{s.name}{_render_labels(s.labels)} {_fmt(s.value)}"
                )
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        """Drop every recorded value (metric objects survive; tests)."""
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            m.clear()


_default = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    return _default


# ---------------------------------------------------------------- bridges
# Small helpers the instrumented modules call, so hot seams carry one
# obs call instead of registry plumbing (and the concurrency-linted
# serving modules never manipulate foreign locks inline).

_latency_bridged: Dict[str, Any] = {}
_latency_lock = threading.Lock()


def register_latency_collector(name: str, stats: Any,
                               model: Optional[str] = None) -> None:
    """Expose a timer.LatencyStats on /metrics. Samples derive from the
    same ``snapshot()`` the serving stats op reports — one ring, every
    reader (the dedupe contract for serving latency). ``model`` adds a
    ``{model=...}`` label for fleet tenants (one series set per model;
    see docs/OBSERVABILITY.md for the cardinality contract)."""
    with _latency_lock:
        if name in _latency_bridged:
            return
        _latency_bridged[name] = stats

    def collect() -> List[Sample]:
        snap = stats.snapshot()
        lab = (("entry", name),)
        if model is not None:
            lab = lab + (("model", model),)
        out = [
            Sample("lgbmtpu_serve_requests_total", "counter",
                   "requests observed by the latency ring", lab,
                   float(snap["count"])),
            Sample("lgbmtpu_serve_rows_total", "counter",
                   "rows scored", lab, float(snap["rows"])),
            Sample("lgbmtpu_serve_rows_per_sec", "gauge",
                   "lifetime rows/second", lab,
                   float(snap["rows_per_sec"])),
            Sample("lgbmtpu_serve_busy_frac", "gauge",
                   "fraction of uptime spent scoring", lab,
                   float(snap["busy_frac"])),
        ]
        for stat in ("mean_ms", "p50_ms", "p95_ms", "p99_ms"):
            out.append(Sample(
                "lgbmtpu_serve_latency_ms", "gauge",
                "request latency over the recent window (ms)",
                lab + (("stat", stat[:-3]),), float(snap[stat]),
            ))
        return out

    _default.register_collector(collect)


def record_training_round(n_iters: int, n_trees: int,
                          seconds: float) -> None:
    """One dispatched training chunk (or one sync iteration)."""
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_train_iterations_total",
              "boosting iterations completed").inc(n_iters)
    r.counter("lgbmtpu_train_trees_total",
              "trees trained (iterations x classes)").inc(n_trees)
    if seconds > 0:
        r.gauge("lgbmtpu_train_trees_per_sec",
                "trees/second over the most recent chunk"
                ).set(n_trees / seconds)
        r.histogram("lgbmtpu_train_chunk_seconds",
                    "wall seconds per dispatched training chunk"
                    ).observe(seconds)


def record_eval_values(evals) -> None:
    """Per-round evaluation results as labeled gauges: every
    ``(dataset, metric, value, higher_better)`` tuple the training loop
    produces (the same rows ``callback.record_evaluation`` collects)
    lands on ``lgbmtpu_eval_metric{dataset,metric}`` — learning curves
    on /metrics with no custom callback (docs/OBSERVABILITY.md)."""
    r = _default
    if not r.enabled or not evals:
        return
    g = r.gauge("lgbmtpu_eval_metric",
                "most recent per-round evaluation metric value",
                labels=("dataset", "metric"))
    for item in evals:
        ds_name, metric, value = item[0], item[1], item[2]
        g.set(float(value), dataset=ds_name, metric=metric)


def record_bucket_dispatch(entry: str, bucket: int, rows: int) -> None:
    """One padded device call through the serving shape ladder."""
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_serve_bucket_dispatch_total",
              "device calls per shape-ladder rung",
              labels=("entry", "bucket")).inc(
        1, entry=entry, bucket=bucket)
    r.counter("lgbmtpu_serve_padded_rows_total",
              "zero rows added to pad requests up to their rung",
              labels=("entry",)).inc(max(bucket - rows, 0), entry=entry)


def record_queue_depth(entry: str, depth: int) -> None:
    r = _default
    if not r.enabled:
        return
    r.gauge("lgbmtpu_serve_queue_depth",
            "requests waiting in the microbatch queue",
            labels=("entry",)).set(depth, entry=entry)


def record_coalesce(entry: str, n_requests: int, rows: int) -> None:
    """One microbatch drain: n_requests coalesced into one call."""
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_serve_coalesced_requests_total",
              "requests coalesced through the microbatch queue",
              labels=("entry",)).inc(n_requests, entry=entry)
    r.histogram("lgbmtpu_serve_coalesced_batch_rows",
                "rows per coalesced device call", labels=("entry",),
                buckets=(1, 4, 16, 64, 256, 1024, 4096)
                ).observe(rows, entry=entry)


def record_host_fallback(entry: str) -> None:
    """One serving chunk scored by the host tree-walker after a device
    scoring fault (docs/RESILIENCE.md "Serving degradation")."""
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_serve_host_fallback_total",
              "chunks degraded to the host tree-walker after a device "
              "scoring fault",
              labels=("entry",)).inc(1, entry=entry)


def record_serve_rejection(entry: str, kind: str) -> None:
    """A serving request rejected before scoring: queue overflow
    (admission control) or deadline expiry."""
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_serve_rejected_total",
              "requests rejected by admission control or deadline "
              "expiry, by kind",
              labels=("entry", "kind")).inc(1, entry=entry, kind=kind)


def record_registry_event(event: str, model: str) -> None:
    """Model-registry lifecycle: load / swap / rollback / unload."""
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_registry_events_total",
              "model registry lifecycle events",
              labels=("event", "model")).inc(1, event=event, model=model)


def record_fleet_page(model: str, event: str) -> None:
    """Fleet HBM paging: ``page_in`` / ``evict`` / ``warmup`` for one
    tenant (serving/fleet.py LRU residency)."""
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_fleet_page_events_total",
              "fleet HBM paging events, by model and kind",
              labels=("model", "event")).inc(1, model=model, event=event)


def record_fleet_resident(resident: int, capacity: int) -> None:
    """Current fleet residency vs the configured HBM capacity."""
    r = _default
    if not r.enabled:
        return
    r.gauge("lgbmtpu_fleet_resident_models",
            "models currently resident in device memory").set(resident)
    r.gauge("lgbmtpu_fleet_capacity_models",
            "configured fleet residency capacity").set(capacity)


def record_request_op(op: str, ok: bool) -> None:
    """One protocol request through handle_request (both transports)."""
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_serve_protocol_requests_total",
              "protocol requests handled, by op",
              labels=("op",)).inc(1, op=op)
    if not ok:
        r.counter("lgbmtpu_serve_protocol_errors_total",
                  "protocol requests answered with ok=false",
                  labels=("op",)).inc(1, op=op)


def record_promotion_event(outcome: str) -> None:
    """One online-loop gate verdict: ``promoted`` (gate passed, registry
    swapped), ``rejected`` (holdout metric regressed), ``rolled_back``
    (anomaly sentinel tripped during the refit — poisoned microbatch
    auto-revert). online/loop.py (docs/RESILIENCE.md "Online loop")."""
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_promotion_events_total",
              "online-loop promotion gate verdicts, by outcome",
              labels=("outcome",)).inc(1, outcome=outcome)


def record_ingest(rows: int) -> None:
    """One microbatch appended to the online ingest spool."""
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_ingest_batches_total",
              "microbatches accepted through the ingest op").inc(1)
    r.counter("lgbmtpu_ingest_rows_total",
              "rows accepted through the ingest op").inc(rows)


def record_loop_progress(version: int, cycle: int, offset: int) -> None:
    """Online-loop liveness gauges: promoted version, verdict cycles,
    and spool bytes consumed."""
    r = _default
    if not r.enabled:
        return
    r.gauge("lgbmtpu_online_version",
            "currently promoted online-loop model version").set(version)
    r.gauge("lgbmtpu_online_cycles_total",
            "online-loop verdict cycles completed").set(cycle)
    r.gauge("lgbmtpu_online_ingest_offset_bytes",
            "ingest spool bytes consumed through the last verdict"
            ).set(offset)


def record_collective_wire(entry: str, nbytes: int) -> None:
    """Host-side estimate of collective payload bytes dispatched (the
    runtime twin of analysis/cost_budget.json's static wire pins)."""
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_collective_wire_bytes_total",
              "estimated collective payload bytes dispatched",
              labels=("entry",)).inc(nbytes, entry=entry)


def record_parallel_mesh(size: int, wire: str) -> None:
    """What a data-parallel Booster resolved to: the chips of its data
    mesh, and the wire its child histograms cross the mesh on
    (learner/rounds.py hist_wire: psum_f32, rs_int32, rs_int16,
    vote_*): one series, 1 under the label of the wire in use."""
    r = _default
    if not r.enabled:
        return
    r.gauge("lgbmtpu_parallel_mesh_size",
            "chips of the data mesh of the newest tree_learner=data "
            "Booster").set(size)
    g = r.gauge("lgbmtpu_parallel_hist_wire",
                "1 on the wire the newest data-parallel Booster's child "
                "histograms cross the mesh on", labels=("wire",))
    g.clear()
    g.set(1, wire=wire)


def record_dataset_push(kind: str, nbytes: int) -> None:
    """Host -> device bytes of a data set's resident copies
    (dataset.BinnedDataset.device_arrays / device_label / device_weight):
    kind is bins (a bin matrix: one copy per Dataset and layout while
    it stays resident) or rows (a padded label or weight)."""
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_dataset_push_bytes_total",
              "host to device bytes of data set copies, by kind "
              "(bins | rows)", labels=("kind",)).inc(nbytes, kind=kind)


def record_grower_rounds(widths, rounds) -> None:
    """Rounds the rounds grower ran at each width of its slot ladder
    (learner/rounds.py ladder_widths) and, as width="route", the rounds
    that only routed rows (the last round of a tree that ends on its
    leaf budget), as counted on the device and fetched with a fused
    chunk's eval rows."""
    r = _default
    if not r.enabled:
        return
    c = r.counter("lgbmtpu_grower_rounds_total",
                  "tree-growth rounds executed, by the round kernel's "
                  "slot width (route: no histogram pass)",
                  labels=("width",))
    for w, n in zip(widths, rounds):
        c.inc(float(n), width=str(w))


def record_hist_schedule(sched, n_cols: int) -> None:
    """Gauges of a rounds-grower program's histogram schedule
    (learner/rounds.py hist_schedule), set where the fused step is
    built: per Pallas kernel the feature blocks of one call (0: the
    program does not call it) and the bins tile's columns as the
    kernel multiplies them (whole loop groups) and the columns one
    matmul of its feature loop contracts (2: the pair at 33..64 bins);
    per pass width (root, then the slot ladder) the kernel calls that
    stream the rows. Nothing where no kernel runs (an XLA-formulation
    backend)."""
    r = _default
    if not r.enabled or not sched.calls:
        return
    from ..learner.pallas_hist import columns_per_matmul, feature_groups

    groups, per_group = feature_groups(n_cols, sched.num_bins)
    per = columns_per_matmul(sched.num_bins)
    whole = (1, groups * per_group, per)
    absent = (0, 0, 0)
    slots = int(sched.calls[-1][0])  # the ladder's last width
    per_kernel = {
        "hist_nat_tpu": ((sched.plan.blocks, sched.plan.feat_block, per)
                         if sched.routed else whole),
        "hist_round_tpu": whole if sched.fused else absent,
        # a routed round's routing pass sees its <= slots split columns;
        # no routing pass builds a histogram one-hot
        "route_round_tpu": ((1, slots, 0) if sched.routed
                            else whole[:2] + (0,) if sched.fused
                            else absent),
    }
    blocks = r.gauge("lgbmtpu_hist_feature_blocks",
                     "feature blocks (the leading grid dimension) of one "
                     "call of a histogram or routing kernel; 0: not in "
                     "the program", labels=("kernel",))
    cols = r.gauge("lgbmtpu_hist_block_columns",
                   "columns of one feature block's bins tile as the "
                   "kernel's loop groups cover them", labels=("kernel",))
    per_matmul = r.gauge("lgbmtpu_hist_columns_per_matmul",
                         "columns whose one-hots share one MXU tile in a "
                         "histogram kernel's feature loop (2 at 33..64 "
                         "bins); 0: no histograms", labels=("kernel",))
    for kernel, (b, c, m) in per_kernel.items():
        blocks.set(b, kernel=kernel)
        cols.set(c, kernel=kernel)
        per_matmul.set(m, kernel=kernel)
    calls = r.gauge("lgbmtpu_hist_calls_per_pass",
                    "histogram kernel calls that stream the rows in one "
                    "pass, by pass width (root, then the slot ladder)",
                    labels=("width",))
    for width, n in sched.calls:
        calls.set(n, width=width)


def record_split_search(dirs) -> None:
    """Gauge of the split search's traced directions
    (learner/split.py SearchDirections: static facts of the Dataset),
    set where the fused step is built: 1 where the program traces the
    direction, else 0; default-right always is."""
    r = _default
    if not r.enabled:
        return
    g = r.gauge("lgbmtpu_split_search_directions",
                "1 where the split search traces the direction or test "
                "(the Dataset can have such a split), else 0",
                labels=("kind",))
    g.set(1, kind="default_right")
    for kind, on in dirs._asdict().items():
        g.set(int(on), kind=kind)


def record_traverse_cat_words(words: int) -> None:
    """Gauge of the word rows a node's category set adds to the valid
    traversal's per-node table (tree.num_cat_words), set beside the
    search's directions where the fused step is built; 0: the traversal
    traces no category test (an all-numerical Dataset)."""
    r = _default
    if not r.enabled:
        return
    r.gauge("lgbmtpu_traverse_cat_words",
            "bit-word rows of a node's category set in the traversal's "
            "per-node table; 0: no category test traced").set(words)


def record_dataset_columns(mappers, max_cat_to_onehot: int,
                           cat_other_rows=None) -> None:
    """Gauges of a training Dataset's used columns by kind, set when it
    is constructed (basic.Dataset.construct, from used_mappers()):
    numerical (all of them), with_nan (the numerical ones with a NaN
    bin: what makes default-left a direction), categorical (all), and
    cat_subset (the categorical ones wider than max_cat_to_onehot: the
    sorted-subset search); and the training rows x categorical columns
    that sit in an other bin (binning.BinMapper._categorical: cut,
    unseen, negative, NaN), where the host bin matrix is unbundled."""
    r = _default
    if not r.enabled:
        return
    from ..binning import BinType

    cats = [m for m in mappers if m.bin_type == BinType.CATEGORICAL]
    nums = [m for m in mappers if m.bin_type != BinType.CATEGORICAL]
    g = r.gauge("lgbmtpu_dataset_columns",
                "used columns of the newest training Dataset, by kind "
                "(numerical | with_nan | categorical | cat_subset)",
                labels=("kind",))
    g.set(len(nums), kind="numerical")
    g.set(sum(m.nan_bin >= 0 for m in nums), kind="with_nan")
    g.set(len(cats), kind="categorical")
    g.set(sum(m.num_bin > max_cat_to_onehot for m in cats),
          kind="cat_subset")
    if cat_other_rows is not None:
        r.gauge("lgbmtpu_dataset_cat_other_rows",
                "training rows x categorical columns that sit in an "
                "other bin (no kept category owns the value)"
                ).set(cat_other_rows)


def record_tree_splits(tree, mappers, max_cat_to_onehot: int) -> None:
    """Splits of one host tree by kind, counted where the fused collect
    builds the host trees (boosting._materialize: host arrays already
    read back, no device op): numerical (missing goes right),
    default_left (numerical, the default-left bit set), cat_onehot and
    cat_subset (a categorical split on a column of at most / more than
    max_cat_to_onehot bins: one-vs-rest / the sorted-subset scan,
    whatever the size of the set it found)."""
    r = _default
    if not r.enabled or not len(tree.split_feature):
        return
    n = dict.fromkeys(
        ("numerical", "default_left", "cat_onehot", "cat_subset"), 0)
    for f, dt in zip(tree.split_feature, tree.decision_type):
        if int(dt) & 1:
            wide = mappers[int(f)].num_bin > max_cat_to_onehot
            n["cat_subset" if wide else "cat_onehot"] += 1
        else:
            n["default_left" if int(dt) & 2 else "numerical"] += 1
    c = r.counter("lgbmtpu_tree_splits_total",
                  "splits of the trees built, by kind (numerical | "
                  "default_left | cat_onehot | cat_subset)",
                  labels=("kind",))
    for kind, count in n.items():
        c.inc(float(count), kind=kind)


def record_label_cache(kind: str, hit: bool) -> None:
    """One lookup of a data set's label-sized residency
    (dataset.BinnedDataset.device_label / device_weight / label_stat):
    kind is label | weight | stats."""
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_dataset_label_cache_total",
              "lookups of the per-Dataset label / weight device copies "
              "and label statistics, by kind and result",
              labels=("kind", "result")
              ).inc(1, kind=kind, result="hit" if hit else "miss")


def record_rank_layout(which: str, layout, reference_pairs=None,
                       pair_slots=None) -> None:
    """Gauges of a data set's ragged query layout (learner/ranking.py):
    `which` is train (set by the ranking objective, with the pair
    counts of one gradient evaluation) or eval (set when a ranking
    metric binds to an eval set)."""
    r = _default
    if not r.enabled:
        return
    values = {
        "lgbmtpu_rank_real_docs": (
            "documents of the query layout", layout.real_docs),
        "lgbmtpu_rank_padded_docs": (
            "document slots of the bucket grids, padding included",
            layout.padded_docs),
        "lgbmtpu_rank_buckets": (
            "query-length buckets of the layout", layout.num_buckets),
        "lgbmtpu_rank_reference_pairs": (
            "pairs the reference's LambdaRank loop visits per gradient "
            "evaluation (each rank below the truncation level against "
            "every lower rank)", reference_pairs),
        "lgbmtpu_rank_pair_slots": (
            "pair slots the device forms per gradient evaluation",
            pair_slots),
    }
    for name, (help_text, value) in values.items():
        if value is not None:
            r.gauge(name, help_text, labels=("set",)).set(value, set=which)


# gateway bridges (serving/gateway.py). Label/naming conventions in
# docs/OBSERVABILITY.md "Gateway metrics": outcome is the GATEWAY
# verdict (ok/failed/shed/deadline/unavailable/drain/fanout_partial),
# result is one ATTEMPT's fate (ok/5xx/error/cancelled), breaker state
# renders as a numeric gauge (0 closed / 1 half_open / 2 open) plus a
# transitions counter.
_BREAKER_STATE_CODE = {"closed": 0, "half_open": 1, "open": 2}


def record_gateway_request(op: str, outcome: str, seconds: float) -> None:
    """One client request through Gateway.handle, end to end."""
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_gateway_requests_total",
              "gateway client requests, by op and outcome",
              labels=("op", "outcome")).inc(1, op=op, outcome=outcome)
    r.histogram("lgbmtpu_gateway_request_seconds",
                "gateway end-to-end request latency (incl. retries "
                "and hedges)", labels=("op",)).observe(seconds, op=op)


def record_gateway_attempt(backend: str, result: str) -> None:
    """One backend attempt (primary, retry, or hedge)."""
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_gateway_attempts_total",
              "backend attempts, by backend and result",
              labels=("backend", "result")).inc(
        1, backend=backend, result=result)


def record_gateway_retry() -> None:
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_gateway_retries_total",
              "retry rounds scheduled (full-jitter backoff)").inc(1)


def record_gateway_hedge(outcome: str) -> None:
    """Hedge verdicts: ``fired`` / ``won`` / ``denied_budget`` /
    ``no_backend``."""
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_gateway_hedges_total",
              "hedged-attempt verdicts, by outcome",
              labels=("outcome",)).inc(1, outcome=outcome)


def record_gateway_breaker(backend: str, state: str) -> None:
    """Breaker transition: new state as a coded gauge + a counter."""
    r = _default
    if not r.enabled:
        return
    r.gauge("lgbmtpu_gateway_breaker_state",
            "circuit state per backend (0 closed, 1 half_open, 2 open)",
            labels=("backend",)).set(
        _BREAKER_STATE_CODE.get(state, -1), backend=backend)
    r.counter("lgbmtpu_gateway_breaker_transitions_total",
              "breaker transitions, by backend and destination state",
              labels=("backend", "to")).inc(1, backend=backend, to=state)


def record_gateway_pool(alive: int, ready: int, total: int) -> None:
    r = _default
    if not r.enabled:
        return
    r.gauge("lgbmtpu_gateway_backends_alive",
            "backends answering HTTP at the last probe sweep"
            ).set(alive)
    r.gauge("lgbmtpu_gateway_backends_ready",
            "backends passing /readyz at the last probe sweep"
            ).set(ready)
    r.gauge("lgbmtpu_gateway_backends_total",
            "configured backend slots").set(total)


def record_native_build(seconds: float, ok: bool) -> None:
    r = _default
    if not r.enabled:
        return
    r.counter("lgbmtpu_native_builds_total",
              "native fastparse toolchain builds",
              labels=("result",)).inc(1, result="ok" if ok else "failed")
    r.gauge("lgbmtpu_native_build_seconds",
            "wall seconds of the most recent native build").set(seconds)
