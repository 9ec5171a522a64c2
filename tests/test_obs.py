"""Observability layer (lightgbm_tpu/obs, docs/OBSERVABILITY.md):
metrics registry + Prometheus exposition, trace-event export, run
manifests, bench_serve artifact, and the no-callback re-audit."""

from __future__ import annotations

import contextlib
import importlib.util
import json
import re
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import boosting, log
from lightgbm_tpu.obs import tracing
from lightgbm_tpu.obs.metrics import MetricsRegistry, default_registry

REPO = Path(__file__).resolve().parents[1]


def _train(params, X, y, rounds=5):
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    p = {"verbosity": -1, **params}
    return lgb.train(p, ds, num_boost_round=rounds)


# ----------------------------------------------------------------- metrics
def test_registry_counter_gauge_histogram():
    r = MetricsRegistry(enabled=True)
    c = r.counter("c_total", "a counter", labels=("op",))
    c.inc(op="score")
    c.inc(2.5, op="score")
    c.inc(op="load")
    assert c.value(op="score") == 3.5
    assert c.value(op="load") == 1.0
    with pytest.raises(ValueError):
        c.inc(-1, op="score")  # counters are monotone
    with pytest.raises(ValueError):
        c.inc(1, bad_label="x")  # undeclared label

    g = r.gauge("g")
    g.set(4.0)
    g.inc()
    g.dec(2.0)
    assert g.value() == 3.0

    h = r.histogram("h_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    s = h.state()
    assert s["count"] == 3 and s["counts"] == [1, 2]
    assert abs(s["sum"] - 5.55) < 1e-9

    # re-registration returns the same object; mismatch raises
    assert r.counter("c_total", labels=("op",)) is c
    with pytest.raises(ValueError):
        r.gauge("c_total")
    with pytest.raises(ValueError):
        r.counter("c_total", labels=("other",))


def test_registry_disabled_is_noop_and_reset():
    r = MetricsRegistry(enabled=False)
    c = r.counter("c_total")
    c.inc()
    assert c.value() == 0.0
    r.enable()
    c.inc()
    assert c.value() == 1.0
    r.reset()
    assert c.value() == 0.0


_PROM_LINE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (\S+)$'
)


def _parse_prom(text):
    """Parse text exposition into {(name, frozenset(labels)): value},
    asserting every non-comment line matches the format."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _PROM_LINE.match(line)
        assert m, f"invalid exposition line: {line!r}"
        labels = frozenset(
            re.findall(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"',
                       m.group(2) or "")
        )
        out[(m.group(1), labels)] = float(m.group(3))
    return out


def test_metrics_endpoint_matches_registry_stats(rng):
    """/metrics exposition parses, and the scraped serving-latency
    values agree with ModelRegistry.stats() — one LatencyStats ring
    behind both readers (the dedupe contract)."""
    import urllib.request

    from lightgbm_tpu.serving import ModelRegistry, serve_http

    X = rng.randn(500, 4)
    bst = _train({"objective": "regression", "num_leaves": 15},
                 X, X[:, 0] + X[:, 1])
    reg = ModelRegistry()
    reg.load("obs", bst)
    httpd = serve_http(reg, port=0, block=False)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        body = json.dumps({"rows": X[:32].tolist(), "model": "obs"}).encode()
        req = urllib.request.Request(
            base + "/v1/score", data=body,
            headers={"Content-Type": "application/json"},
        )
        for _ in range(3):
            with urllib.request.urlopen(req, timeout=30) as r:
                assert json.loads(r.read())["ok"]

        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            health = json.loads(r.read())
        assert health["ok"] and "obs" in health["models"]

        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            scraped = _parse_prom(r.read().decode())
        stats = reg.stats()["obs"]

        entry = frozenset({("entry", "serve:obs")})
        assert scraped[("lgbmtpu_serve_requests_total", entry)] == \
            stats["count"]
        assert scraped[("lgbmtpu_serve_rows_total", entry)] == stats["rows"]
        for stat in ("p50", "p95", "p99", "mean"):
            key = ("lgbmtpu_serve_latency_ms",
                   frozenset({("entry", "serve:obs"), ("stat", stat)}))
            assert scraped[key] == pytest.approx(stats[f"{stat}_ms"])
        # the serve-loop op counter rode the same scrape
        score_ops = [
            v for (name, labels), v in scraped.items()
            if name == "lgbmtpu_serve_protocol_requests_total"
            and ("op", "score") in labels
        ]
        assert score_ops and score_ops[0] >= 3
        # bucket-ladder dispatch accounting is present for this entry
        assert any(
            name == "lgbmtpu_serve_bucket_dispatch_total"
            and ("entry", "serve:obs") in labels
            for (name, labels) in scraped
        )
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_latency_stats_reset_and_shared_ring():
    from lightgbm_tpu.timer import latency_stats

    s = latency_stats("obs-reset-test")
    s.observe(0.010, rows=8)
    assert s.snapshot()["count"] == 1
    s.reset()
    snap = s.snapshot()
    assert snap["count"] == 0 and snap["rows"] == 0 and snap["p99_ms"] == 0
    # same name -> same object (the one-source-of-truth registry)
    assert latency_stats("obs-reset-test") is s


# ----------------------------------------------------------------- tracing
def test_trace_export_fused_round_spans(rng, tmp_path, monkeypatch):
    """Chrome trace-event JSON loads and carries one fused-round span
    per DISPATCH: a 4-round training is one chunk-scan launch (one
    span covering all 4 rounds); under a chunk ladder of (1,) it is
    one span per round."""
    X = rng.randn(400, 4)
    y = (X[:, 0] > 0).astype(np.float32)
    path = tmp_path / "trace.json"
    with tracing.tracing(chrome_path=str(path)) as rec:
        _train({"objective": "binary", "num_leaves": 7}, X, y, rounds=4)
    data = json.loads(path.read_text())
    assert "traceEvents" in data
    spans = [e for e in data["traceEvents"] if e.get("ph") == "X"]
    for e in spans:
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert "pid" in e and "tid" in e and e["name"]
    fused = [e for e in spans if e["name"] == boosting.FUSED_ROUND_PHASE]
    assert len(fused) == 1  # 4 rounds = one chunk dispatch
    assert rec.events()  # recorder still readable after export
    # per-round dispatch keeps the one-span-per-round stream
    import lightgbm_tpu.config as cfg

    monkeypatch.setattr(cfg, "DEFAULT_CHUNK_LADDER", (1,))
    path2 = tmp_path / "trace_ladder_1.json"
    with tracing.tracing(chrome_path=str(path2)):
        _train({"objective": "binary", "num_leaves": 7}, X, y, rounds=4)
    data2 = json.loads(path2.read_text())
    fused2 = [e for e in data2["traceEvents"]
              if e.get("ph") == "X"
              and e["name"] == boosting.FUSED_ROUND_PHASE]
    assert len(fused2) == 4


def test_trace_eager_path_has_every_round_phase(rng):
    """The eager (non-fused) training loop emits a span for EVERY
    per-round phase: gradients, grow, score update."""
    X = rng.randn(400, 4)
    y = (X[:, 0] > 0).astype(np.float32)

    def cb(env):
        return None

    cb.before_iteration = True  # pre-iteration callbacks force non-fused
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    with tracing.tracing() as rec:
        lgb.train({"objective": "binary", "num_leaves": 7,
                   "verbosity": -1}, ds, num_boost_round=3, callbacks=[cb])
    names = {e["name"] for e in rec.events() if e.get("ph") == "X"}
    for phase in boosting.ROUND_PHASES:
        assert phase in names, f"missing per-round phase span {phase!r}"


_VALID_PH = {"X", "B", "E", "i", "I", "C", "M", "b", "e", "n", "s", "t", "f"}


def _validate_chrome_trace(data):
    """Schema-validate a Chrome trace-event export: required fields per
    phase type, numeric timestamps, and B/E begin/end events paired per
    (pid, tid, name)."""
    assert "traceEvents" in data
    open_stacks = {}
    for e in data["traceEvents"]:
        ph = e.get("ph")
        assert ph in _VALID_PH, f"unknown phase type {ph!r}: {e}"
        assert e.get("name"), f"event missing name: {e}"
        assert "pid" in e, f"event missing pid: {e}"
        if ph != "M":  # metadata events carry no timestamp
            assert isinstance(e.get("ts"), (int, float)), e
            assert "tid" in e or ph == "C", f"event missing tid: {e}"
        if ph == "X":
            assert isinstance(e.get("dur"), (int, float)) and e["dur"] >= 0
        if ph == "B":
            open_stacks.setdefault((e["pid"], e["tid"]), []).append(e["name"])
        if ph == "E":
            stack = open_stacks.get((e["pid"], e["tid"]))
            assert stack, f"E event without matching B: {e}"
            stack.pop()
    dangling = {k: v for k, v in open_stacks.items() if v}
    assert not dangling, f"unclosed B events: {dangling}"


def test_trace_export_schema_valid(rng, tmp_path):
    """The full Chrome export passes trace-event schema validation
    (required ph/ts/pid/tid/name fields, paired B/E or complete X),
    including instant + metadata events."""
    X = rng.randn(300, 4)
    y = (X[:, 0] > 0).astype(np.float32)
    path = tmp_path / "trace.json"
    with tracing.tracing(chrome_path=str(path)) as rec:
        _train({"objective": "binary", "num_leaves": 7}, X, y, rounds=2)
        rec.add_instant("checkpoint", {"k": 1})
    _validate_chrome_trace(json.loads(path.read_text()))


def test_trace_validation_catches_unpaired_begin():
    """The validator itself is red-to-green: a B without its E fails."""
    bad = {"traceEvents": [
        {"name": "x", "ph": "B", "ts": 0.0, "pid": 1, "tid": 1},
    ]}
    with pytest.raises(AssertionError, match="unclosed B"):
        _validate_chrome_trace(bad)


# ------------------------------------- program spans in a profiler trace
@pytest.fixture(scope="module")
def profiled_job(tmp_path_factory):
    """The same tiny 8-round fused job twice: bare (no profiler, no
    timetag, no sink), then under a jax.profiler session. Returns the
    phase-timer summary before and after the bare run, both model
    texts, and the `lgbm:` host events of the profiled run, per thread
    line."""
    import jax
    from jax.profiler import ProfileData

    from lightgbm_tpu.timer import TRACE_PREFIX, global_timer

    rs = np.random.RandomState(11)
    X = rs.randn(600, 5)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    vs = lgb.Dataset(X[:200], label=y[:200], reference=ds,
                     free_raw_data=False)
    ds.construct()
    vs.construct()

    def job():
        return lgb.train(
            {"objective": "binary", "num_leaves": 7, "metric": "auc",
             "verbosity": -1}, ds, num_boost_round=8, valid_sets=[vs],
            valid_names=["valid"]).model_to_string()

    was_enabled = global_timer.enabled
    global_timer.disable()
    try:
        summary_before = global_timer.summary()
        bare = job()
        summary_after = global_timer.summary()
        out = tmp_path_factory.mktemp("xplane")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # spans only, not every Python call
        jax.profiler.start_trace(str(out), profiler_options=opts)
        try:
            profiled = job()
        finally:
            jax.profiler.stop_trace()
    finally:
        global_timer.enabled = was_enabled
    (pb,) = out.glob("plugins/profile/*/*.xplane.pb")
    lines = []
    for plane in ProfileData.from_file(str(pb)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(
                ((e.name, e.start_ns, e.start_ns + e.duration_ns)
                 for e in line.events if e.name.startswith(TRACE_PREFIX)),
                key=lambda e: (e[1], -e[2]))
            if evs:
                lines.append(evs)
    return {"summaries": (summary_before, summary_after), "bare": bare,
            "profiled": profiled, "lines": lines}


def test_program_spans_nest_in_the_profiler_trace(profiled_job):
    """ACCEPTANCE: under a profiler session one lgb.train call leaves
    its layer-boundary spans as `lgbm:` host events on ONE thread, in
    order, nested the way the call nests."""
    (events,) = profiled_job["lines"]  # one thread holds them all
    by_name = {}
    for name, s, e in events:
        by_name.setdefault(name, []).append((s, e))
    (train,) = by_name["lgbm:engine.train"]
    assert all(train[0] <= s and e <= train[1] for _, s, e in events)
    order = ["lgbm:engine.booster_init", "lgbm:boosting.fused_start",
             "lgbm:fused dispatch", "lgbm:fused collect (readback)"]
    spans = []
    for n in order:
        (one,) = by_name[n]
        spans.append(one)
    for (_, e0), (s1, _) in zip(spans, spans[1:]):
        assert e0 <= s1, "layer-boundary spans out of order or overlapping"
    (fs,) = by_name["lgbm:boosting.fused_start"]
    for child in ("lgbm:objective.boost_from_score",
                  "lgbm:boosting.build_step"):
        (c,) = by_name[child]
        assert fs[0] <= c[0] and c[1] <= fs[1], child
    (bi,) = by_name["lgbm:engine.booster_init"]
    for child in ("lgbm:boosting.objective_init",
                  "lgbm:boosting.device_inputs"):
        (c,) = by_name[child]
        assert bi[0] <= c[0] and c[1] <= bi[1], child
    # the train set and the valid set each get their score arrays
    assert len(by_name["lgbm:boosting.score_init"]) == 2
    assert all(bi[0] <= s and e <= bi[1]
               for s, e in by_name["lgbm:boosting.score_init"])
    (fd,) = by_name["lgbm:fused dispatch"]
    steps = by_name["lgbm:" + boosting.FUSED_ROUND_PHASE]
    assert len(steps) == 2  # 8 rounds = two rung-4 chunk dispatches
    assert all(fd[0] <= s and e <= fd[1] for s, e in steps)
    for n in ("lgbm:engine.callbacks", "lgbm:engine.finish",
              "lgbm:materialize host trees (readback)"):
        assert n in by_name, n


def test_program_spans_are_per_call_not_per_round(profiled_job):
    """An 8-round fused job opens at most 40 spans: none sits inside a
    per-round, per-leaf or per-row loop of the fused path."""
    n = sum(len(evs) for evs in profiled_job["lines"])
    assert 10 <= n <= 40, n


def test_spans_without_a_profiler_record_and_change_nothing(profiled_job):
    """No profiler, no timetag, no sink: the scopes accumulate nothing,
    and the model is byte-identical to the profiled run's."""
    before, after = profiled_job["summaries"]
    # (empty, unless an earlier test of this process left entries)
    assert after == before
    assert profiled_job["bare"] == profiled_job["profiled"]


# ------------------------------------------------------------ device phases
_PHASE_TOKEN = re.compile(r"lgbm\.([a-z_.]+)")


def _drop_traces():
    import jax

    boosting._FUSED_STEP_CACHE.clear()
    jax.clear_caches()


@contextlib.contextmanager
def _compiling_here():
    """No persistent compile cache inside: an executable loaded from it
    carries the op_names of whichever program filled it (the cache key
    strips debug info), and a jit's first dispatch hands what it loaded
    to every later `.lower().compile()` of the same trace."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def _fused_step_text(params, X, y, group=None):
    """(compiled text of the one-round fused step, model text) of a tiny
    job with one valid set on the chip's default program (rounds grower,
    int16 channels, true-gradient leaf renewal)."""
    kw = {"group": group} if group is not None else {}
    ds = lgb.Dataset(X, label=y, free_raw_data=False, **kw)
    vkw = {"group": group[:len(group) // 2]} if group is not None else {}
    nv = sum(vkw["group"]) if vkw else 200
    vs = lgb.Dataset(X[:nv], label=y[:nv], reference=ds,
                     free_raw_data=False, **vkw)
    with _compiling_here():
        bst = lgb.train(
            {"verbosity": -1, "num_leaves": 7, "min_data_in_leaf": 5,
             "tpu_growth_mode": "rounds", "tpu_hist_dtype": "int16",
             **params},
            ds, num_boost_round=2, valid_sets=[vs], valid_names=["valid"])
        g = bst._gbdt
        text = g._f_program.chunk(1).lower(
            g._fstate, g._f_data).compile().as_text()
    return text, bst.model_to_string()


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("case", ["binary", "lambdarank", "data_mesh"])
def test_fused_step_carries_every_phase_it_can_reach(case, monkeypatch):
    """The compiled fused step names its device work from inside: every
    phase of timer.DEVICE_PHASES that the configuration can reach is in
    some instruction's op_name, and no `lgbm.` token lies outside the
    vocabulary (the benchmark's readers search for that prefix)."""
    from lightgbm_tpu import config
    from lightgbm_tpu.timer import DEVICE_PHASES, DEVICE_PREFIX

    assert DEVICE_PREFIX == "lgbm." and 10 <= len(DEVICE_PHASES) <= 14
    rs = np.random.RandomState(11)
    X = rs.randn(600, 5)
    _drop_traces()
    if case == "lambdarank":
        group = [20] * 30
        y = rs.randint(0, 4, 600).astype(np.float32)
        params = {"objective": "lambdarank", "metric": "ndcg",
                  "eval_at": [3]}
    else:
        group = None
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
        params = {"objective": "binary", "metric": "auc"}
        if case == "data_mesh":
            params["tree_learner"] = "data"
    monkeypatch.setattr(config, "DEFAULT_CHUNK_LADDER", (1,))
    text, _ = _fused_step_text(params, X, y, group)
    found = {m for n in _op_names(text) for m in _PHASE_TOKEN.findall(n)}
    assert found <= set(DEVICE_PHASES), found - set(DEVICE_PHASES)
    want = set(DEVICE_PHASES)
    if case != "data_mesh":
        want -= {"parallel.reduce"}  # nothing crosses a mesh of one
    assert found >= want, want - found
    # scopes nest and the innermost names the op
    assert any("lgbm.learner.route/lgbm.learner.hist" in n.replace(
        "vmap(", "").replace(")", "") or "lgbm.learner.select/" in n
        for n in _op_names(text))
    _drop_traces()


def test_device_phases_change_no_equation_and_no_model(monkeypatch):
    """A named scope is metadata: with `device_phase` a null context the
    fused step has the same instructions under other names, and the
    model text is the same byte for byte."""
    from lightgbm_tpu import config, timer
    from lightgbm_tpu.learner import rounds as rounds_mod
    from lightgbm_tpu.parallel import data_parallel

    rs = np.random.RandomState(11)
    X = rs.randn(600, 5)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    params = {"objective": "binary", "metric": "auc"}
    _drop_traces()
    monkeypatch.setattr(config, "DEFAULT_CHUNK_LADDER", (1,))
    text, model = _fused_step_text(params, X, y)
    assert any("lgbm." in n for n in _op_names(text))
    for mod in (timer, boosting, rounds_mod, data_parallel):
        monkeypatch.setattr(mod, "device_phase",
                            lambda name: contextlib.nullcontext())
    _drop_traces()
    bare_text, bare_model = _fused_step_text(params, X, y)
    assert not {m for n in _op_names(bare_text)
                for m in _PHASE_TOKEN.findall(n)}
    assert bare_model == model

    def instructions(t):
        """(result shape, opcode) of every instruction, in order: XLA
        names an instruction after its metadata, so names differ."""
        found = (re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", ln)
                 for ln in t.splitlines())
        return [m.groups() for m in found if m]

    assert len(instructions(text)) > 1000
    assert instructions(bare_text) == instructions(text)
    _drop_traces()


def test_device_phase_fails_at_trace_time_outside_the_vocabulary():
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.timer import device_phase

    def f(x):
        with device_phase("nonsense"):
            return x + 1

    with pytest.raises(KeyError, match="nonsense"):
        jax.jit(f).lower(jnp.zeros(3))
    with device_phase("learner.hist"):
        pass  # outside a trace a scope is nothing


def test_timer_scope_names_no_device_work():
    """Timer.scope is a HOST span: what is traced inside it compiles to
    the same module, op_names included, with the timer on or off."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.timer import Timer

    def text(enabled):
        t = Timer()
        t.enabled = enabled
        with t.scope("round: fused step"):
            return jax.jit(lambda x: jnp.tanh(x) * 2).lower(
                jnp.zeros(8)).compile().as_text()

    on, off = _op_names(text(True)), _op_names(text(False))
    assert on == off and any(n.endswith("/tanh") for n in on)
    assert not any("round" in n for n in on)


def test_compile_counters_keep_seconds_by_stage():
    """compile_counters() splits compile time into trace / lower /
    backend-compile seconds (a nested jit's trace is not counted
    twice); the two event counts count as before."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.analysis import retrace

    retrace.ensure_installed()

    @jax.jit
    def inner(x):
        return jnp.tanh(x) * 3.0

    def fresh(x):
        return inner(x).sum() + inner(x + 1).sum()

    x = jnp.asarray(np.arange(7, dtype=np.float32))
    before = retrace.compile_counters()
    t0 = time.perf_counter()
    jax.jit(fresh)(x).block_until_ready()
    wall = time.perf_counter() - t0
    after = retrace.compile_counters()
    assert after["jaxpr_traces"] >= before["jaxpr_traces"] + 2  # + inner
    assert after["backend_compiles"] == before["backend_compiles"] + 1
    grew = {k: after[k] - before[k]
            for k in ("trace_s", "lower_s", "backend_compile_s")}
    assert all(v > 0 for v in grew.values()), grew
    # wall seconds, so the three stages fit inside the call
    assert sum(grew.values()) <= wall
    assert after["cache_load_s"] >= before["cache_load_s"]
    assert after["cache_load_s"] <= after["backend_compile_s"]


# -------------------------------------------------------------- aggregate
def test_two_registry_snapshot_merge():
    """ACCEPTANCE: two independent registries (the two-process stand-in
    on the collective-less CPU backend) merge host-side — counters sum,
    gauges sum with min/max spread, no jax collective anywhere."""
    from lightgbm_tpu.obs import aggregate

    r1 = MetricsRegistry(enabled=True)
    r2 = MetricsRegistry(enabled=True)
    for i, r in enumerate((r1, r2)):
        r.counter("fleet_rounds_total", "rounds", labels=("entry",)).inc(
            10 * (i + 1), entry="train")
        r.gauge("fleet_trees_per_sec", "tps").set(5.0 * (i + 1))
    snaps = [
        aggregate.snapshot_dict(r, process=i)
        for i, r in enumerate((r1, r2))
    ]
    merged = aggregate.merge(snaps)
    assert merged["processes"] == 2
    ctr = merged["metrics"]["fleet_rounds_total"]
    assert ctr["values"]['{entry="train"}'] == 30.0
    assert "min" not in ctr  # counters are additive, no spread
    g = merged["metrics"]["fleet_trees_per_sec"]
    assert g["values"][""] == 15.0  # fleet throughput = sum
    assert g["min"][""] == 5.0 and g["max"][""] == 10.0


def test_snapshot_file_roundtrip_and_merge(tmp_path):
    from lightgbm_tpu.obs import aggregate

    r1 = MetricsRegistry(enabled=True)
    r1.counter("c_total").inc(3)
    p1 = tmp_path / "metrics_rank00000.json"
    aggregate.write_snapshot(str(p1), r1, process=0)
    snap = aggregate.read_snapshot(str(p1))
    assert snap["metrics"]["c_total"]["kind"] == "counter"
    merged = aggregate.merge_files([str(p1)])
    assert merged["metrics"]["c_total"]["values"][""] == 3.0
    # a non-snapshot json is rejected loudly
    bad = tmp_path / "other.json"
    bad.write_text("{}")
    with pytest.raises(ValueError, match="not a metrics snapshot"):
        aggregate.read_snapshot(str(bad))


@pytest.mark.slow
def test_prometheus_parse_and_http_pull_merge(rng):
    """Fleet aggregation's HTTP leg: scrape two /metrics bodies (one
    live worker endpoint + one rendered registry) and merge them —
    exactly what a multi-replica serving fleet view does."""
    from lightgbm_tpu.obs import aggregate
    from lightgbm_tpu.serving import ModelRegistry, serve_http

    X = rng.randn(400, 4)
    bst = _train({"objective": "regression", "num_leaves": 7},
                 X, X[:, 0])
    reg = ModelRegistry()
    reg.load("agg", bst)
    reg.predict("agg", X[:16].astype(np.float32))
    httpd = serve_http(reg, port=0, block=False)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        pulled = aggregate.pull_snapshot(url, process=0)
        assert any(
            name.startswith("lgbmtpu_") for name in pulled["metrics"]
        )
        # parse a rendered exposition as the "second worker"
        local = aggregate.parse_prometheus(
            default_registry().render_prometheus(), process=1
        )
        merged = aggregate.merge([pulled, local])
        assert merged["processes"] == 2
        # the pulled sample and the local sample describe the same
        # registry here, so the merged counter is exactly double
        name = "lgbmtpu_serve_rows_total"
        key = '{entry="serve:agg"}'
        assert merged["metrics"][name]["values"][key] == \
            2 * pulled["metrics"][name]["values"][key]
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_multihost_fleet_snapshot_files(tmp_path):
    """parallel.multihost's fleet helpers: write this process's
    snapshot, merge the directory — file-based, no collectives."""
    from lightgbm_tpu.obs.metrics import default_registry
    from lightgbm_tpu.parallel.multihost import (
        merged_fleet_snapshot,
        write_metrics_snapshot,
    )

    default_registry().counter("fleet_probe_total").inc(2)
    path = write_metrics_snapshot(str(tmp_path))
    assert Path(path).name == "metrics_rank00000.json"
    merged = merged_fleet_snapshot(str(tmp_path))
    assert merged["metrics"]["fleet_probe_total"]["values"][""] >= 2.0
    with pytest.raises(FileNotFoundError):
        merged_fleet_snapshot(str(tmp_path / "empty"))


def test_obs_report_renders(tmp_path, capsys):
    """tools/obs_report.py renders snapshots + recorder streams."""
    import importlib.util as ilu

    from lightgbm_tpu.obs import aggregate

    r = MetricsRegistry(enabled=True)
    r.counter("c_total").inc(1)
    snap = tmp_path / "metrics_rank00000.json"
    aggregate.write_snapshot(str(snap), r, process=0)
    rec = tmp_path / "run.jsonl"
    rec.write_text(
        json.dumps({"schema": "lightgbm-tpu/flight-record/v1"}) + "\n"
        + json.dumps({"round": 0, "evals": {"v l2": 1.0},
                      "trees_per_sec": 2.0}) + "\n"
    )
    spec = ilu.spec_from_file_location(
        "obs_report", REPO / "tools" / "obs_report.py"
    )
    mod = ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--snapshots", str(snap), "--recorder", str(rec)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "fleet metrics" in out and "c_total" in out
    assert "flight record" in out and "round 0" in out


# ---------------------------------------------------------------- manifest
def test_run_manifest_schema_and_static_wire_budget(rng, tmp_path):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.obs.manifest import SCHEMA, write_manifest

    X = rng.randn(300, 4)
    bst = _train({"objective": "regression", "num_leaves": 7}, X, X[:, 0])
    cfg = Config({"objective": "regression", "num_leaves": 7})
    out = tmp_path / "manifest.json"
    m = write_manifest(str(out), config=cfg, booster=bst,
                       extra={"note": "test"})
    on_disk = json.loads(out.read_text())
    assert on_disk["schema"] == SCHEMA
    assert m["config"]["resolved"]["objective"] == "regression"
    assert m["devices"]["device_count"] >= 1
    assert {"jaxpr_traces", "backend_compiles"} <= set(m["compile"])
    assert m["model"]["num_trees"] == bst.num_trees()
    # static wire pins ride along verbatim from cost_budget.json
    budget = json.loads(
        (REPO / "lightgbm_tpu" / "analysis" / "cost_budget.json").read_text()
    )
    static = m["collectives"]["static_budget_wire_bytes"]
    assert static == {k: v["wire_bytes"] for k, v in budget.items()}
    assert m["collectives"]["runtime_wire_bytes_estimate"] >= 0


def test_data_parallel_runtime_wire_counter(rng):
    """tree_learner=data training ticks the runtime collective
    wire-bytes counter (the manifest's runtime side)."""
    reg = default_registry()
    c = reg.counter("lgbmtpu_collective_wire_bytes_total",
                    labels=("entry",))
    before = c.value(entry="data_parallel_grow")
    X = rng.randn(600, 4)
    y = (X[:, 0] > 0).astype(np.float32)
    _train({"objective": "binary", "num_leaves": 7,
            "tree_learner": "data"}, X, y, rounds=3)
    after = c.value(entry="data_parallel_grow")
    assert after > before


@pytest.mark.parametrize("extra", [{}, {"ladder": (1,)},
                                   {"record_file": "rec.jsonl"}],
                         ids=["chunk_scan", "ladder_1", "recorded"])
def test_grower_rounds_counter_rides_the_eval_readback(rng, tmp_path, extra,
                                                       monkeypatch):
    """lgbmtpu_grower_rounds_total{width}: the rounds grower's per-width
    round counts end the fused step's eval row, so they arrive with the
    readback fused_collect already makes; the caller's evals and the
    recorder's gh norms read the row as before."""
    from lightgbm_tpu.learner.rounds import ladder_widths

    c = default_registry().counter("lgbmtpu_grower_rounds_total",
                                   labels=("width",))
    X = rng.randn(3000, 5)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    extra = dict(extra)
    if "ladder" in extra:  # one dispatch per round: five (1, E) stacks
        import lightgbm_tpu.config as cfg

        monkeypatch.setattr(cfg, "DEFAULT_CHUNK_LADDER", extra.pop("ladder"))
    if "record_file" in extra:
        extra["record_file"] = str(tmp_path / extra["record_file"])
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    before = {w: c.value(width=str(w)) for w in (8, 16)}
    ev = {}
    bst = lgb.train(
        {"verbosity": -1, "objective": "binary", "num_leaves": 63,
         "metric": "auc", "min_data_in_leaf": 5,
         "tpu_growth_mode": "rounds", **extra},
        ds, num_boost_round=5, valid_sets=[ds], valid_names=["tr"],
        callbacks=[lgb.record_evaluation(ev)])
    g = bst._gbdt
    widths = ladder_widths(g.spec)
    assert widths[:2] == (8, 16) and g._f_ladder_widths == widths
    delta = {w: c.value(width=str(w)) - before[w] for w in (8, 16)}
    # five trees: each doubles 1, 2, 4, 8 candidates at 8 slots, then
    # has at least one round of 9-16
    assert delta[8] >= 20 and delta[16] >= 5
    assert all(float(v).is_integer() for v in delta.values())
    assert list(ev["tr"]) == ["auc"] and len(ev["tr"]["auc"]) == 5
    assert all(0.5 < a <= 1.0 for a in ev["tr"]["auc"])
    if "record_file" in extra:
        assert len(g._last_gh_rows) > 0
        assert all(gn > 0 and hn > 0 for gn, hn in g._last_gh_rows)


@pytest.mark.parametrize("extra,ends_on_budget", [
    ({}, True), ({"min_gain_to_split": 40.0}, False),
    ({"monotone_constraints": [1, 0, 0, 0, 0],
      "monotone_constraints_method": "intermediate"}, None),
], ids=["leaf_budget", "stops_on_gain", "conflict_guard"])
def test_grower_rounds_counter_routing_only_rounds(rng, extra,
                                                   ends_on_budget):
    """lgbmtpu_grower_rounds_total{width="route"}: the rounds that only
    routed rows, one per tree that ends on its leaf budget, on the same
    readback as the per-width counts. A tree that runs out of positive
    gain first has none, and neither has one grown under the monotone
    conflict guard (its last round is not known before its data pass);
    the per-width counts then hold every round."""
    from lightgbm_tpu.learner.rounds import ROUTE_LABEL, ladder_widths

    c = default_registry().counter("lgbmtpu_grower_rounds_total",
                                   labels=("width",))
    X = rng.randn(3000, 5)
    y = X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.1 * rng.randn(3000)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    params = {"verbosity": -1, "objective": "regression", "num_leaves": 31,
              "metric": "l2", "min_data_in_leaf": 5,
              "tpu_growth_mode": "rounds", **extra}
    labels = ["8", "16", "25", ROUTE_LABEL]
    before = {w: c.value(width=w) for w in labels}
    bst = lgb.train(params, ds, num_boost_round=4, valid_sets=[ds],
                    valid_names=["tr"])
    delta = {w: c.value(width=w) - before[w] for w in labels}
    assert [str(w) for w in ladder_widths(bst._gbdt.spec)] == labels[:-1]
    leaves = [t.num_leaves for t in bst._gbdt.models]
    if ends_on_budget:
        assert leaves == [31] * 4 and delta[ROUTE_LABEL] == 4
    elif ends_on_budget is None:
        assert bst._gbdt.spec.mono_mode == 1 and leaves == [31] * 4
        assert delta[ROUTE_LABEL] == 0
    else:
        assert 1 < max(leaves) < 31 and delta[ROUTE_LABEL] == 0
    assert delta["8"] >= 4 and sum(delta.values()) >= 4 * 3
    assert all(float(v).is_integer() for v in delta.values())


# ------------------------------------------------------------ re-audit
def test_instrumentation_added_no_host_callbacks():
    """All audited jaxpr entries stay callback-free: the observability
    layer is host-side only (acceptance criterion)."""
    from lightgbm_tpu.analysis.jaxpr_audit import run_audits

    results = run_audits()
    checked = 0
    for r in results:
        for c in r.contracts:
            if c.name == "no_host_callbacks":
                checked += 1
                assert c.ok, f"{r.name}: {c.detail}"
    assert checked >= 4  # every hot entry still audited


# -------------------------------------------------------------- analysis
def test_obs_modules_in_analysis_scan():
    """The strict gate's AST passes (lint + concurrency) cover the new
    obs/ modules — same file set for both (iter_package_modules)."""
    from lightgbm_tpu.analysis.lint import iter_package_modules

    files, root = iter_package_modules()
    rel = {p.relative_to(root).as_posix() for p in files}
    for mod in ("obs/__init__.py", "obs/metrics.py", "obs/tracing.py",
                "obs/manifest.py", "obs/recorder.py", "obs/anomaly.py",
                "obs/aggregate.py"):
        assert mod in rel, f"{mod} escaped the analysis scan"


# ------------------------------------------------------------------- log
def test_log_debug_routes_to_debug_method():
    calls = []

    class L:
        def info(self, m):
            calls.append(("info", m))

        def warning(self, m):
            calls.append(("warning", m))

        def debug(self, m):
            calls.append(("debug", m))

    prev = (log._logger, log._info_method, log._warning_method,
            log._debug_method, log._VERBOSITY)
    try:
        log.register_logger(L())
        log.set_verbosity(2)
        log.debug("d")
        log.info("i")
        log.warning("w")
        assert [c[0] for c in calls] == ["debug", "info", "warning"]
    finally:
        (log._logger, log._info_method, log._warning_method,
         log._debug_method) = prev[:4]
        log.set_verbosity(prev[4])


def test_log_debug_falls_back_to_info_method():
    calls = []

    class L:
        def info(self, m):
            calls.append(("info", m))

        warning = info

    prev = (log._logger, log._info_method, log._warning_method,
            log._debug_method, log._VERBOSITY)
    try:
        log.register_logger(L())
        log.set_verbosity(2)
        log.debug("d")
        assert calls and calls[0][0] == "info"
        with pytest.raises(TypeError):
            log.register_logger(L(), debug_method_name="nope")
    finally:
        (log._logger, log._info_method, log._warning_method,
         log._debug_method) = prev[:4]
        log.set_verbosity(prev[4])


def test_log_fatal_only_verbosity_respected_for_registered_logger():
    calls = []

    class L:
        def info(self, m):
            calls.append(m)

        warning = info
        debug = info

    prev = (log._logger, log._info_method, log._warning_method,
            log._debug_method, log._VERBOSITY)
    try:
        log.register_logger(L())
        log.set_verbosity(-1)  # fatal-only
        log.debug("d")
        log.info("i")
        log.warning("w")
        assert calls == []
        with pytest.raises(log.LightGBMError):
            log.fatal("boom")
    finally:
        (log._logger, log._info_method, log._warning_method,
         log._debug_method) = prev[:4]
        log.set_verbosity(prev[4])


# ------------------------------------------------------------ bench_serve
def _load_bench_serve(name: str):
    spec = importlib.util.spec_from_file_location(
        name, REPO / "bench_serve.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_serve_refuses_cpu_backend(tmp_path, monkeypatch):
    """The serving bench measures an accelerator or nothing: on the
    CPU backend main() exits non-zero naming what it found and writes
    no artifact."""
    monkeypatch.setenv("BENCH_SERVE_DIR", str(tmp_path))
    mod = _load_bench_serve("bench_serve_cpu")
    with pytest.raises(SystemExit) as exc:
        mod.main()
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)
    assert not list(tmp_path.glob("BENCH_SERVE_r*.json"))


def test_bench_serve_writes_artifact(tmp_path, monkeypatch):
    """The bench's phases and artifact, driven on the CPU mesh through
    a TEST seam (the device check is substituted; the script itself has
    no CPU mode). The artifact names its device and carries no number
    from an earlier run; a run manifest is stamped in."""
    import bench

    monkeypatch.setattr(
        bench, "require_accelerator",
        lambda who: {"platform": "test", "device_kind": "seam",
                     "device_count": 8})
    monkeypatch.setenv("BENCH_SERVE_DIR", str(tmp_path))
    monkeypatch.setenv("BENCH_MANIFEST_OUT", str(tmp_path / "m.json"))
    monkeypatch.setenv("BENCH_SERVE_TRAIN_ROWS", "400")
    monkeypatch.setenv("BENCH_SERVE_FEATURES", "4")
    monkeypatch.setenv("BENCH_SERVE_TREES", "5")
    monkeypatch.setenv("BENCH_SERVE_LEAVES", "7")
    monkeypatch.setenv("BENCH_SERVE_REQUESTS", "8")
    monkeypatch.setenv("BENCH_SERVE_BATCH", "16")
    monkeypatch.setenv("BENCH_SERVE_THREADS", "2")
    monkeypatch.setenv("BENCH_SERVE_GATEWAY_BACKENDS", "")
    mod = _load_bench_serve("bench_serve")
    assert mod.main() == 0
    files = list(tmp_path.glob("BENCH_SERVE_r*.json"))
    assert len(files) == 1
    data = json.loads(files[0].read_text())
    for key in ("qps", "p50_ms", "p99_ms"):
        assert key in data and data[key] >= 0
    assert data["requests"] == 8
    assert data["stats"].get("count", 0) >= 1
    assert (data["platform"], data["device_kind"],
            data["device_count"]) == ("test", "seam", 8)
    assert "last_tpu_verified" not in data
    assert data["gateway"] is None
    manifest = json.loads(Path(data["run_manifest"]).read_text())
    assert manifest["extra"]["run_id"] == data["run_id"]
    assert manifest["extra"]["artifact"] == str(files[0])


def test_bench_serve_gateway_phase_refuses_on_accelerator(monkeypatch):
    """One process per chip: a parent that holds an accelerator must not
    spawn task=serve children that each need it — the phase answers
    with the reason instead of a number."""
    import jax

    mod = _load_bench_serve("bench_serve_gw")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    import subprocess

    monkeypatch.setattr(
        subprocess, "Popen",
        lambda *a, **k: pytest.fail("spawned a chip-needing child"))
    out = mod._gateway_phase("unused.txt", "", 4, 1)
    assert set(out) == {"refused"} and "one process per chip" in \
        out["refused"]


def test_bench_train_manifest_stamp(tmp_path, monkeypatch):
    """bench.py's provenance hook: run manifest written, path + run id
    folded into the state the final JSON reports."""
    spec = importlib.util.spec_from_file_location(
        "bench_prov", REPO / "bench.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setenv("BENCH_MANIFEST_OUT",
                       str(tmp_path / "manifest.json"))
    mod._STATE.update(run_id="test-run", rows=1000, leaves=7,
                      trees_per_sec=1.0, platform="test",
                      device_kind="seam", device_count=1)
    mod.write_run_manifest({"objective": "binary", "num_leaves": 7})
    assert mod._STATE["run_manifest"] == str(tmp_path / "manifest.json")
    m = json.loads((tmp_path / "manifest.json").read_text())
    assert m["extra"]["run_id"] == "test-run"
    assert m["config"]["explicit"]["objective"] == "binary"
    out = mod._final_json()
    assert out["run_id"] == "test-run"
    assert out["run_manifest"] == str(tmp_path / "manifest.json")


# --------------------------------------------------------------- profile
def test_cli_profile_dir_and_manifest(tmp_path, rng):
    """profile_dir + run_manifest through the CLI: span trace +
    manifest land in the directory (jax.profiler capture is
    best-effort on CPU)."""
    from lightgbm_tpu.cli import main as cli_main

    X = rng.randn(300, 4)
    y = (X[:, 0] > 0).astype(int)
    data = tmp_path / "train.csv"
    np.savetxt(data, np.column_stack([y, X]), delimiter=",", fmt="%.6g")
    prof = tmp_path / "prof"
    model = tmp_path / "model.txt"
    manifest = tmp_path / "manifest.json"
    rc = cli_main([
        "task=train", f"data={data}", "objective=binary",
        "num_leaves=7", "num_trees=3", "verbosity=-1",
        f"output_model={model}", f"profile_dir={prof}",
        f"run_manifest={manifest}",
    ])
    assert rc == 0
    trace = json.loads((prof / "trace_events.json").read_text())
    names = {e.get("name") for e in trace["traceEvents"]}
    assert boosting.FUSED_ROUND_PHASE in names
    m = json.loads(manifest.read_text())
    assert m["extra"]["task"] == "train"
    assert (prof / "run_manifest.json").exists()
