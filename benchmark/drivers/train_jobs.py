"""Closed-loop training jobs, one client: ``lgb.train`` of R rounds with
a live valid-set eval, back to back on a Dataset constructed once in
set-up.

Why jobs and not one long ``lgb.train``: the engine replays callbacks
only every 64 rounds, which at the published Higgs size is longer than
any window, so a rate read from callbacks would be a burst artefact. A
job's return is a true synchronisation (its last eval row and its trees
have been read back), so trees over the time from the window's start to
the last job's return is a rate of completed work. The timed trees are
always trees 1..R of a fresh ensemble.

The traffic file gives ``rounds_per_job``, ``warmup_jobs``, ``min_jobs``
and ``trace_jobs``; the configuration gives the data generator and its
sizes, the training parameters, what the program is expected to resolve
them to, and the reference that decides ``correct``."""

from __future__ import annotations

import time
from typing import Any, Dict, List

from benchmark.harness import cellrun, compiles, stats
from benchmark.harness.manifest import load_plugin
from benchmark.harness.spans import counter_totals

WIRE_COUNTER = "lgbmtpu_collective_wire_bytes_total"


def _off_path(bst, expect: Dict[str, Any], rounds: int) -> List[str]:
    """Why this job's model did not come from the program the
    configuration expects (empty if it did)."""
    g = bst._gbdt
    why = []
    if bst.num_trees() != rounds:
        why.append(f"{bst.num_trees()} trees delivered of {rounds} asked")
    if g._force_sync:
        why.append(f"left the fused loop: {g._force_sync_reason}")
    if expect["grower"] == "rounds" and not g.spec.rounds_slots > 0:
        why.append("not the rounds grower")
    if g.hist_dtype != expect["hist_dtype"]:
        why.append(f"hist_dtype {g.hist_dtype!r}, expected "
                   f"{expect['hist_dtype']!r}")
    held = {s.device for s in g.dev["bins"].addressable_shards}
    if len(held) != expect["devices"]:
        why.append(f"bin matrix on {len(held)} device(s), expected "
                   f"{expect['devices']}")
    return why


def run(cell, args: cellrun.RunArgs, rec) -> cellrun.Outcome:
    import lightgbm_tpu as lgb

    cfg, mix = cell.config, cell.traffic
    data, params, expect = cfg["dataset"], cfg["params"], cfg["expect"]
    rounds = int(mix["rounds_per_job"])
    compiles.install()  # count compiles from here on

    with rec.span("data"):
        gen = load_plugin(cell.root, "datasets", data["generator"])
        X, y, Xv, yv = gen.make(args.seed, data["rows"],
                                data["valid_rows"], data["features"])
    with rec.span("dataset.construct"):
        ds = lgb.Dataset(X, label=y, params=dict(params),
                         free_raw_data=False)
        ds.construct()
        vs = lgb.Dataset(Xv, label=yv, reference=ds, free_raw_data=False)
        vs.construct()

    def job(span: str):
        evals: Dict[str, Any] = {}
        with rec.span(span):
            t0 = time.perf_counter()
            bst = lgb.train(
                dict(params), ds, num_boost_round=rounds,
                valid_sets=[vs], valid_names=["valid"],
                callbacks=[lgb.record_evaluation(evals)])
            return bst, evals, time.perf_counter() - t0

    with rec.span("first_train"):
        for _ in range(int(mix["warmup_jobs"])):
            job("warmup_job")

    compiles0 = compiles.counts()
    wire0 = counter_totals((WIRE_COUNTER,))[WIRE_COUNTER]
    limit = int(mix["trace_jobs"]) if args.trace else None
    if args.trace:
        cellrun.start_trace(cell)
    times: List[float] = []
    problems: List[str] = []
    failed = dispatches = 0
    t_window = time.perf_counter()
    with rec.span("window"):
        while True:
            n = len(times)
            if limit is not None and n >= limit:
                break
            # start no job that the running estimate says would end past
            # the window; at least min_jobs always run
            if n >= int(mix["min_jobs"]) and (
                    time.perf_counter() - t_window + stats.median(times)
                    > args.seconds):
                break
            bst, evals, dt = job("job")
            t_end = time.perf_counter()
            times.append(dt)
            why = _off_path(bst, expect, rounds)
            if why:
                failed += rounds
                problems.append(f"job {n + 1}: " + "; ".join(why))
            dispatches += bst._gbdt.fused_dispatch_count
    if args.trace:
        cellrun.stop_trace()
    trees = rounds * len(times)
    in_window = compiles.delta(compiles0)
    if in_window["compiles"]:
        problems.append(f"{in_window['compiles']} compiles inside the "
                        "window")
    rec.observe(
        trees=trees, jobs=len(times), fused_dispatches=dispatches,
        compiles_in_window=in_window["compiles"],
        cache_loads_in_window=in_window["cache_loads"],
        wire_bytes=counter_totals((WIRE_COUNTER,))[WIRE_COUNTER] - wire0,
        rows=int(data["rows"]), features=int(data["features"]),
        bins=int(params["max_bin"]),
        chips=int(expect["devices"]), hist_dtype=expect["hist_dtype"],
    )

    # ---- correct: outside the window, against the plain reference
    t0 = time.perf_counter()
    reference = load_plugin(cell.root, "references", cfg["reference"])
    verdict = reference.audit(
        bst.model_to_string(), X, y, Xv, yv, ds._binned.bins,
        evals["valid"][params["metric"]], params, cfg["quality"],
        args.seed)
    cellrun.log(f"{cfg['reference']} took "
                f"{time.perf_counter() - t0:.1f}s")
    return cellrun.Outcome(
        attempted=trees, failed=failed,
        problems=problems + verdict["problems"],
        end_to_end={"train_trees_per_s":
                    (trees - failed) / (t_end - t_window)},
        t_window=t_window,
        facts=dict(verdict["facts"], jobs=len(times), job_seconds=times),
    )
