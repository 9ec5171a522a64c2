"""Training entry points: train() and cv() (reference engine.py:109,627)."""

from __future__ import annotations

import collections
import copy
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import numpy as np

from . import callback as callback_mod
from . import log
from .basic import Booster, Dataset
from .callback import CallbackEnv, EarlyStopException
from .config import Config, resolve_alias
from .obs.anomaly import AnomalyAbort
from .resilience import checkpoint as ckpt_mod
from .resilience import faultinject
from .resilience.faultinject import fault_point
from .timer import global_timer as _gt


def _resolve_num_boost_round(params: Dict[str, Any], num_boost_round: int) -> Tuple[Dict, int]:
    params = copy.deepcopy(params)
    for k in list(params.keys()):
        if resolve_alias(k) == "num_iterations":
            num_boost_round = int(params.pop(k))
    return params, num_boost_round


class _ObsHooks:
    """Flight recorder + anomaly sentinel wiring for train()'s two
    loops (docs/OBSERVABILITY.md "Flight recorder & anomaly policies").

    One record per boosting round: evals (with higher-better flags for
    the loss-spike sentinel), per-phase durations drained from the
    timer span sink, per-class tree stats when the round's host trees
    are materialized (fused: every chunk; eager sync: every round; the
    async fast path defers trees, so those records omit stats), gh
    norms, and chunk throughput. Every record is written+flushed before
    the sentinel sees it, so an ``anomaly_policy=abort`` trip can never
    lose the round that tripped it."""

    def __init__(self, recorder, sentinel):
        self.recorder = recorder
        self.sentinel = sentinel
        # records carry ABSOLUTE round indices so a resumed run's
        # truncate+append stream stays monotonic (engine sets this to
        # the checkpoint round on resume)
        self.round_offset = 0
        self._gbdt = None
        self._chunk_tps: Optional[float] = None
        self._step_durs: List[float] = []
        self._chunk_phases: Dict[str, float] = {}
        self._gh_rows: List[Tuple[float, float]] = []

    def bind(self, gbdt) -> None:
        self._gbdt = gbdt
        gbdt.recorder = self.recorder  # eager loops publish gh norms
        self.recorder.attach()

    # ------------------------------------------------------------------
    def _tree_stats(self, i: int):
        """Stats for iteration i's K class-trees, when materialized."""
        gbdt = self._gbdt
        if gbdt._pending:
            return None  # async fast path: host trees not yet fetched
        K = gbdt.num_class
        base = (gbdt._init_iters + i) * K
        models = gbdt._models
        if len(models) < base + K:
            return None
        from .obs.recorder import tree_stats

        return tree_stats(models[base: base + K])

    def _fill_evals(self, rec: Dict[str, Any], evals) -> None:
        # tuples are (dataset, metric, value, higher_better[, stdv]);
        # index access keeps custom-feval 5-tuples working too
        if not evals:
            return
        rec["evals"] = {
            f"{it[0]} {it[1]}": float(it[2]) for it in evals
        }
        rec["evals_hb"] = {
            f"{it[0]} {it[1]}": bool(it[3])
            for it in evals if len(it) > 3
        }

    def _emit(self, rec: Dict[str, Any]) -> None:
        self.recorder.record(rec)
        if self.sentinel is not None:
            self.sentinel.check(rec)  # abort policy raises AnomalyAbort

    # ------------------------------------------------------------------
    def start_chunk(self, n_records: int, chunk_seconds: float) -> None:
        """Fused chunk boundary: drain the span sink once and slice the
        ``round: fused step`` spans out; chunk-level scopes
        (dispatch/collect/materialize) ride the chunk's first record.

        One span covers one DISPATCH — a whole C-round lax.scan — so
        the booster's ``_last_dispatch_rounds`` apportions each span
        evenly across its rounds: records keep a per-round duration."""
        from .boosting import FUSED_ROUND_PHASE

        drained = self.recorder.drain_phases()
        spans = drained.pop(FUSED_ROUND_PHASE, [])
        durs: List[float] = []
        for dur, n_rounds in zip(spans, self._gbdt._last_dispatch_rounds):
            durs.extend([dur / max(n_rounds, 1)] * n_rounds)
        self._step_durs = durs
        self._chunk_phases = {
            k: round(sum(v), 6) for k, v in drained.items()
        }
        K = self._gbdt.num_class
        self._chunk_tps = (
            n_records * K / chunk_seconds
            if n_records and chunk_seconds > 0 else None
        )
        self._gh_rows = list(self._gbdt._last_gh_rows)

    def _provenance(self) -> Dict[str, Any]:
        """Per-round training-path provenance: resolved histogram
        numerics plus the resolved tree learner, and the voting
        election footprint when the elected-columns-only wire is
        active (ISSUE 14 — lets recorder output distinguish the
        voting-on-rounds path from a full-histogram run)."""
        g = self._gbdt
        out: Dict[str, Any] = {
            # resolved histogram channel layout — numerics provenance
            # per round (the int-packed path changes per-tree math)
            "hist_dtype": getattr(g, "hist_dtype", None),
            "tree_learner": getattr(g, "tree_learner_resolved", None),
        }
        ec = getattr(g, "voting_elected_cols", None)
        if ec is not None:
            out["voting_elected_cols"] = ec
            out["voting_wire_bytes_est"] = getattr(
                g, "voting_wire_bytes_est", None
            )
        return out

    def fused_round(self, i: int, j: int, evals) -> None:
        from .boosting import FUSED_ROUND_PHASE

        rec: Dict[str, Any] = {
            "round": self.round_offset + i, "t_unix": time.time(),
            **self._provenance(),
        }
        if j < len(self._step_durs):
            rec["phases"] = {
                FUSED_ROUND_PHASE: round(self._step_durs[j], 6)
            }
        if j == 0 and self._chunk_phases:
            rec["chunk_phases"] = self._chunk_phases
        if self._chunk_tps is not None:
            rec["trees_per_sec"] = round(self._chunk_tps, 4)
        if j < len(self._gh_rows):
            rec["gnorm"], rec["hnorm"] = (
                round(self._gh_rows[j][0], 6),
                round(self._gh_rows[j][1], 6),
            )
        self._fill_evals(rec, evals)
        ts = self._tree_stats(i)
        if ts is not None:
            rec["trees"] = ts
        self._emit(rec)

    def eager_round(self, i: int, evals, iter_seconds: float) -> None:
        rec: Dict[str, Any] = {
            "round": self.round_offset + i, "t_unix": time.time(),
            **self._provenance(),
        }
        drained = self.recorder.drain_phases()
        if drained:
            rec["phases"] = {
                k: round(sum(v), 6) for k, v in drained.items()
            }
        if iter_seconds > 0:
            rec["trees_per_sec"] = round(
                self._gbdt.num_class / iter_seconds, 4
            )
        gh = self._gbdt._last_gh_norm
        if gh is not None:
            rec["gnorm"], rec["hnorm"] = round(gh[0], 6), round(gh[1], 6)
        self._fill_evals(rec, evals)
        ts = self._tree_stats(i)
        if ts is not None:
            rec["trees"] = ts
        self._emit(rec)

    def close(self) -> None:
        """Exception-safe teardown (train()'s finally): detaches the
        timer sink and flushes/closes the JSONL stream so an abort
        leaves no torn state behind. Also unhooks the booster — a kept
        training booster must not keep paying the gh-norm readbacks
        into a closed recorder."""
        if self._gbdt is not None:
            self._gbdt.recorder = None
        self.recorder.close()


def _make_obs_hooks(cfg, resume_bytes: Optional[int] = None
                    ) -> Optional[_ObsHooks]:
    """record_file / anomaly_policy config -> hooks (None = both off,
    the default: zero per-round overhead). ``resume_bytes`` is the
    checkpoint's captured record-stream offset: the recorder truncates
    the stream back to it and appends, so a resumed run's flight
    record carries each round exactly once."""
    path = cfg.record_file
    policy = cfg.anomaly_policy
    if not path and policy == "off":
        return None
    from .obs.anomaly import make_sentinel
    from .obs.recorder import FlightRecorder

    recorder = FlightRecorder(path or None, resume_bytes=resume_bytes)
    sentinel = make_sentinel(policy, recorder=recorder)
    return _ObsHooks(recorder, sentinel)


def train(
    params: Dict[str, Any],
    train_set: Dataset,
    num_boost_round: int = 100,
    valid_sets: Optional[List[Dataset]] = None,
    valid_names: Optional[List[str]] = None,
    feval: Optional[Callable] = None,
    init_model: Optional[Union[str, Booster]] = None,
    keep_training_booster: bool = False,
    callbacks: Optional[List[Callable]] = None,
    fobj: Optional[Callable] = None,
) -> Booster:
    """Train a model (reference engine.py:109 lgb.train).

    The whole call is the `engine.train` span; the spans of its layer
    boundaries (docs/OBSERVABILITY.md, "Spans of one lgb.train call")
    open on this thread, so in a profiler trace they nest inside it."""
    with _gt.scope("engine.train"):
        return _train(
            params, train_set, num_boost_round, valid_sets, valid_names,
            feval, init_model, keep_training_booster, callbacks, fobj,
        )


def _train(
    params: Dict[str, Any],
    train_set: Dataset,
    num_boost_round: int,
    valid_sets: Optional[List[Dataset]],
    valid_names: Optional[List[str]],
    feval: Optional[Callable],
    init_model: Optional[Union[str, Booster]],
    keep_training_booster: bool,
    callbacks: Optional[List[Callable]],
    fobj: Optional[Callable],
) -> Booster:
    params, num_boost_round = _resolve_num_boost_round(params, num_boost_round)
    cfg_probe = Config(params)
    if cfg_probe.timetag:
        # runtime USE_TIMETAG switch (docs/OBSERVABILITY.md): phase
        # timing on without restarting the process
        from .timer import enable_timetag

        enable_timetag()
    if cfg_probe.objective == "none" and fobj is None:
        log.warning("Using custom objective requires fobj; objective=none trains nothing")
    # deterministic fault plans (fault_plan param / LGBMTPU_FAULT_PLAN
    # env, docs/RESILIENCE.md); disarmed = a single None check per round
    faultinject.configure(cfg_probe.fault_plan)
    # the raw caller-supplied callbacks, before ES/logging are appended:
    # an anomaly_policy=rollback retry must re-run train() with these
    # (the appended callbacks hold consumed state and would double up)
    user_callbacks = list(callbacks) if callbacks else []
    # early stopping via params (engine.py behavior)
    callbacks = list(callbacks) if callbacks else []
    if cfg_probe.early_stopping_round and cfg_probe.early_stopping_round > 0:
        callbacks.append(
            callback_mod.early_stopping(
                cfg_probe.early_stopping_round,
                first_metric_only=cfg_probe.first_metric_only,
                min_delta=cfg_probe.early_stopping_min_delta,
            )
        )
    if cfg_probe.verbosity >= 1 and not any(
        getattr(cb, "order", None) == 10 and not getattr(cb, "before_iteration", False)
        for cb in callbacks
    ):
        callbacks.append(callback_mod.log_evaluation(period=cfg_probe.metric_freq))

    # ---- crash-consistent resume (docs/RESILIENCE.md). A checkpoint
    # is adopted exactly like a user init_model: the model text rides
    # _continue_from, and because every sampling key is derived from
    # the ABSOLUTE iteration (boosting.py fold_in(seed, iteration)), a
    # resumed run replays the identical tree sequence — the final model
    # bit-matches an uninterrupted run (tests/test_resilience.py).
    ckpt_path = cfg_probe.checkpoint_file or ckpt_mod.default_path(
        cfg_probe.output_model
    )
    resume_offset = 0
    resume_rows: List[List[Tuple]] = []
    record_resume_bytes: Optional[int] = None
    if init_model is None and (cfg_probe.resume == "auto"
                               or cfg_probe.resume_from):
        found, state = ckpt_mod.find_resume_checkpoint(
            cfg_probe.resume, cfg_probe.resume_from, ckpt_path
        )
        if state is not None:
            fp = ckpt_mod.config_fingerprint(params)
            if state.get("fingerprint") and state["fingerprint"] != fp:
                log.warning(
                    f"Checkpoint {found} was written under a different "
                    f"training config (fingerprint {state['fingerprint']}"
                    f" != {fp}); resuming anyway — the combined model "
                    "will not bit-match a single uninterrupted run"
                )
            init_model = Booster(model_str=state["model"])
            resume_offset = state["engine_round"]
            resume_rows = ckpt_mod.truncate_eval_history(
                state.get("eval_history", ()), resume_offset
            )
            record_resume_bytes = state.get("record_offset")
            log.info(
                f"Resuming training from checkpoint {found} "
                f"(round {resume_offset})"
            )

    if cfg_probe.data_source == "chunked":
        # out-of-core plane active (docs/DATA_PLANE.md): surface the
        # resolved budget once at train level; per-chunk RSS lands in
        # the run manifest as manifest["data_plane"]
        from .data import DEFAULT_RAM_BUDGET_MB

        log.info(
            "data_source=chunked: host memory bounded by "
            f"ram_budget_mb={cfg_probe.ram_budget_mb or DEFAULT_RAM_BUDGET_MB}"
            " MB (per-chunk RSS recorded in the run manifest)"
        )

    valid_sets = valid_sets or []
    valid_names = valid_names or []
    valid_contain_train = False
    with _gt.scope("engine.booster_init"):
        booster = Booster(params=params, train_set=train_set)
        for i, vs in enumerate(valid_sets):
            name = valid_names[i] if i < len(valid_names) else f"valid_{i}"
            if vs is train_set:
                valid_contain_train = True
                booster._train_data_name = name
                continue
            booster.add_valid(vs, name)

        if init_model is not None:
            ib = (
                init_model
                if isinstance(init_model, Booster)
                else Booster(model_file=init_model)
            )
            booster._continue_from(ib)

    cb_before = [cb for cb in callbacks if getattr(cb, "before_iteration", False)]
    cb_after = [cb for cb in callbacks if not getattr(cb, "before_iteration", False)]
    cb_before.sort(key=lambda cb: getattr(cb, "order", 0))
    cb_after.sort(key=lambda cb: getattr(cb, "order", 0))

    # rounds are ABSOLUTE across resume: a checkpoint at round R leaves
    # `num_boost_round - R` rounds to run, and every callback / fault
    # site / snapshot sees `resume_offset + i` so the resumed half is
    # indistinguishable from the tail of an uninterrupted run
    total_rounds = num_boost_round
    num_boost_round = max(total_rounds - resume_offset, 0)

    snapshot_freq = cfg_probe.snapshot_freq
    ckpt_fingerprint = (
        ckpt_mod.config_fingerprint(params) if snapshot_freq > 0 else ""
    )
    # eval history through the current round rides in the checkpoint so
    # a resume can replay it into the stateful callbacks (early
    # stopping, record_evaluation) before new rounds run
    eval_history: List[List[Tuple]] = [list(r) for r in resume_rows]

    def _snapshot(done_iter: int, evals) -> None:
        """snapshot_freq model dumps during training (gbdt.cpp:258-262)
        plus the crash-consistent training checkpoint (resume=auto)."""
        if snapshot_freq <= 0:
            return
        abs_round = resume_offset + done_iter + 1
        # truncate-and-set keeps the history exactly `abs_round` rows
        eval_history[abs_round - 1:] = [[tuple(t) for t in (evals or [])]]
        if abs_round % snapshot_freq != 0:
            return
        out = f"{cfg_probe.output_model}.snapshot_iter_{abs_round}"
        # clamp explicitly (the fused path materializes whole chunks
        # before callbacks replay); done_iter counts NEW iterations —
        # offset by any init_model trees so snapshots keep them
        total = booster._gbdt._init_iters + done_iter + 1
        booster.save_model(out, num_iteration=total)
        log.info(f"Saved snapshot to {out}")
        record_offset = None
        if obs_hooks is not None and obs_hooks.recorder.path:
            # the round's record is written+flushed before _snapshot
            # runs, so the captured size covers rounds <= abs_round —
            # a resume truncates the stream back to exactly here
            try:
                record_offset = os.path.getsize(obs_hooks.recorder.path)
            except OSError:
                record_offset = None
        ckpt_mod.save_checkpoint(
            ckpt_path,
            booster.model_to_string(num_iteration=total),
            engine_round=abs_round,
            total_iters=total,
            eval_history=eval_history,
            record_offset=record_offset,
            fingerprint=ckpt_fingerprint,
        )

    # flight recorder + anomaly sentinels (record_file / anomaly_policy
    # params, docs/OBSERVABILITY.md); None when both are off
    obs_hooks = _make_obs_hooks(cfg_probe, record_resume_bytes)
    if obs_hooks is not None:
        obs_hooks.round_offset = resume_offset
        obs_hooks.bind(booster._gbdt)
    else:
        # an unrecorded run supersedes any earlier recorded run: a
        # manifest written after THIS run must not carry the previous
        # run's flight-record summary
        from .obs.recorder import clear_last_summary

        clear_last_summary()

    evaluation_result_list: List[Tuple] = (
        list(resume_rows[-1]) if resume_rows else []
    )
    i = -1
    if resume_offset > 0 and resume_rows:
        # replay the checkpointed learning curve into the STATEFUL
        # post-iteration callbacks (order >= 20: record_evaluation,
        # early_stopping) so their internal state matches an
        # uninterrupted run; log_evaluation (order 10) is skipped —
        # those rounds were already printed by the crashed run
        replay_cbs = [
            cb for cb in cb_after if getattr(cb, "order", 0) >= 20
        ]
        try:
            for r, row in enumerate(resume_rows):
                for cb in replay_cbs:
                    cb(CallbackEnv(booster, params, r, 0, total_rounds,
                                   list(row)))
        except EarlyStopException as e:
            # the crashed run would have stopped inside the
            # checkpointed prefix — nothing left to train
            booster.best_iteration = e.best_iteration + 1
            evaluation_result_list = e.best_score
            num_boost_round = 0
    use_fused = (
        fobj is None
        and feval is None
        and not cb_before
        and hasattr(booster._gbdt, "fused_eligible")
        and booster._gbdt.fused_eligible()
    )
    if not use_fused:
        # the sync path drains the device queue for a host readback
        # every iteration — tell the user WHY they fell off the fused
        # loop instead of silently training slower
        if fobj is not None:
            why = "custom fobj"
        elif feval is not None:
            why = "custom feval"
        elif cb_before:
            why = "pre-iteration callbacks"
        elif hasattr(booster._gbdt, "fused_ineligible_reason"):
            why = booster._gbdt.fused_ineligible_reason() or "unknown"
        else:
            why = "unsupported booster"
        log.info(
            f"Using the per-iteration sync training loop ({why}); "
            "the fused device loop is faster on accelerators"
        )
    try:
        if use_fused:
            # fused device loop: rounds dispatched as C-round lax.scan
            # chunks (one executable launch per ladder rung;
            # boosting.fused_dispatch), zero host syncs; evals fetched
            # per chunk and callbacks replayed in order (identical
            # per-iteration semantics, delivered late)
            gbdt = booster._gbdt
            gbdt.train.name = booster._train_data_name
            gbdt.fused_start(track_train=valid_contain_train)
            chunk = gbdt._check_every
            done = 0
            stop = False
            from .obs.metrics import record_eval_values, record_training_round

            while done < num_boost_round and not stop:
                n = min(chunk, num_boost_round - done)
                t_chunk = time.perf_counter()
                with _gt.scope("fused dispatch"):
                    gbdt.fused_dispatch(n)
                with _gt.scope("fused collect (readback)"):
                    records = gbdt.fused_collect()
                record_training_round(
                    len(records), len(records) * gbdt.num_class,
                    time.perf_counter() - t_chunk,
                )
                if obs_hooks is not None:
                    obs_hooks.start_chunk(
                        len(records), time.perf_counter() - t_chunk
                    )
                # one span per CHUNK: the rounds' records replayed
                with _gt.scope("engine.callbacks"):
                    for j, evals in enumerate(records):
                        i = done + j
                        fault_point("round", resume_offset + i)
                        evaluation_result_list = evals
                        record_eval_values(evals)
                        if obs_hooks is not None:
                            obs_hooks.fused_round(i, j, evals)
                        _snapshot(i, evals)
                        try:
                            for cb in cb_after:
                                cb(CallbackEnv(booster, params,
                                               resume_offset + i, 0,
                                               total_rounds, evals))
                        except EarlyStopException as e:
                            booster.best_iteration = e.best_iteration + 1
                            evaluation_result_list = e.best_score
                            # truncate counts TOTAL iterations: keep
                            # loaded trees
                            gbdt.fused_truncate(gbdt._init_iters + i + 1)
                            stop = True
                            break
                done += max(len(records), 1)
                if gbdt._stopped:
                    # the sync path runs cb_after once for the stop iteration
                    # (whose eval equals the previous iteration's: the failed
                    # trees were rolled back) — replay that here too
                    if not stop and done < num_boost_round:
                        try:
                            for cb in cb_after:
                                cb(CallbackEnv(booster, params,
                                               resume_offset + done, 0,
                                               total_rounds,
                                               evaluation_result_list))
                        except EarlyStopException as e:
                            booster.best_iteration = e.best_iteration + 1
                            evaluation_result_list = e.best_score
                    break
        else:
            from .obs.metrics import record_eval_values, record_training_round

            for i in range(num_boost_round):
                fault_point("round", resume_offset + i)
                for cb in cb_before:
                    cb(CallbackEnv(booster, params, resume_offset + i, 0,
                                   total_rounds, None))
                t_iter = time.perf_counter()
                finished = booster.update(fobj=fobj)
                record_training_round(
                    1, booster._gbdt.num_class, time.perf_counter() - t_iter
                )

                evaluation_result_list = []
                if valid_contain_train:
                    evaluation_result_list.extend(booster.eval_train(feval))
                if booster._gbdt.valids:
                    evaluation_result_list.extend(booster.eval_valid(feval))
                record_eval_values(evaluation_result_list)
                if obs_hooks is not None:
                    obs_hooks.eager_round(
                        i, evaluation_result_list,
                        time.perf_counter() - t_iter,
                    )
                _snapshot(i, evaluation_result_list)
                try:
                    for cb in cb_after:
                        cb(CallbackEnv(booster, params, resume_offset + i,
                                       0, total_rounds,
                                       evaluation_result_list))
                except EarlyStopException as e:
                    booster.best_iteration = e.best_iteration + 1
                    evaluation_result_list = e.best_score
                    break
                if finished:
                    break

    except AnomalyAbort as anomaly:
        # anomaly_policy=rollback: restore the last good checkpoint and
        # retrain instead of discarding the run (docs/RESILIENCE.md
        # "Recovery policies"). The budget (anomaly_rollback_max)
        # decrements through the retry params so a deterministic
        # re-trip terminates; without a checkpoint it degrades to abort.
        if (cfg_probe.anomaly_policy == "rollback"
                and snapshot_freq > 0
                and cfg_probe.anomaly_rollback_max > 0
                and os.path.exists(ckpt_path)):
            if obs_hooks is not None:
                # flush/close now: the retry reopens the record stream
                # (truncate+append) and publishes its own summary
                obs_hooks.close()
                obs_hooks = None
            retry_params = copy.deepcopy(params)
            for k in list(retry_params):
                if resolve_alias(k) in (
                    "learning_rate", "resume", "resume_from",
                    "anomaly_rollback_max",
                ):
                    retry_params.pop(k)
            decay = cfg_probe.anomaly_rollback_lr_decay
            retry_params["learning_rate"] = cfg_probe.learning_rate * decay
            retry_params["resume_from"] = ckpt_path
            retry_params["anomaly_rollback_max"] = (
                cfg_probe.anomaly_rollback_max - 1
            )
            log.warning(
                f"anomaly rollback: {anomaly} — restoring checkpoint "
                f"{ckpt_path} and retraining with learning_rate="
                f"{retry_params['learning_rate']:g} "
                f"({cfg_probe.anomaly_rollback_max - 1} rollback(s) left)"
            )
            return train(
                retry_params, train_set, total_rounds,
                valid_sets=valid_sets, valid_names=valid_names,
                feval=feval, init_model=None,
                keep_training_booster=keep_training_booster,
                callbacks=user_callbacks, fobj=fobj,
            )
        raise
    finally:
        # exception-safe flush (anomaly abort, callback errors,
        # KeyboardInterrupt): detach the span sink and close the
        # JSONL stream so the flight record's tail stays parseable
        # and the run manifest can summarize it
        if obs_hooks is not None:
            obs_hooks.close()

    with _gt.scope("engine.finish"):
        # flush the async training pipeline (fast-path pending device
        # trees)
        booster._gbdt._materialize()
        # surface the run's sentinel verdict on the booster: the online
        # promotion gate (online/gate.py) reads trips from the refit
        # result directly instead of the module-global recorder summary
        if obs_hooks is not None and obs_hooks.sentinel is not None:
            booster.anomaly_summary = obs_hooks.sentinel.summary()
        # the stop condition is only detected every _check_every
        # iterations on the fast path; _materialize may have truncated
        # blindly-trained iterations — clamp iteration-derived state to
        # the surviving models
        n_iters = booster._gbdt.num_trees() // booster._gbdt.num_class
        if booster.best_iteration > n_iters:
            booster.best_iteration = n_iters
        if n_iters < booster._gbdt._init_iters + i + 1:
            # truncation rolled back the blindly-trained iterations whose
            # scores produced the last eval — don't record stale values
            evaluation_result_list = []

        # record best score
        for item in evaluation_result_list or []:
            booster.best_score.setdefault(item[0], collections.OrderedDict())
            booster.best_score[item[0]][item[1]] = item[2]
    return booster


class CVBooster:
    """Ensemble of per-fold boosters (reference engine.py:356)."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def append(self, booster: Booster) -> "CVBooster":
        self.boosters.append(booster)
        return self

    def __getattr__(self, name: str):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]

        return handler_function


def _make_n_folds(full_data: Dataset, nfold: int, params: Dict, seed: int,
                  stratified: bool, shuffle: bool):
    full_data.construct()
    num_data = full_data.num_data()
    rng = np.random.RandomState(seed)
    if stratified and full_data.label is not None:
        label = np.asarray(full_data.label)
        folds = [[] for _ in range(nfold)]
        for cls in np.unique(label):
            idx = np.nonzero(label == cls)[0]
            if shuffle:
                rng.shuffle(idx)
            for i, chunk in enumerate(np.array_split(idx, nfold)):
                folds[i].extend(chunk.tolist())
        fold_idx = [np.asarray(sorted(f)) for f in folds]
    else:
        idx = np.arange(num_data)
        if shuffle:
            rng.shuffle(idx)
        fold_idx = [np.sort(c) for c in np.array_split(idx, nfold)]
    for i in range(nfold):
        test_idx = fold_idx[i]
        train_idx = np.setdiff1d(np.arange(num_data), test_idx, assume_unique=False)
        yield train_idx, test_idx


def cv(
    params: Dict[str, Any],
    train_set: Dataset,
    num_boost_round: int = 100,
    folds=None,
    nfold: int = 5,
    stratified: bool = True,
    shuffle: bool = True,
    metrics=None,
    feval=None,
    init_model=None,
    fpreproc=None,
    seed: int = 0,
    callbacks=None,
    eval_train_metric: bool = False,
    return_cvbooster: bool = False,
    fobj: Optional[Callable] = None,
) -> Dict[str, Any]:
    """Cross-validation (reference engine.py:627)."""
    params, num_boost_round = _resolve_num_boost_round(params, num_boost_round)
    if metrics is not None:
        params["metric"] = metrics
    cfg_probe = Config(params)
    if cfg_probe.objective in ("lambdarank", "rank_xendcg") and stratified:
        stratified = False

    if folds is not None:
        if hasattr(folds, "split"):
            fold_iter = list(folds.split(np.zeros(train_set.num_data()), train_set.label))
        else:
            fold_iter = list(folds)
    else:
        fold_iter = list(_make_n_folds(train_set, nfold, params, seed, stratified, shuffle))

    cvbooster = CVBooster()
    for train_idx, test_idx in fold_iter:
        tr = train_set.subset(train_idx)
        te = train_set.subset(test_idx)
        if fpreproc is not None:
            tr, te, fold_params = fpreproc(tr, te, copy.deepcopy(params))
        else:
            fold_params = params
        bst = Booster(params=fold_params, train_set=tr)
        bst.add_valid(te, "valid")
        cvbooster.append(bst)

    callbacks = list(callbacks) if callbacks else []
    if cfg_probe.early_stopping_round and cfg_probe.early_stopping_round > 0:
        callbacks.append(
            callback_mod.early_stopping(
                cfg_probe.early_stopping_round,
                first_metric_only=cfg_probe.first_metric_only,
                min_delta=cfg_probe.early_stopping_min_delta,
            )
        )
    cb_before = sorted(
        (cb for cb in callbacks if getattr(cb, "before_iteration", False)),
        key=lambda cb: getattr(cb, "order", 0),
    )
    cb_after = sorted(
        (cb for cb in callbacks if not getattr(cb, "before_iteration", False)),
        key=lambda cb: getattr(cb, "order", 0),
    )

    # ---- fused cv: every fold's training rides the
    # chunked fused device loop, and because the traced step is
    # fold-agnostic (per-fold arrays are jit arguments, boosting.py
    # _FUSED_STEP_CACHE), fold 2..k reuse fold 1's trace+executable —
    # 5-fold cv pays ONE trace. Per-iteration aggregation/callbacks
    # replay from the per-chunk eval records exactly like engine.train.
    use_fused_cv = (
        fobj is None and feval is None and not cb_before
        and all(b._gbdt.fused_eligible() for b in cvbooster.boosters)
    )
    results = collections.defaultdict(list)

    def _cv_iteration(i: int, fold_evals) -> bool:
        """Aggregate one iteration's per-fold eval tuples into results,
        replay cb_after; returns True when early stopping fired (shared
        by the fused replay and the sync fold loop so semantics cannot
        drift)."""
        merged: Dict[Tuple[str, str, bool], List[float]] = (
            collections.OrderedDict()
        )
        for one in fold_evals:
            for dn, mn, v, hb in one:
                merged.setdefault((dn, mn, hb), []).append(v)
        agg = [
            ("cv_agg", f"{dn} {mn}", float(np.mean(vs)), hb,
             float(np.std(vs)))
            for (dn, mn, hb), vs in merged.items()
        ]
        for (dn, mn, hb), vs in merged.items():
            results[f"{dn} {mn}-mean"].append(float(np.mean(vs)))
            results[f"{dn} {mn}-stdv"].append(float(np.std(vs)))
        try:
            for cb in cb_after:
                cb(CallbackEnv(cvbooster, params, i, 0, num_boost_round,
                               agg))
        except EarlyStopException as e:
            cvbooster.best_iteration = e.best_iteration + 1
            for bst in cvbooster.boosters:
                bst.best_iteration = cvbooster.best_iteration
            for k in results:
                results[k] = results[k][: cvbooster.best_iteration]
            return True
        return False

    if use_fused_cv:
        for bst in cvbooster.boosters:
            bst._gbdt.fused_start(track_train=eval_train_metric)
        chunk = cvbooster.boosters[0]._gbdt._check_every
        done = 0
        stop = False
        while done < num_boost_round and not stop:
            n = min(chunk, num_boost_round - done)
            fold_records = []
            for bst in cvbooster.boosters:
                bst._gbdt.fused_dispatch(n)
            for bst in cvbooster.boosters:
                fold_records.append(bst._gbdt.fused_collect())
            n_done = min(len(r) for r in fold_records) if fold_records else 0
            for j in range(n_done):
                i = done + j
                if _cv_iteration(i, [recs[j] for recs in fold_records]):
                    # keep trees THROUGH the stop iteration (i+1),
                    # matching the sync fold loop and engine.train; only
                    # the chunk's blindly-trained tail drops
                    for bst in cvbooster.boosters:
                        bst._gbdt.fused_truncate(
                            bst._gbdt._init_iters + i + 1
                        )
                    stop = True
                    break
            n_recorded = done + n_done  # iterations with results rows
            done += max(n_done, 1)
            if not stop and any(
                b._gbdt._stopped for b in cvbooster.boosters
            ):
                # a fold hit the no-splittable-leaf stop mid-chunk: its
                # records (and results) end early — clamp EVERY fold's
                # trees to the recorded length so num_trees() always
                # agrees with the results lists
                for bst in cvbooster.boosters:
                    bst._gbdt.fused_truncate(
                        bst._gbdt._init_iters + n_recorded
                    )
                break
        for bst in cvbooster.boosters:
            bst._gbdt._materialize()
    else:
        for i in range(num_boost_round):
            for cb in cb_before:
                cb(CallbackEnv(cvbooster, params, i, 0, num_boost_round,
                               None))
            for bst in cvbooster.boosters:
                bst.update(fobj=fobj)
            fold_evals = []
            for bst in cvbooster.boosters:
                one = bst.eval_valid(feval)
                if eval_train_metric:
                    one = bst.eval_train(feval) + one
                fold_evals.append(one)
            if _cv_iteration(i, fold_evals):
                break
    out = dict(results)
    if return_cvbooster:
        out["cvbooster"] = cvbooster
    return out
