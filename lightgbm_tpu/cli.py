"""Config-file-driven command line application.

Reference surface: src/main.cpp:14 + src/application/application.cpp —
`lightgbm config=train.conf [k=v ...]` with tasks train / predict /
save_binary / convert_model / refit (config.h:35 TaskType). Parameter
layering matches Application::LoadParameters (application.cpp:53-89):
command-line pairs first, then `config=` file lines (k = v, `#`
comments), FIRST occurrence of a key wins (config.cpp KeepFirstValues).

Run as `python -m lightgbm_tpu config=train.conf` (or the bin/lightgbm
wrapper). The reference's example train.conf files run unmodified.
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from . import log


def parse_kv_args(argv: List[str]) -> Dict[str, str]:
    """argv 'k=v' pairs + config= file lines; first occurrence wins."""
    params: Dict[str, str] = {}

    def add(k: str, v: str) -> None:
        k = k.strip()
        v = v.strip().strip('"').strip("'")
        if k and k not in params:
            params[k] = v

    for arg in argv:
        if "=" in arg:
            k, v = arg.split("=", 1)
            add(k, v)
    cfg = params.get("config", "")
    if cfg:
        if not Path(cfg).exists():
            log.fatal(f"config file {cfg} does not exist")
        for line in Path(cfg).read_text().splitlines():
            if "#" in line:
                line = line[: line.index("#")]
            line = line.strip()
            if not line or "=" not in line:
                continue
            k, v = line.split("=", 1)
            add(k, v)
    params.pop("config", None)
    return params


_DATA_KEYS = (
    "header", "label_column", "weight_column", "group_column",
    "ignore_column", "categorical_feature",
)


def _mappers_equal(a, b) -> bool:
    if len(a) != len(b):
        return False
    for ma, mb in zip(a, b):
        if (
            ma.num_bin != mb.num_bin
            or ma.bin_type != mb.bin_type
            or ma.categories != mb.categories
            or not np.array_equal(ma.upper_bounds, mb.upper_bounds)
        ):
            return False
    return True


def _load_dataset(params: Dict[str, str], path: str, reference=None):
    """Text or .bin cache -> lgb.Dataset (constructed)."""
    from . import Dataset
    from .parsers import is_binary_file, load_binary, load_text_file

    if is_binary_file(path):
        log.info(f"Loading binary dataset cache {path}")
        binned = load_binary(path)
        if reference is not None:
            # a valid set must share the training set's bin mappers
            # (reference DatasetLoader::LoadFromFileAlignWithOtherDataset);
            # a cache binned independently would silently corrupt eval
            reference.construct()
            if not _mappers_equal(binned.mappers, reference._binned.mappers):
                log.fatal(
                    f"binary cache {path} was binned with different bin "
                    "mappers than the training data; rebuild it with "
                    "task=save_binary against this training set"
                )
        return Dataset.from_binned(binned)

    loaded = load_text_file(
        path,
        header=str(params.get("header", "false")).lower() in ("true", "1"),
        label_column=params.get("label_column", 0),
        weight_column=params.get("weight_column", ""),
        group_column=params.get("group_column", ""),
        ignore_column=params.get("ignore_column", ""),
        categorical_feature=params.get("categorical_feature", ""),
    )
    train_params = {
        k: v for k, v in params.items() if k not in _DATA_KEYS
    }
    ds = Dataset(
        loaded["X"],
        label=loaded["label"],
        weight=loaded["weight"],
        group=loaded["group"],
        init_score=loaded["init_score"],
        feature_name=loaded["feature_names"] or "auto",
        categorical_feature=loaded["categorical_feature"] or "auto",
        params=train_params,
        reference=reference,
        free_raw_data=False,
    )
    return ds


def _task_train(params: Dict[str, str]) -> None:
    from . import train as lgb_train
    from .config import Config

    data_path = params.get("data", "")
    if not data_path:
        log.fatal("No training/prediction data, application quit")
    t0 = time.time()
    ds = _load_dataset(params, data_path)
    ds.construct()
    log.info(
        f"Loaded {ds.num_data()} rows x {ds.num_feature()} features "
        f"from {data_path} in {time.time()-t0:.1f}s"
    )

    if str(params.get("is_save_binary_file", params.get("save_binary", "false"))).lower() in ("true", "1"):
        from .parsers import save_binary

        save_binary(ds._binned, data_path + ".bin")
        log.info(f"Saved binary cache to {data_path}.bin")

    valid_sets = []
    valid_names = []
    vpaths = [v for v in str(params.get("valid_data", params.get("valid", ""))).split(",") if v]
    for i, vp in enumerate(vpaths):
        vs = _load_dataset(params, vp, reference=ds)
        valid_sets.append(vs)
        valid_names.append(f"valid_{i + 1}")  # reference naming: valid_1, ...

    cfg = Config(dict(params))
    if str(params.get("is_training_metric", params.get("train_metric", "false"))).lower() in ("true", "1"):
        valid_sets = [ds] + valid_sets
        valid_names = ["training"] + valid_names

    num_rounds = cfg.num_iterations
    init_model = cfg.input_model  # resolves model_in/model_input aliases
    booster = lgb_train(
        dict(params), ds, num_boost_round=num_rounds,
        valid_sets=valid_sets, valid_names=valid_names,
        init_model=init_model or None,
    )
    out = params.get("output_model", "LightGBM_model.txt")
    booster.save_model(out)
    log.info(f"Finished training; model saved to {out}")


def _task_predict(params: Dict[str, str]) -> None:
    from . import Booster
    from .parsers import load_text_file

    data_path = params.get("data", "")
    model_path = params.get("input_model", "LightGBM_model.txt")
    if not data_path:
        log.fatal("No training/prediction data, application quit")
    if not Path(model_path).exists():
        log.fatal(f"input model {model_path} does not exist")
    bst = Booster(model_file=model_path)
    loaded = load_text_file(
        data_path,
        header=str(params.get("header", "false")).lower() in ("true", "1"),
        label_column=params.get("label_column", 0),
        weight_column=params.get("weight_column", ""),
        group_column=params.get("group_column", ""),
        ignore_column=params.get("ignore_column", ""),
    )
    raw = str(params.get("predict_raw_score", "false")).lower() in ("true", "1")
    leaf = str(params.get("predict_leaf_index", "false")).lower() in ("true", "1")
    contrib = str(params.get("predict_contrib", "false")).lower() in ("true", "1")
    es_kwargs = {}
    if str(params.get("pred_early_stop", "false")).lower() in ("true", "1"):
        es_kwargs = {
            "pred_early_stop": True,
            "pred_early_stop_freq": int(params.get("pred_early_stop_freq", 10)),
            "pred_early_stop_margin": float(
                params.get("pred_early_stop_margin", 10.0)
            ),
        }
    pred = bst.predict(
        loaded["X"], raw_score=raw, pred_leaf=leaf, pred_contrib=contrib,
        **es_kwargs,
    )
    out = params.get("output_result", "LightGBM_predict_result.txt")
    pred2 = np.atleast_2d(pred.T).T  # (N, K) even for 1-D
    np.savetxt(out, pred2, delimiter="\t", fmt="%.9g")
    log.info(f"Finished prediction; results saved to {out}")


def _task_save_binary(params: Dict[str, str]) -> None:
    from .parsers import save_binary

    data_path = params.get("data", "")
    if not data_path:
        log.fatal("No training/prediction data, application quit")
    ds = _load_dataset(params, data_path)
    ds.construct()
    out = params.get("output_model", data_path + ".bin")
    save_binary(ds._binned, out)
    log.info(f"Finished saving binary dataset cache to {out}")


def _task_convert_model(params: Dict[str, str]) -> None:
    """task=convert_model (application.cpp:223 ConvertModel): model ->
    if-else C++ source. convert_model_language=cpp is the only language
    the reference supports too (config.h)."""
    from . import Booster
    from .model_io import model_to_if_else

    lang = params.get("convert_model_language", "cpp")
    if lang not in ("", "cpp"):
        log.fatal(f"convert_model_language={lang} is not supported (cpp only)")
    model_path = params.get("input_model", "LightGBM_model.txt")
    if not Path(model_path).exists():
        log.fatal(f"input model {model_path} does not exist")
    bst = Booster(model_file=model_path)
    out = params.get("convert_model", "gbdt_prediction.cpp")
    Path(out).write_text(
        model_to_if_else(
            bst._gbdt.models, bst._gbdt.num_class,
            average_output=bool(getattr(bst._gbdt, "average_output", False)),
        )
    )
    log.info(f"Finished converting model to if-else code at {out}")


def _task_refit(params: Dict[str, str]) -> None:
    """task=refit (config.h:35 kRefitTree): recompute the existing
    model's leaf values from new data (Booster.refit)."""
    from . import Booster
    from .parsers import load_text_file

    data_path = params.get("data", "")
    model_path = params.get("input_model", "LightGBM_model.txt")
    if not data_path:
        log.fatal("No training/prediction data, application quit")
    if not Path(model_path).exists():
        log.fatal(f"input model {model_path} does not exist")
    bst = Booster(model_file=model_path, params=dict(params))
    loaded = load_text_file(
        data_path,
        header=str(params.get("header", "false")).lower() in ("true", "1"),
        label_column=params.get("label_column", 0),
        weight_column=params.get("weight_column", ""),
        group_column=params.get("group_column", ""),
        ignore_column=params.get("ignore_column", ""),
    )
    new_bst = bst.refit(
        loaded["X"], loaded["label"],
        decay_rate=float(params.get("refit_decay_rate", 0.9)),
        weight=loaded["weight"], group=loaded["group"],
    )
    out = params.get("output_model", "LightGBM_model.txt")
    new_bst.save_model(out)
    log.info(f"Finished the refit task; new model saved to {out}")


def _task_serve(params: Dict[str, str]) -> None:
    """task=serve: load input_model into the serving registry and run
    the scoring loop (lightgbm_tpu/serving, docs/SERVING.md). With
    serve_port=0 (default) speaks line-delimited JSON over
    stdin/stdout — one request per line, one response line each; with
    serve_port>0 runs the HTTP front end on that port. More models can
    be loaded/hot-swapped at runtime through the protocol's
    load/swap/rollback ops."""
    import jax

    from .config import Config
    from .serving import ModelRegistry, ScoringServer, serve_http

    t0 = time.time()
    cfg = Config(dict(params))
    model_path = params.get("input_model", "LightGBM_model.txt")
    if not Path(model_path).exists():
        log.fatal(f"input model {model_path} does not exist")
    prev_logger = (log._logger, log._info_method, log._warning_method,
                   log._debug_method)
    if cfg.serve_port == 0:
        # stdio mode: the protocol owns stdout — framework logs move to
        # stderr BEFORE anything (registry load, mesh setup) can emit,
        # so an info line can never corrupt a JSON response (restored on
        # exit: the logger is process-global state and in-process
        # callers must not inherit the reroute)
        class _StderrLogger:
            @staticmethod
            def info(msg: str) -> None:
                print(msg, file=sys.stderr, flush=True)

            warning = info

        log.register_logger(_StderrLogger)
    try:
        mesh = None
        if jax.device_count() > 1:
            from .parallel.data_parallel import make_mesh

            mesh = make_mesh(axis_name=cfg.tpu_mesh_axes.split(",")[0])
            log.info(
                f"serving rows sharded over {jax.device_count()} devices"
            )
        # chaos testing: a fault plan from config/env arms the
        # serve_request / device_put sites (docs/RESILIENCE.md)
        from .resilience import faultinject

        faultinject.configure(cfg.fault_plan)
        if cfg.serve_fleet:
            # multi-tenant fleet: capacity-bounded HBM residency with
            # LRU paging instead of a table set per model
            # (serving/fleet.py, docs/SERVING.md "Fleet serving")
            from .serving import ModelFleet

            registry = ModelFleet(
                mesh=mesh, buckets=cfg.serve_buckets,
                warmup=cfg.serve_warmup,
                deadline_s=cfg.serve_deadline_ms / 1000.0,
                queue_cap=cfg.serve_queue_cap,
                capacity=cfg.serve_fleet_capacity,
                slots_per_family=cfg.serve_fleet_slots,
            )
        else:
            registry = ModelRegistry(
                mesh=mesh, buckets=cfg.serve_buckets,
                warmup=cfg.serve_warmup,
                deadline_s=cfg.serve_deadline_ms / 1000.0,
                queue_cap=cfg.serve_queue_cap,
                replicas=cfg.serve_replicas,
            )
        registry.load(cfg.serve_model_name, model_path)
        if cfg.serve_port > 0:
            import signal
            import threading

            # SIGTERM = graceful drain: readiness flips false (the
            # gateway stops routing here), new POSTs shed 503
            # shutdown, in-flight requests finish (server_close joins
            # handler threads), then the process exits — the backend
            # half of tools/gateway_rolling.sh
            draining = threading.Event()  # lint: allow[per-call-lock] — one per process, shared with every handler thread
            httpd = serve_http(
                registry, cfg.serve_port, cfg.serve_host, block=False,
                socket_timeout_s=cfg.serve_socket_timeout_s,
                max_body_mb=cfg.serve_max_body_mb, draining=draining)

            def _drain(signum, frame):  # noqa: ARG001 — signal API
                draining.set()
                # shutdown() must run off the serve_forever thread
                threading.Thread(target=httpd.shutdown,
                                 daemon=True).start()

            try:
                signal.signal(signal.SIGTERM, _drain)
            except ValueError:
                pass  # not the main thread (in-process callers)
            try:
                httpd.serve_forever()
            except KeyboardInterrupt:
                pass
            finally:
                httpd.server_close()
        else:
            n = ScoringServer(registry).serve(sys.stdin, sys.stdout)
            print(f"[serve] handled {n} requests", file=sys.stderr)
        # summary logged HERE, while the stdio reroute is still
        # registered: in stdio mode the protocol owns stdout to EOF, so
        # main() must not append its own line after the logger restore
        log.info(f"Finished, elapsed {time.time()-t0:.2f} seconds")
    finally:
        (log._logger, log._info_method, log._warning_method,
         log._debug_method) = prev_logger


def _task_gateway(params: Dict[str, str]) -> None:
    """task=gateway: the resilient serving gateway
    (serving/gateway.py, docs/RESILIENCE.md "Serving gateway") — a
    host-side HTTP front end spreading traffic over the ``task=serve``
    backend processes named by ``gateway_backends=`` (comma-separated
    base URLs). Least-outstanding balancing over /readyz-passing
    backends, full-jitter retries and latency-triggered hedging for
    idempotent ops, per-backend circuit breakers, end-to-end deadline
    propagation, and SIGTERM graceful drain. ``GET /metrics`` serves
    the MERGED fleet exposition (gateway + every live backend)."""
    import signal
    import threading

    from .config import Config
    from .resilience import faultinject
    from .serving.gateway import Gateway, gateway_http

    t0 = time.time()
    cfg = Config(dict(params))
    # chaos testing: arm the gw_* sites before any request flows
    faultinject.configure(cfg.fault_plan)
    urls = [u.strip() for u in str(cfg.gateway_backends).split(",")
            if u.strip()]
    if not urls:
        log.fatal("task=gateway needs gateway_backends= "
                  "(comma-separated backend base URLs)")
    gw = Gateway(
        urls,
        retries=cfg.gateway_retries,
        backoff_base_s=cfg.gateway_backoff_base_s,
        hedge_quantile=cfg.gateway_hedge_quantile,
        hedge_budget=cfg.gateway_hedge_budget,
        breaker_failures=cfg.gateway_breaker_failures,
        breaker_cooldown_s=cfg.gateway_breaker_cooldown_s,
        default_deadline_ms=cfg.gateway_deadline_ms,
        health_interval_s=cfg.gateway_health_interval_s,
        attempt_timeout_s=cfg.serve_socket_timeout_s,
    )
    gw.start()
    httpd = gateway_http(
        gw, cfg.gateway_port, cfg.gateway_host, block=False,
        max_body_mb=cfg.serve_max_body_mb,
        socket_timeout_s=cfg.serve_socket_timeout_s)

    def _drain(signum, frame):  # noqa: ARG001 — signal API
        def _go() -> None:
            # deregister (readyz 503) + shed new work, finish
            # in-flight, then stop the listener
            gw.drain(cfg.gateway_drain_timeout_s)
            httpd.shutdown()

        threading.Thread(target=_go, daemon=True).start()

    try:
        signal.signal(signal.SIGTERM, _drain)
    except ValueError:
        pass  # not the main thread (in-process callers)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        gw.stop()
        httpd.server_close()
    log.info(f"Finished, elapsed {time.time()-t0:.2f} seconds")


def _task_loop(params: Dict[str, str]) -> None:
    """task=loop: the online train-and-serve loop (lightgbm_tpu/online,
    docs/RESILIENCE.md "Online loop"). Serves the promoted model on the
    configured transport while verdict cycles refit / gate / promote
    from microbatches spooled through the ``ingest`` op. ``valid_data=``
    names the fixed holdout shard the gate judges on; v0 comes from
    ``input_model=`` if it exists, else is trained from ``data=``, and
    a loop_dir that already holds state resumes from it regardless."""
    import threading

    from .config import Config
    from .online import OnlineLoop, state_path
    from .resilience import faultinject
    from .serving import ModelRegistry, ScoringServer, serve_http

    t0 = time.time()
    cfg = Config(dict(params))
    # chaos testing: arm loop_* / serve_request sites before anything
    faultinject.configure(cfg.fault_plan)

    from .parsers import load_text_file

    vpath = str(params.get("valid_data", params.get("valid", ""))
                ).split(",")[0]
    if not vpath:
        log.fatal("task=loop needs valid_data= (the holdout shard the "
                  "promotion gate judges on)")
    loaded = load_text_file(
        vpath,
        header=str(params.get("header", "false")).lower() in ("true", "1"),
        label_column=params.get("label_column", 0),
        weight_column=params.get("weight_column", ""),
        group_column=params.get("group_column", ""),
        ignore_column=params.get("ignore_column", ""),
        categorical_feature=params.get("categorical_feature", ""),
    )
    holdout = (loaded["X"], loaded["label"], loaded["weight"])

    init_model = None
    if not Path(state_path(cfg.loop_dir)).exists():
        model_path = params.get("input_model", "")
        if model_path and Path(model_path).exists():
            init_model = model_path
        elif params.get("data"):
            from . import train as lgb_train

            ds = _load_dataset(params, params["data"])
            log.info(f"task=loop: training v0 from {params['data']}")
            init_model = lgb_train(dict(params), ds,
                                   num_boost_round=cfg.num_iterations)
        else:
            log.fatal("task=loop needs input_model= or data= to seed v0 "
                      "(or an existing loop_dir to resume)")

    loop = OnlineLoop(dict(params), holdout, initial_model=init_model)
    registry = ModelRegistry(
        buckets=cfg.serve_buckets, warmup=cfg.serve_warmup,
        deadline_s=cfg.serve_deadline_ms / 1000.0,
        queue_cap=cfg.serve_queue_cap, replicas=cfg.serve_replicas,
    )
    loop.attach(registry, cfg.serve_model_name)

    if cfg.serve_port > 0:
        httpd = serve_http(registry, cfg.serve_port, cfg.serve_host,
                           block=False)
        server_thread = threading.Thread(
            target=httpd.serve_forever, name="lgb-loop-http", daemon=True)
        server_thread.start()
        try:
            n = loop.run()
            log.info(f"task=loop: {n} verdict cycle(s) complete")
        finally:
            httpd.shutdown()
            httpd.server_close()
        log.info(f"Finished, elapsed {time.time()-t0:.2f} seconds")
        return

    # stdio mode: the JSONL protocol owns stdout to EOF (same logger
    # reroute as task=serve); the loop drives from a background thread
    # and stops when the request stream ends
    prev_logger = (log._logger, log._info_method, log._warning_method,
                   log._debug_method)

    class _StderrLogger:
        @staticmethod
        def info(msg: str) -> None:
            print(msg, file=sys.stderr, flush=True)

        warning = info

    log.register_logger(_StderrLogger)
    try:
        loop_thread = threading.Thread(
            target=loop.run, name="lgb-online-loop", daemon=True)
        loop_thread.start()
        n = ScoringServer(registry).serve(sys.stdin, sys.stdout)
        loop.stop_event.set()
        loop_thread.join(timeout=60.0)
        print(f"[loop] handled {n} requests", file=sys.stderr)
        log.info(f"Finished, elapsed {time.time()-t0:.2f} seconds")
    finally:
        (log._logger, log._info_method, log._warning_method,
         log._debug_method) = prev_logger


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    params = parse_kv_args(argv)
    # device_type=cpu (alias device=cpu, reference config.h device_type)
    # steers the run onto the CPU backend
    device = params.get("device_type", params.get("device", ""))
    if device == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
    if not params:
        print(
            "usage: python -m lightgbm_tpu config=<file> [key=value ...]\n"
            "tasks: train (default), predict, save_binary, "
            "convert_model, refit, serve, gateway, loop",
            file=sys.stderr,
        )
        return 1
    task = params.get("task", "train")
    # ---- observability hooks (docs/OBSERVABILITY.md): runtime phase
    # timing, jax.profiler + span capture, and the run manifest
    def _truthy(v: Any) -> bool:
        return str(v).strip().lower() in ("true", "1", "yes", "on")

    if _truthy(params.get("timetag", "")):
        from .timer import enable_timetag

        enable_timetag()
    profile_dir = str(params.get("profile_dir", "")).strip()
    manifest_path = str(
        params.get("run_manifest", params.get("manifest_file", ""))
    ).strip()
    rec = None
    if profile_dir or manifest_path:
        # start compile-event counting now so the manifest's numbers
        # cover the whole run
        from .analysis.retrace import ensure_installed

        ensure_installed()
    if profile_dir:
        import jax

        from .obs import tracing

        os.makedirs(profile_dir, exist_ok=True)
        rec = tracing.start_tracing()
        try:
            jax.profiler.start_trace(profile_dir)
        except Exception as e:  # noqa: BLE001 — span capture still works
            log.warning(f"jax.profiler trace capture unavailable: {e}")
    t0 = time.time()
    try:
        if task == "train":
            _task_train(params)
        elif task in ("predict", "prediction", "test"):
            _task_predict(params)
        elif task == "save_binary":
            _task_save_binary(params)
        elif task == "convert_model":
            _task_convert_model(params)
        elif task in ("refit", "refit_tree"):
            _task_refit(params)
        elif task == "serve":
            _task_serve(params)  # logs its own protocol-safe summary
            return 0
        elif task == "gateway":
            _task_gateway(params)  # logs its own summary
            return 0
        elif task == "loop":
            _task_loop(params)  # logs its own protocol-safe summary
            return 0
        else:
            log.fatal(f"Unknown task {task}")
        log.info(f"Finished, elapsed {time.time()-t0:.2f} seconds")
        return 0
    finally:
        # export failures must never mask the task's own error; and
        # after task=serve the stdio protocol has owned stdout to EOF —
        # export log lines go to stderr so a strict JSONL consumer
        # never sees a non-JSON line on the response stream
        prev_logger = None
        if task in ("serve", "gateway", "loop") \
                and (profile_dir or manifest_path):
            prev_logger = (log._logger, log._info_method,
                           log._warning_method, log._debug_method)

            class _ExportStderrLogger:
                @staticmethod
                def info(msg: str) -> None:
                    print(msg, file=sys.stderr, flush=True)

                warning = info

            log.register_logger(_ExportStderrLogger)
        try:
            if profile_dir:
                import jax

                from .obs import tracing

                try:
                    jax.profiler.stop_trace()
                except Exception:  # noqa: BLE001 — trace may not have started
                    pass
                tracing.stop_tracing()
                if rec is not None:
                    try:
                        rec.write_chrome(
                            os.path.join(profile_dir, "trace_events.json")
                        )
                    except OSError as e:
                        log.warning(f"trace export failed: {e}")
            if profile_dir or manifest_path:
                try:
                    from .config import Config
                    from .obs.manifest import write_manifest

                    cfg = Config(dict(params))
                    targets = [p for p in (
                        manifest_path,
                        os.path.join(profile_dir, "run_manifest.json")
                        if profile_dir else "",
                    ) if p]
                    for p in targets:
                        write_manifest(p, config=cfg, extra={"task": task})
                except Exception as e:  # noqa: BLE001 — incl. config fatals
                    log.warning(f"run manifest not written: {e}")
        finally:
            if prev_logger is not None:
                (log._logger, log._info_method, log._warning_method,
                 log._debug_method) = prev_logger


if __name__ == "__main__":
    sys.exit(main())
