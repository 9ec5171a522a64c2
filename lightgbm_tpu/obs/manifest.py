"""Run manifests: one JSON record describing what ran, where, and what
it cost.

A BENCH json answers "how fast"; a manifest answers "what exactly was
this run" — resolved config, device topology, software versions,
compile counts (from the retrace guard's process-lifetime counters),
phase-timer totals, the metrics snapshot, and runtime collective
wire-byte estimates side by side with the static budgets pinned in
``analysis/cost_budget.json``. Written per training run through the
``run_manifest`` / ``profile_dir`` CLI params (cli.py), or directly
via :func:`write_manifest`.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Any, Dict, Optional

SCHEMA = "lightgbm-tpu/run-manifest/v1"

# config keys always recorded resolved (beyond the explicit params):
# the ones that change what the run computes or how it is distributed
_CORE_KEYS = (
    "task", "objective", "boosting", "num_iterations", "num_leaves",
    "learning_rate", "max_bin", "tree_learner", "num_class",
    "use_quantized_grad", "tpu_growth_mode", "tpu_hist_dtype",
)


def _device_info() -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    return {
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "local_device_count": jax.local_device_count(),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "device_kinds": sorted({getattr(d, "device_kind", "?")
                                for d in devs}),
    }


def _versions() -> Dict[str, str]:
    import jax
    import numpy as np

    out = {
        "python": sys.version.split()[0],
        "jax": jax.__version__,
        "numpy": np.__version__,
    }
    try:
        import jaxlib

        out["jaxlib"] = jaxlib.__version__
    except Exception:  # noqa: BLE001 — jaxlib version is best-effort
        pass
    return out


def _static_wire_budget() -> Dict[str, int]:
    """wire_bytes per audited entry from analysis/cost_budget.json (the
    exact static pins the runtime counter is compared against)."""
    from pathlib import Path

    from ..analysis import cost_audit

    path = Path(cost_audit.__file__).parent / "cost_budget.json"
    if not path.exists():
        return {}
    budgets = json.loads(path.read_text())
    return {
        name: int(d.get("wire_bytes", 0))
        for name, d in budgets.items()
    }


def build_manifest(config: Optional[Any] = None,
                   booster: Optional[Any] = None,
                   extra: Optional[Dict[str, Any]] = None
                   ) -> Dict[str, Any]:
    """Assemble the manifest dict (JSON-serializable).

    config: a Config (or plain params dict); booster: a trained
    Booster (model summary section); extra: caller payload merged in
    under "extra"."""
    from ..analysis.retrace import compile_counters
    from ..timer import global_timer
    from .metrics import default_registry

    cfg_section: Dict[str, Any] = {}
    if config is not None:
        if hasattr(config, "explicit_params"):
            cfg_section["explicit"] = dict(config.explicit_params())
            cfg_section["resolved"] = {
                k: getattr(config, k) for k in _CORE_KEYS if k in config
            }
        else:
            cfg_section["explicit"] = dict(config)

    reg = default_registry()
    snap = reg.snapshot()
    runtime_wire = sum(
        snap.get("lgbmtpu_collective_wire_bytes_total", {}).values()
    )
    manifest: Dict[str, Any] = {
        "schema": SCHEMA,
        "created_unix": time.time(),
        "argv": list(sys.argv),
        "config": cfg_section,
        "devices": _device_info(),
        "versions": _versions(),
        "compile": compile_counters(),
        "phase_timers": {
            name: {"seconds": round(acc, 6), "calls": cnt}
            for name, (acc, cnt) in global_timer.summary().items()
        },
        "metrics": snap,
        "collectives": {
            "runtime_wire_bytes_estimate": int(runtime_wire),
            "static_budget_wire_bytes": _static_wire_budget(),
        },
    }
    # fold in the most recent flight record (docs/OBSERVABILITY.md):
    # rounds recorded, stream path, final evals, anomaly trip counts —
    # the longitudinal run summary next to the point-in-time snapshot
    from .recorder import last_summary

    fr = last_summary()
    if fr is not None:
        manifest["flight_recorder"] = fr
    # fold in the most recent chunked-ingestion record (spool/bin rates
    # and per-chunk peak RSS — the flat-memory proof for out-of-core
    # runs, docs/DATA_PLANE.md)
    from ..data import last_stats

    dp = last_stats()
    if dp is not None:
        manifest["data_plane"] = dp
    if booster is not None:
        try:
            manifest["model"] = {
                "num_trees": booster.num_trees(),
                "best_iteration": getattr(booster, "best_iteration", -1),
                "num_class": getattr(
                    getattr(booster, "_gbdt", None), "num_class", 1
                ),
                # RESOLVED histogram channel layout (may differ from
                # the requested tpu_hist_dtype — e.g. auto, or the
                # off-rounds-path fallback): the numerics provenance a
                # reproduction needs
                "hist_dtype": getattr(
                    getattr(booster, "_gbdt", None), "hist_dtype", None
                ),
                # RESOLVED tree learner after mode resolution plus the
                # voting election footprint (elected columns and the
                # per-tree wire estimate) — distinguishes the
                # elected-columns-only reduce from a full-histogram run
                "tree_learner": getattr(
                    getattr(booster, "_gbdt", None),
                    "tree_learner_resolved", None
                ),
                "voting_elected_cols": getattr(
                    getattr(booster, "_gbdt", None),
                    "voting_elected_cols", None
                ),
                "voting_wire_bytes_est": getattr(
                    getattr(booster, "_gbdt", None),
                    "voting_wire_bytes_est", None
                ),
            }
        except Exception:  # noqa: BLE001 — model summary is best-effort
            pass
    if extra:
        manifest["extra"] = dict(extra)
    return manifest


def write_manifest(path: str, config: Optional[Any] = None,
                   booster: Optional[Any] = None,
                   extra: Optional[Dict[str, Any]] = None
                   ) -> Dict[str, Any]:
    """Build and write the manifest; returns the dict. Tuples and other
    non-JSON values in config params degrade to strings rather than
    failing the run they describe."""
    m = build_manifest(config=config, booster=booster, extra=extra)
    with open(path, "w") as f:
        json.dump(m, f, indent=2, sort_keys=True, default=str)
        f.write("\n")
    from .. import log

    log.info(f"run manifest written to {path}")
    return m
