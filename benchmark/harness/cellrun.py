"""One run of one cell: resolve the names, call the traffic mix's driver,
reduce the trace, ask each per-layer reader for its number, and build
the result line. ``benchmark/run.py`` is the only caller that prints; the
tests call ``run_cell`` directly on a CPU with a tiny configuration."""

from __future__ import annotations

import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

from . import device as device_mod
from .manifest import Cell, load_plugin, resolve_cell
from .spans import Recorder
from .trace import TraceView


@dataclass
class RunArgs:
    seed: int
    seconds: float
    trace: bool
    t_process: float  # perf_counter() at process start: set-up counts from here


@dataclass
class Outcome:
    """What a driver returns. ``end_to_end`` holds every end-to-end
    metric it measured except the two the harness takes itself
    (``setup_s`` needs ``t_window``; the memory peak is read here)."""

    attempted: int
    failed: int
    problems: List[str]
    end_to_end: Dict[str, float]
    t_window: float  # perf_counter() when the measured window began
    facts: Dict[str, Any] = field(default_factory=dict)


@dataclass
class LayerInput:
    """What a per-layer reader sees."""

    cell: Cell
    rec: Recorder
    trace: Optional[TraceView]  # None unless the trace has device planes
    peaks: Optional[Dict[str, float]]  # None off the chip (tests)

    def plugin(self, kind: str, name: str):
        return load_plugin(self.cell.root, kind, name)


def log(msg: str) -> None:
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


def trace_dir(cell: Cell) -> Path:
    """Fixed place inside the checkout (git-ignored) for this cell's
    newest trace."""
    return cell.root / ".bench_out" / "trace" / cell.name


def start_trace(cell: Cell) -> None:
    """Start the profiler; the cell's previous trace is removed first."""
    import jax

    d = trace_dir(cell)
    shutil.rmtree(d, ignore_errors=True)
    d.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    # the Python tracer records every interpreter call: far more host
    # work than the spans the reduction reads
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(d), profiler_options=opts)


def stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()


def read_layers(inp: LayerInput) -> Dict[str, Dict[str, Any]]:
    """{metric: {"value", "unit"}} for every per-layer metric of the
    cell whose reader found something to read."""
    out: Dict[str, Dict[str, Any]] = {}
    for spec in inp.cell.per_layer:
        reader = load_plugin(inp.cell.root, "layer_metrics", spec["name"])
        value = reader.read(inp)
        if value is not None:
            out[spec["name"]] = {"value": float(value),
                                 "unit": spec["unit"]}
    return out


def run_cell(root: Path, workload: str, args: RunArgs,
             device: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """The result object of one run. ``device`` is what
    ``require_accelerator`` returned, or None in a CPU rehearsal (then no
    memory peak and no published peaks are looked up)."""
    cell = resolve_cell(root, workload)
    rec = Recorder()
    driver = load_plugin(root, "drivers", cell.traffic["driver"])
    outcome: Outcome = driver.run(cell, args, rec)
    for p in outcome.problems:
        log(f"NOT CORRECT: {p}")
    log(f"facts: {outcome.facts}")

    dev = dict(device or {"platform": "cpu", "kind": "cpu", "count": 0})
    if device is not None:
        dev["memory_peak_bytes"] = device_mod.memory_peak_bytes()
    result: Dict[str, Any] = {
        "correct": not outcome.problems,
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
    }
    if not args.trace:
        values = dict(outcome.end_to_end)
        values["setup_s"] = outcome.t_window - args.t_process
        if device is not None:
            values["peak_hbm_gb"] = dev["memory_peak_bytes"] / 1e9
        result["metrics"] = {
            e["name"]: {"value": float(values[e["name"]]),
                        "unit": e["unit"]}
            for e in cell.end_to_end if e["name"] in values
        }
    else:
        t0 = time.perf_counter()
        trace = TraceView.newest_under(trace_dir(cell))
        if trace is not None and not trace.devices:
            trace = None  # a CPU rehearsal: host planes only
        inp = LayerInput(
            cell=cell, rec=rec, trace=trace,
            peaks=device_mod.peaks_for(dev["kind"]) if device else None)
        result["metrics"] = read_layers(inp)
        if trace is not None:
            dev["busy_s"] = trace.busy_s()
            dev["window_s"] = trace.window_s
            result["breakdown"] = {"device_ops": trace.top_ops(10),
                                   "idle_gaps": trace.idle_gaps(10)}
        log(f"trace reduced in {time.perf_counter() - t0:.1f}s")
    result["device"] = dev
    return result
