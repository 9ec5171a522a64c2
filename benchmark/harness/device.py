"""The device gate, the device's identity and memory peak, and the one
table of published peaks (peaks.json, keyed by ``device_kind``)."""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict


class NoAccelerator(SystemExit):
    """Raised (as a non-zero exit, with no result line) when JAX holds no
    accelerator or fewer chips than the cell asks for."""


def require_accelerator(chips: int) -> Dict[str, Any]:
    """Initialise the backend in THIS process and refuse a CPU or too
    few chips. Returns the ``device`` object of the result line (less
    the memory peak, which is read after the window)."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform == "cpu":
        raise NoAccelerator(
            f"[benchmark] backend is {d0.platform!r} ({d0.device_kind}, "
            f"{len(devs)} device(s)): the benchmark measures an "
            "accelerator and prints no result from anything else"
        )
    if len(devs) < chips:
        raise NoAccelerator(
            f"[benchmark] the cell needs {chips} chip(s), JAX found "
            f"{len(devs)} ({d0.device_kind})"
        )
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device, as the runtime's
    allocator reports it (process lifetime, so set-up is included)."""
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            raise RuntimeError(
                f"{d} reports no peak_bytes_in_use (memory_stats: "
                f"{sorted(stats)})"
            )
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)


def peaks_for(device_kind: str) -> Dict[str, float]:
    """Published peaks of ``device_kind``; an unknown kind is an error,
    never a default."""
    table = json.loads(
        (Path(__file__).with_name("peaks.json")).read_text()
    )
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"benchmark/harness/peaks.json (have: "
            f"{sorted(k for k in table if not k.startswith('_'))})"
        )
    return table[device_kind]
