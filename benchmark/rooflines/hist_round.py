"""Operations and bytes of one pass of the slot-packed histogram kernels
(``pallas_hist._round_kernel`` and ``_nat_kernel``), from shapes.

Two different counts, kept apart on purpose:

- ISSUED: the kernels build a (rows x features*bins) one-hot and contract
  it on the MXU with ``slots x channels`` gradient rows, so one pass
  issues ``slots * channels * rows * features * bins`` multiply-adds
  whether or not a slot is live. Issued operations over the MXU's peak
  over kernel time is how full the MXU is kept *by this formulation*.
- NEEDED: the histogram the algorithm needs adds ``channels`` numbers per
  (row, feature) and has to read each row's bins (``features`` x 4 B as
  the program stores them, int32) and its gradient pair plus count
  (12 B) once per pass. The larger of needed adds over peak and needed
  bytes over HBM bandwidth is the least time a pass could take on the
  chip; that over kernel time is the kernel's roofline share. On a v5e
  the bytes bound it (10.5M x 124 B = 1.30 GB -> 1.59 ms at 819 GB/s,
  against 8.8e8 adds -> 4.5 us at 197 T/s).

Hand numbers these are unit-checked against (ISSUE 22): one full-width
pass (48 slots, 3 channels) over 1M x 28 x 255 issues 2.06e12 FLOP,
about 10 ms at 197 TF/s; 10.5M rows need 1.30e9 bytes, 1.59 ms."""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

CHANNELS = 3  # grad, hess, count (the int-packed layouts of both cells)
BIN_BYTES = 4  # device bins are int32 (dataset.py device_arrays)
GH_BYTES = 12
# the names the device trace shows for the two Pallas calls: the ops line
# names an event by its whole HLO instruction, which starts with the
# wrapper's name and carries the call's shapes, e.g.
# "%hist_round_tpu.8 = (f32[144,7140]{...}, s32[1,10500096]{...}) custom-call(..."
KERNEL_PATTERN = r"^%?(hist_round_tpu|hist_nat_tpu)\b"


def issued_flops(rows: int, features: int, bins: int, slots: int,
                 channels: int = CHANNELS) -> float:
    return 2.0 * slots * channels * rows * features * bins


def needed_adds(rows: int, features: int,
                channels: int = CHANNELS) -> float:
    return float(channels) * rows * features


def needed_bytes(rows: int, features: int) -> float:
    return float(rows) * (features * BIN_BYTES + GH_BYTES)


def peak_ops(peaks: Dict[str, float], hist_dtype: str) -> float:
    """The MXU peak the kernel's operand type runs at."""
    return peaks["int8_ops"] if hist_dtype == "int8" else peaks[
        "bf16_flops"]


def floor_seconds(rows: int, features: int, peaks: Dict[str, float],
                  hist_dtype: str) -> Tuple[float, str]:
    """(least seconds one pass could take, which bound: 'compute' or
    'hbm')."""
    t_ops = needed_adds(rows, features) / peak_ops(peaks, hist_dtype)
    t_mem = needed_bytes(rows, features) / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops > t_mem else (t_mem, "hbm")


def slots_of(instruction: str, features: int, bins: int
             ) -> Optional[int]:
    """Slot count of one kernel call, read from the shapes in its HLO
    instruction (the trace event's name): the kernels' histogram output
    is (slots*channels, features*bins), f32 or s32."""
    for dims in re.findall(r"[fs]32\[(\d+),(\d+)\]", instruction):
        m, k = int(dims[0]), int(dims[1])
        if k == features * bins and m % CHANNELS == 0:
            return m // CHANNELS
    return None


def kernel_passes(trace, features: int, bins: int):
    """[(slots, seconds)] of every histogram-kernel call of the first
    chip inside the trace's window (every chip runs the same program).
    Empty when the trace does not carry the calls' shapes: the readers
    then report nothing instead of guessing a width."""
    dev = min(trace.devices)
    out = []
    for ev in trace.ops_matching(dev, KERNEL_PATTERN):
        slots = slots_of(ev.name, features, bins)
        if slots is None:
            return []
        out.append((slots, ev.dur / 1e9))
    return out
