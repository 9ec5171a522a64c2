"""Feature-histogram construction.

The reference builds per-(leaf, feature) histograms of (sum_grad,
sum_hess, count) with sequential scatter loops on CPU
(src/io/dense_bin.hpp:99-174 ConstructHistogram) and shared-memory
atomics on CUDA (src/treelearner/cuda/cuda_histogram_constructor.cu).
A TPU has no vector scatter, so scatter-add becomes a one-hot
contraction. Two backends share one data layout:

- **Pallas TPU kernel** (`pallas_hist.hist_tpu`): the one-hot tile only
  ever lives in VMEM, the contraction rides the MXU. Requires the row
  count to be a multiple of `HIST_BLK`.
- **XLA einsum fallback** (CPU tests, virtual meshes, odd row counts):
  same math, one-hot materialized per small row block under `lax.scan`.

Layouts put the LONG (row) axis minor-most everywhere — TPU memory
tiles pad the last dim to 128 lanes, so a row-major (N, 28) bin matrix
would physically occupy 4.5x its nominal bytes. Hence: bins are
feature-major `(F, N)` int32; per-row channels `(8, N)` f32 with rows
`(g_hi, g_lo, h_hi, h_lo, count, 0, 0, 0)`; histograms are `(3, F, B)`
(channel leading, bins on lanes). The bf16x2 split (hi = bf16(x),
lo = x - hi) lets the MXU run in bf16 while the recombined histogram
keeps ~f32 accuracy — the padded channel slots are free because the
matmul M dim pads 3 -> 8 anyway. Gradient/hessian sums per bin are f32
like the reference's GPU path (gpu_hist_t, docs/GPU-Performance.rst).
"""

from __future__ import annotations

import contextlib
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

HIST_BLK = 2048  # pallas row-block; device row padding is a multiple of this
CH = 8
NAT_CH = 5  # useful gh channels packed per slot (g_hi, g_lo, h_hi, h_lo, cnt)
# scoped-VMEM limit every Pallas kernel is compiled with (pallas_hist
# passes it as vmem_limit_bytes; a v5e core has 128 MiB). _round_caps /
# _TAKE_L_CAP below are the compile limits established on the chip AT
# this value
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _interpret_pallas() -> bool:
    """CI hook: LGBM_TPU_PALLAS_INTERPRET=1 runs the TPU kernels under
    the pallas interpreter on CPU so kernel drift is caught off-hardware."""
    import os

    return os.environ.get("LGBM_TPU_PALLAS_INTERPRET", "") == "1"


def _use_pallas() -> bool:
    from .._backend import on_tpu

    return _interpret_pallas() or on_tpu()


_gate_warned: set = set()


def _pallas_ok(kernel: str, n_rows: int, fits: bool = True,
               why: str = "",
               instead: str = "the XLA formulation") -> bool:
    """The one gate in front of every Pallas kernel: the backend runs
    Pallas (a TPU, or the interpreter under test), the row count is
    HIST_BLK-aligned, and the caller's VMEM condition `fits` holds.
    Off-TPU a miss is how the XLA formulations get chosen. ON a TPU a
    miss means a chip run is about to take the slow formulation
    (`instead` names it), so it warns — once per (kernel, reason) —
    instead of passing silently."""
    if not _use_pallas():
        return False
    if n_rows % HIST_BLK != 0 or n_rows < HIST_BLK:
        reason = (f"row count {n_rows} is not a positive multiple of "
                  f"HIST_BLK={HIST_BLK}")
    elif not fits:
        reason = why
    else:
        return True
    if (kernel, reason) not in _gate_warned:
        _gate_warned.add((kernel, reason))
        from .. import log

        log.warning(
            f"pallas {kernel} not used: {reason}; running {instead} "
            "instead (much slower on TPU)"
        )
    return False


def build_gh8(grad: jax.Array, hess: jax.Array, count: jax.Array) -> jax.Array:
    """(N,) grad/hess/count (already masked) -> (8, N) bf16x2-split channels."""
    g_hi = grad.astype(jnp.bfloat16).astype(jnp.float32)
    g_lo = grad - g_hi
    h_hi = hess.astype(jnp.bfloat16).astype(jnp.float32)
    h_lo = hess - h_hi
    z = jnp.zeros_like(count)
    return jnp.stack([g_hi, g_lo, h_hi, h_lo, count, z, z, z])


def combine_ch(hist8: jax.Array) -> jax.Array:
    """(CH, F, B) accumulated channels -> (3, F, B) (grad, hess, count)."""
    return jnp.stack(
        [hist8[0] + hist8[1], hist8[2] + hist8[3], hist8[4]]
    )


def _hist_fallback(bins_fm: jax.Array, gh8: jax.Array, num_bins: int,
                   blk: int = 512) -> jax.Array:
    """One-hot einsum under lax.scan; any N (pads to a block multiple)."""
    F, N = bins_fm.shape
    gh3 = jnp.stack([gh8[0] + gh8[1], gh8[2] + gh8[3], gh8[4]])  # (3, N)
    if N % blk != 0:
        pad = blk - N % blk
        bins_fm = jnp.pad(bins_fm, ((0, 0), (0, pad)))
        gh3 = jnp.pad(gh3, ((0, 0), (0, pad)))
        N += pad
    nb = N // blk
    bb = bins_fm.reshape(F, nb, blk).transpose(1, 0, 2)  # (nb, F, blk)
    gg = gh3.reshape(3, nb, blk).transpose(1, 0, 2)  # (nb, 3, blk)
    iota = jnp.arange(num_bins, dtype=bins_fm.dtype)

    def body(acc, xs):
        b, g = xs  # (F, blk), (3, blk)
        onehot = (b[:, :, None] == iota).astype(jnp.float32)  # (F, blk, B)
        acc = acc + jnp.einsum(
            "frb,cr->cfb", onehot, g, preferred_element_type=jnp.float32
        )
        return acc, None

    init = jnp.zeros((3, F, num_bins), dtype=jnp.float32)
    hist, _ = lax.scan(body, init, (bb, gg))
    return hist


def histogram(bins_fm: jax.Array, gh8: jax.Array, num_bins: int) -> jax.Array:
    """(F, N) int32 bins + (8, N) channels -> (3, F, B) f32 histogram."""
    F, N = bins_fm.shape
    if _pallas_ok("hist_tpu", N):
        from .pallas_hist import hist_tpu

        return combine_ch(
            hist_tpu(bins_fm, gh8, num_bins, interpret=_interpret_pallas())
        )
    return _hist_fallback(bins_fm, gh8, num_bins)


def _hist_nat_fallback(bins_fm: jax.Array, gh8: jax.Array, slot: jax.Array,
                       num_slots: int, num_bins: int,
                       blk: int = 512, quant: bool = False) -> jax.Array:
    """XLA reference for hist_nat_slots: blocked one-hot einsum with an
    extra slot one-hot axis. Any N; CPU tests and odd row counts."""
    F, N = bins_fm.shape
    S = num_slots
    if quant:
        gh3 = gh8[:3]  # (g_int, h_int, count) — no hi/lo split
    else:
        gh3 = jnp.stack([gh8[0] + gh8[1], gh8[2] + gh8[3], gh8[4]])  # (3, N)
    if N % blk != 0:
        pad = blk - N % blk
        bins_fm = jnp.pad(bins_fm, ((0, 0), (0, pad)))
        gh3 = jnp.pad(gh3, ((0, 0), (0, pad)))
        slot = jnp.pad(slot, (0, pad), constant_values=S)
        N += pad
    nb = N // blk
    bb = bins_fm.reshape(F, nb, blk).transpose(1, 0, 2)  # (nb, F, blk)
    gg = gh3.reshape(3, nb, blk).transpose(1, 0, 2)  # (nb, 3, blk)
    ss = slot.reshape(nb, blk)
    iota_b = jnp.arange(num_bins, dtype=bins_fm.dtype)
    iota_s = jnp.arange(S, dtype=slot.dtype)

    def body(acc, xs):
        b, g, sl = xs  # (F, blk), (3, blk), (blk,)
        onehot = (b[:, :, None] == iota_b).astype(jnp.float32)  # (F, blk, B)
        slh = (sl[None, :] == iota_s[:, None]).astype(jnp.float32)  # (S, blk)
        acc = acc + jnp.einsum(
            "frb,cr,sr->scfb", onehot, g, slh,
            preferred_element_type=jnp.float32,
        )
        return acc, None

    init = jnp.zeros((S, 3, F, num_bins), jnp.float32)
    out, _ = lax.scan(body, init, (bb, gg, ss))
    return out


def build_gh8_quant(gq: jax.Array, hq: jax.Array, count: jax.Array) -> jax.Array:
    """Quantized-channel layout: (g_int, h_int, count, 0, ...). Integer
    levels (|g| <= num_grad_quant_bins/2 etc.) are exact in bf16, so the
    hi/lo split is unnecessary — 3 channels per slot instead of 5 packs
    48 slots per MXU pass (the TPU analog of the reference's int16
    histogram entries, bin.h:63-81)."""
    z = jnp.zeros_like(count)
    return jnp.stack([gq, hq, count, z, z, z, z, z])


def hist_nat_slots(
    bins_fm: jax.Array,  # (F, N) int32, NATURAL row order
    gh8: jax.Array,  # (8, N) f32 build_gh8 channels
    slot: jax.Array,  # (N,) int32 in [0, num_slots]; num_slots = trash
    num_slots: int,
    num_bins: int,
    quant: bool = False,  # gh8 built by build_gh8_quant (3 channels)
    int8: bool = False,  # quant levels within +/-127: s8 MXU, s32 sums
    oh_shift: int = 0,  # SWAR one-hot scale (int8_oh_shift policy)
    plan: Optional["HistPlan"] = None,  # the caller's program-wide
    # plan (rounds.py: one feature block for all of a tree's passes);
    # else this call's own
) -> jax.Array:
    """Per-slot histograms keyed by a row->slot vector -> (S, 3, F, B).

    The natural-order multi-leaf construction: rows never move; each
    row's slot assignment selects which histogram it accumulates into.
    On TPU this is ONE pass of the slot-packed MXU kernel
    (pallas_hist.hist_nat_tpu) — the matmul M axis carries
    num_slots x NAT_CH channel rows, so up to ~25 slots cost the same
    wall time as a single-leaf histogram (the M=8 single-hist matmul
    leaves 120 of the MXU's 128 rows idle). Multi-leaf batching as in
    the reference CUDA kernel (cuda_histogram_constructor.cu:20) without
    its per-leaf row indices."""
    F, N = bins_fm.shape
    nat_ch = 3 if quant else NAT_CH
    use_i8 = bool(int8 and quant)
    # quant levels past s8 (the int16 program): two s8 limbs a level
    use_limbs = bool(quant and not use_i8)
    # VMEM guard (hist_plan): the slot axis in chunks and, past one
    # bins tile, the feature axis in blocks, so that a call's resident
    # output block stays within its share of the stated scoped limit
    if plan is None:
        plan = hist_plan(num_slots, F, num_bins, quant, use_i8)
    n_local, ax, mesh = _row_layout(N)
    if _pallas_ok("hist_nat_tpu", n_local, plan.s_max > 0,
                  f"one slot's output block at {F} columns x {num_bins} "
                  "bins exceeds the VMEM budget"):
        from .pallas_hist import hist_nat_tpu

        def call(bins_fm, gh8, slot):
            parts = []
            for c0, sc in _slot_chunks(num_slots, plan.s_max):
                if c0 == 0 and sc == num_slots:
                    local = slot
                else:
                    in_chunk = (slot >= c0) & (slot < c0 + sc)
                    local = jnp.where(in_chunk, slot - c0, sc)
                out = hist_nat_tpu(
                    bins_fm, gh8, local, sc, num_bins,
                    interpret=_interpret_pallas(), nat_ch=nat_ch,
                    int8=use_i8, oh_shift=oh_shift,
                    feat_block=plan.feat_block, limbs=use_limbs,
                )  # (sc*nat_ch, F*B)
                o = out.reshape(sc, nat_ch, F, num_bins)
                if quant:
                    parts.append(o)
                else:
                    parts.append(jnp.stack(
                        [o[:, 0] + o[:, 1], o[:, 2] + o[:, 3], o[:, 4]],
                        axis=1))
            out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            return out if ax is None else lax.psum(out, ax)

        return _on_rows(
            call, mesh, (P(None, ax), P(None, ax), P(ax)), P()
        )(bins_fm, gh8, slot)
    return _hist_nat_fallback(bins_fm, gh8, slot, num_slots, num_bins,
                              quant=quant)


def int8_oh_shift(n_rows: int, quant_levels: int) -> Optional[int]:
    """SWAR one-hot scale policy for the int8 histogram path.

    The SWAR one-hot bytes carry value 128 >> shift, so a histogram
    cell's s32 accumulator sees sums up to n_rows * level * (128 >>
    shift). Pick the cheapest shift (0 is ~2 VPU ops/vector cheaper
    than 4; 7 yields exact 1s like the compare path) that keeps the
    worst case under 2^31; None means even unscaled sums can overflow
    and the caller must not use int8 at all (ADVICE r4: a near-constant
    feature on a >16M-row dataset at max levels wraps silently — the
    reference's int32 buffers have the same bound, bin.h:63-81)."""
    levels = max(int(quant_levels), 1)
    for shift in (0, 4, 7):
        if n_rows * levels * (128 >> shift) < 2 ** 31:
            return shift
    return None


def rs_exact_ok(local_rows: int, n_ranks: int, quant_levels: int) -> bool:
    """Worst-case exactness bound for the int32 reduce-scatter wire
    (ADVICE r5 medium; same policy shape as int8_oh_shift).

    The rs wire ships per-rank integer histogram sums as int32 and the
    'quantized sums are exact, the wire is lossless' claim needs BOTH:

    - global: the mesh-wide hessian-channel cell sum reaches
      local_rows * n_ranks * quant_levels, which must stay under 2^31
      or the int32 reduction wraps silently (~8.4M global rows at 256
      levels — exactly the pod scale the path targets);
    - local: each rank accumulates its integer sums in f32 before the
      astype(int32) cast, so the per-rank worst case must stay within
      f32's exact-integer range 2^24 or the cast quantizes.

    False sends the caller to the f32 psum fallback (lossy-by-design,
    like the reference's f32 histogram mode). Static ints only — the
    decision is a trace-time constant, never a device value."""
    return rs_wire_dtype(local_rows, n_ranks, quant_levels) is not None


def rs_wire_dtype(local_rows: int, n_ranks: int,
                  quant_levels: int) -> "str | None":
    """Narrowest exact dtype for the reduce-scatter histogram wire
    (ROADMAP 3a; the reference's int16/int32 socket reducers,
    include/LightGBM/bin.h:63-81).

    - "int16" when the mesh-wide hessian-channel worst case
      local_rows * n_ranks * quant_levels stays under 2^15 — the
      per-rank partial AND the reduced global sum both fit int16, so
      the wire payload halves with no loss (the count channel is
      bounded by global rows, which the same product dominates);
    - "int32" under the wider bounds: global worst case under 2^31,
      and per-rank sums within f32's exact-integer range 2^24 (ranks
      accumulate in f32 before the integer cast);
    - None sends the caller to the f32 psum fallback (lossy-by-design,
      like the reference's f32 histogram mode).

    Static ints only — a trace-time constant, never a device value."""
    levels = max(int(quant_levels), 1)
    if local_rows * n_ranks * levels < 2 ** 15:
        return "int16"
    if (local_rows * n_ranks * levels < 2 ** 31
            and local_rows * levels < 2 ** 24):
        return "int32"
    return None


def _round_caps(nat_ch: int) -> tuple:
    """(slot cap, output-block VMEM budget) for the slot-packed kernels,
    shared by hist_nat_slots and the fused round kernel. The kernel's
    scoped-VMEM need is a multiple of its grid-constant output block
    (double-buffered block + the W tile, per-feature one-hots and
    (S, blk) partition temporaries, all linear in slots): measured
    5.2x at ch3 S=45 F=28 B=255 under libtpu 0.0.34 (19.03 MiB), so the
    block may take a fifth of VMEM_LIMIT_BYTES less the iota scratch.
    The slot caps bound the matmul M axis (S x channels) and Mosaic
    compile time, which grows faster than linearly in S."""
    return (32 if nat_ch >= 5 else 64), VMEM_LIMIT_BYTES // 5


def _oh_scratch_bytes(num_bins: int, int8: bool) -> int:
    """VMEM bytes of the kernels' persistent one-hot iota scratch
    (pallas_hist._oh_iota_shape): part of the explicit block schedule,
    so the slot-budget math must charge for it."""
    from .pallas_hist import _oh_iota_shape

    rows, blk = _oh_iota_shape(num_bins, HIST_BLK, int8)
    return rows * blk * 4


# the fused round kernel may chunk its slot axis (each chunk re-streams
# the bins/gh blocks, so the fan-out is capped — past this the
# non-fused path's separate passes are no worse)
_ROUND_MAX_CHUNKS = 4


def _slot_chunks(num_slots: int, s_max: int) -> list:
    """[(first slot, slots)] of the kernel calls that cover `num_slots`
    at most `s_max` at a time: the fewest calls, of EQUAL size (32 slots
    under a cap of 25 run as 16 + 16, not 25 + 7: two kernel shapes
    fewer to compile, and a 7-slot call costs the one-hot floor of a
    16-slot one)."""
    calls = -(-num_slots // s_max)
    size = -(-num_slots // calls)
    return [(c0, min(size, num_slots - c0))
            for c0 in range(0, num_slots, size)]


def _slot_block_bytes(nat_ch: int, num_feat: int, num_bins: int) -> int:
    """Bytes one slot takes of a histogram kernel's output block: its
    channels x the block's columns (whole feature groups,
    pallas_hist.hist_out_block) x a column's lanes (its bins, or the
    pair's stride), 4 B each."""
    from .pallas_hist import column_stride, feature_groups

    groups, per_group = feature_groups(num_feat, num_bins)
    return nat_ch * groups * per_group * column_stride(num_bins) * 4


def _round_s_max(num_feat: int, num_bins: int, quant: bool,
                 int8: bool) -> int:
    nat_ch = 3 if quant else NAT_CH
    s_cap, budget = _round_caps(nat_ch)
    budget = max(budget - _oh_scratch_bytes(num_bins, int8), 0)
    per_slot = _slot_block_bytes(nat_ch, num_feat, num_bins)
    if per_slot > budget:
        return 0
    return max(1, min(budget // max(per_slot, 1), s_cap))


# columns of the (columns, HIST_BLK) int32 bins tile a kernel call may
# hold per grid step: double-buffered, an eighth of the scoped limit
# (512 columns, 8 MiB). The cells' tables (28, 137 columns) are a
# fraction of it; a 2,000-column tile would be 2 x 16.4 MB before any
# output block.
_TILE_COLS = VMEM_LIMIT_BYTES // 8 // (2 * HIST_BLK * 4)


class HistPlan(NamedTuple):
    """How the slot-packed histogram kernels cover `num_slots` slots of
    a (num_feat, N) table (hist_plan)."""

    s_max: int  # slots one kernel call holds (0: not even one fits)
    feat_block: int  # columns of one feature block (num_feat: the whole
    # table is one bins tile and one resident output block)
    num_feat: int

    @property
    def blocks(self) -> int:
        return -(-self.num_feat // self.feat_block)


def hist_plan(num_slots: int, num_feat: int, num_bins: int, quant: bool,
              int8: bool = False) -> HistPlan:
    """Static VMEM plan of a histogram pass, from the shapes alone.

    While the whole table fits one call's schedule (one slot's output
    block inside the budget, the slot axis in at most _ROUND_MAX_CHUNKS
    chunks, the bins tile inside _TILE_COLS) the plan is the whole
    table in chunks of _round_s_max slots: every program of 28 or 137
    columns. Past that the FEATURE axis is blocked instead, so that a
    pass still reads the bin matrix once (each slot chunk would
    re-stream it): blocks of whole FEATURE_UNROLL groups, equal, the
    widest whose min(num_slots, slot cap)-slot output block fits the
    same budget. 2,000 columns x 33..64 bins (a column's stride in the
    block is 64 lanes, pallas_hist.column_stride), 3 channels, 48 slots:
    one group of one slot is 3 x 32 x 64 x 4 = 24,576 B, the budget
    12,373,196 B (a fifth of the limit less the 128-row iota scratch of
    a pair's one-hot tile), so 10 groups fit 48 slots, the 63 groups go
    in 7 blocks of 9 (288 columns, 10.6 MB); at 255 bins 2 groups, 32
    blocks of 64 columns."""
    from .pallas_hist import FEATURE_UNROLL, column_stride

    nat_ch = 3 if quant else NAT_CH
    s_whole = _round_s_max(num_feat, num_bins, quant, int8)
    whole = HistPlan(s_whole, num_feat, num_feat)
    if (s_whole > 0 and num_slots <= _ROUND_MAX_CHUNKS * s_whole
            and num_feat <= _TILE_COLS):
        return whole
    s_cap, budget = _round_caps(nat_ch)
    budget = max(budget - _oh_scratch_bytes(num_bins, int8), 0)
    # one slot's block of one group
    group = nat_ch * FEATURE_UNROLL * column_stride(num_bins) * 4
    slots = min(num_slots, s_cap, budget // group)
    if slots < 1:
        return whole
    groups = -(-num_feat // FEATURE_UNROLL)
    fit = min(budget // (slots * group), _TILE_COLS // FEATURE_UNROLL)
    blocks = -(-groups // max(fit, 1))
    if blocks == 1:  # nothing to block: the whole table, chunked
        return whole
    return HistPlan(slots, -(-groups // blocks) * FEATURE_UNROLL, num_feat)


def can_hist_round(n_rows: int, num_slots: int, num_feat: int,
                   num_bins: int, quant: bool,
                   int8: bool = False) -> bool:
    """Static gate for the fused round kernel (pallas path only),
    which holds the WHOLE table's bins tile. The slot axis may be
    CHUNKED (hist_round composes the disjoint per-chunk partition
    updates), so the gate requires one chunk to fit the scoped-VMEM
    schedule and caps the re-stream fan-out at _ROUND_MAX_CHUNKS. A
    table that hist_plan blocks by features never asks here: its round
    is the routing pass over the split columns and a blocked
    slot-keyed pass (rounds.py)."""
    s_max = _round_s_max(num_feat, num_bins, quant, int8)
    return _pallas_ok(
        "hist_round_tpu", n_rows,
        s_max > 0 and num_slots <= _ROUND_MAX_CHUNKS * s_max
        and num_feat <= _TILE_COLS,
        f"{num_slots} slots at {num_feat} columns x {num_bins} bins need "
        f"more than {_ROUND_MAX_CHUNKS} chunks of {s_max} slots, or a "
        f"bins tile past {_TILE_COLS} columns",
        instead="the XLA partition and hist_nat_tpu passes",
    )


def hist_round(
    bins_fm: jax.Array,  # (F, N) int32
    gh8: jax.Array,  # (CH, N) f32
    pleaf: jax.Array,  # (N,) int32 row -> leaf
    params: jax.Array,  # (S, 16) int32 per-slot split params
    col_onehot: jax.Array,  # (S, F) f32
    num_slots: int,
    num_bins: int,
    quant: bool = False,
    int8: bool = False,
    oh_shift: int = 0,
    efb: bool = False,
    cat_mask=None,
):
    """Fused round step -> ((S, 3, F, B) f32 histograms, (N,) new
    row->leaf). Callers must check can_hist_round first; histogram
    sums are exact (integer s32 on the int8 path, rescaled here; per
    row block in s32 on the limb path, pallas_hist._nat_kernel).

    When S exceeds the one-chunk VMEM schedule, the slot axis is
    chunked: every chunk sees the ORIGINAL row->leaf vector and only
    its own slots' split params, so the per-chunk partition deltas
    touch disjoint rows (memberships are disjoint across slots) and
    compose by summation — pleaf_new = pleaf + sum(pleaf_chunk -
    pleaf). Histogram chunks concatenate along the slot axis."""
    from .pallas_hist import hist_round_tpu, _swar_divisor

    F, N = bins_fm.shape
    nat_ch = 3 if quant else NAT_CH
    use_int8 = bool(int8 and quant)
    use_limbs = bool(quant and not use_int8)  # as in hist_nat_slots
    s_max = _round_s_max(F, num_bins, quant, use_int8) or num_slots
    outs = []
    pl_new = None
    for c0, sc in _slot_chunks(num_slots, s_max):
        out_c, pl_c = hist_round_tpu(
            bins_fm, gh8, pleaf, params[c0:c0 + sc],
            col_onehot[c0:c0 + sc], sc, num_bins, nat_ch,
            int8=use_int8, oh_shift=oh_shift, efb=efb,
            cat_mask=None if cat_mask is None else cat_mask[c0:c0 + sc],
            interpret=_interpret_pallas(), limbs=use_limbs,
        )
        outs.append(out_c.reshape(sc, nat_ch, F, num_bins))
        pl_new = pl_c if pl_new is None else pl_new + (pl_c - pleaf)
    o = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    if use_int8:
        o = o.astype(jnp.float32) * (1.0 / _swar_divisor(oh_shift))
    if quant:
        return o, pl_new
    o3 = jnp.stack([o[:, 0] + o[:, 1], o[:, 2] + o[:, 3], o[:, 4]], axis=1)
    return o3, pl_new


def route_round(
    bins_fm: jax.Array,  # (F, N) int32
    pleaf: jax.Array,  # (N,) int32 row -> leaf
    params: jax.Array,  # (S, 16) int32 per-slot split params
    col_onehot: jax.Array,  # (S, F) f32
    num_slots: int,
    num_bins: int,
    efb: bool = False,
    cat_mask=None,
    with_slot: bool = False,
):
    """hist_round's second output without the first: the (N,) new
    row->leaf of a round whose children nobody will search (the round
    that spends the last of the leaf budget, rounds.py). Same gate as
    hist_round (can_hist_round). ONE call at any slot count: the pass
    has no grid-constant histogram block, so the VMEM schedule that
    chunks hist_round's slot axis (_round_s_max) has nothing to bound
    here; what it holds per slot is a few (1, HIST_BLK) vectors.

    A round at width (hist_plan blocks the features) routes through
    here in EVERY round, over a table of its split columns alone
    (bins_fm[columns], col_onehot the identity), and `with_slot` also
    returns each row's histogram slot (num_slots: none) for the blocked
    hist_nat_slots pass that follows."""
    from .pallas_hist import route_round_tpu

    return route_round_tpu(
        bins_fm, pleaf, params, col_onehot, num_slots, num_bins, efb=efb,
        cat_mask=cat_mask, interpret=_interpret_pallas(),
        with_slot=with_slot,
    )


# the take/seg_sum kernels materialize an (L, HIST_BLK) f32 one-hot
# tile in VMEM per grid step; num_leaves may legally reach 131072
# (config.h num_leaves check), at which point the tile alone (131072 x
# 2048 x 4 = 1 GB) dwarfs the ~16 MB scoped budget and Mosaic compile
# fails where plain XLA take/scatter worked (ADVICE r4 medium). Cap the
# one-hot tile + in/out blocks at a conservative 8 MB -> L <= ~960.
_TAKE_L_CAP = (8 * 2 ** 20) // (HIST_BLK * 4)


# (mesh, axis name) while a data-parallel boosting step is being
# traced OUTSIDE the grower's shard_map: Mosaic kernels cannot be
# partitioned by GSPMD ("wrap the call in a shard_map"), so the per-row
# kernels the step calls on mesh-resident arrays — score updates, valid
# traversal, leaf renewal — wrap themselves per shard. The grower's own
# shard_map body clears it (its kernels already see one shard).
_row_mesh: Optional[tuple] = None


@contextlib.contextmanager
def row_mesh(mesh, axis_name: str = "data"):
    """Trace-time scope naming the data mesh (None clears it)."""
    global _row_mesh
    prev = _row_mesh
    _row_mesh = None if mesh is None or mesh.devices.size <= 1 \
        else (mesh, axis_name)
    try:
        yield
    finally:
        _row_mesh = prev


def _row_layout(n_rows: int):
    """(rows one kernel call sees, row PartitionSpec axis or None, mesh).
    Without an active mesh: the whole array, no wrapping. With one: rows
    split over the mesh axis when every shard stays HIST_BLK-aligned
    (the training rows — padded to HIST_BLK x devices), else the call
    runs replicated on every device (valid sets, padded per dataset)."""
    if _row_mesh is None:
        return n_rows, None, None
    mesh, ax = _row_mesh
    n = int(mesh.devices.size)
    if n_rows % (n * HIST_BLK) == 0:
        return n_rows // n, ax, mesh
    return n_rows, None, mesh


def _on_rows(call, mesh, in_specs, out_specs):
    """`call` as is, or per shard (replicated when the specs say so)
    over the active data mesh."""
    if mesh is None:
        return call
    return jax.shard_map(call, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def take_cols(tab: jax.Array, idx: jax.Array) -> jax.Array:
    """(k, L) table, (N,) int32 indices -> (k, N) tab[:, idx].

    Small tables (L <= _TAKE_L_CAP) on TPU: one-hot MXU contraction
    (pallas take_small_tpu). Large tables — the one-hot costs O(L) per
    row, so past the cap the XLA gather IS the formulation (the
    serving forest's T*M node table) — and non-TPU backends: plain
    take. Negative / >= L indices return 0 on both paths."""
    N = idx.shape[0]
    L = tab.shape[1]
    n_local, ax, mesh = _row_layout(N)
    if L <= _TAKE_L_CAP and _pallas_ok("take_small_tpu", n_local):
        from .pallas_hist import take_small_tpu

        def call(t, i):
            return take_small_tpu(t, i, interpret=_interpret_pallas())

        return _on_rows(call, mesh, (P(), P(ax)), P(None, ax))(tab, idx)
    out = jnp.take(tab, jnp.clip(idx, 0, L - 1), axis=1)
    return jnp.where(((idx >= 0) & (idx < L))[None, :], out, 0.0)


def seg_sum(vals: jax.Array, idx: jax.Array, num_out: int) -> jax.Array:
    """(k, N) values + (N,) int32 indices -> (k, num_out) per-index
    column sums. TPU with num_out <= _TAKE_L_CAP: one-hot MXU
    contraction (pallas seg_sum_tpu); larger outputs and non-TPU
    backends: XLA scatter-add. Out-of-range indices are dropped on
    both paths."""
    k, N = vals.shape
    n_local, ax, mesh = _row_layout(N)
    if num_out <= _TAKE_L_CAP and _pallas_ok("seg_sum_tpu", n_local):
        from .pallas_hist import seg_sum_tpu

        def call(v, i):
            out = seg_sum_tpu(v, i, num_out, interpret=_interpret_pallas())
            return out if ax is None else lax.psum(out, ax)

        return _on_rows(call, mesh, (P(None, ax), P(ax)), P())(vals, idx)
    in_range = (idx >= 0) & (idx < num_out)
    safe = jnp.where(in_range, idx, num_out)  # num_out -> dropped
    return jnp.zeros((k, num_out), vals.dtype).at[:, safe].add(
        jnp.where(in_range[None, :], vals, 0.0), mode="drop"
    )


def gather_rows(bins_fm: jax.Array, idx: jax.Array) -> jax.Array:
    """Gather rows (lane axis) by index -> (F, len(idx)). Out-of-range
    idx (pad slots) fill with bin 0; callers zero their gh so those rows
    contribute nothing."""
    return jnp.take(bins_fm, idx, axis=1, mode="fill", fill_value=0)


def gather_gh8(gh8: jax.Array, idx: jax.Array) -> jax.Array:
    return jnp.take(gh8, idx, axis=1, mode="fill", fill_value=0.0)


def hist_capacities(n_rows: int, min_cap: int = HIST_BLK) -> tuple:
    """Static ladder of gather-buffer sizes: N/2, N/4, ... >= min_cap,
    each rounded up to a HIST_BLK multiple. The smaller child always
    fits in N/2; deep (small) leaves use the small buffers so histogram
    cost tracks leaf size."""

    def _round(c: int) -> int:
        return ((c + HIST_BLK - 1) // HIST_BLK) * HIST_BLK

    caps = []
    c = n_rows // 2
    while c >= min_cap:
        caps.append(_round(c))
        c //= 2
    if not caps:
        caps.append(_round(max(n_rows // 2, 1)))
    return tuple(caps)


def root_sums(gh8: jax.Array, axis_name: Optional[str] = None) -> jax.Array:
    """(sum_grad, sum_hess, count) over all in-bag rows. Globally reduced
    over the data mesh axis when present (reference
    data_parallel_tree_learner.cpp:169-221 root allreduce)."""
    s8 = jnp.sum(gh8, axis=1)
    s = jnp.stack([s8[0] + s8[1], s8[2] + s8[3], s8[4]])
    if axis_name is not None:
        s = lax.psum(s, axis_name)
    return s
