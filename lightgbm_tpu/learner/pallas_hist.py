"""Pallas TPU histogram-construction kernel.

The histogram is the reference's single hottest loop
(src/io/dense_bin.hpp:99-174 ConstructHistogram on CPU, shared-memory
atomics in src/treelearner/cuda/cuda_histogram_constructor.cu on CUDA).
A TPU has no vector scatter, so the kernel reformulates scatter-add as
a one-hot contraction — but unlike a plain XLA einsum, the one-hot
matrix only ever exists one (B, HIST_BLK) tile at a time in VMEM,
never in HBM. Per grid step (one row block):

    bins tile (F, blk) int32, gh tile (8, blk) f32    -> VMEM
    for each feature f (static unroll):
        ohT = (bins[f:f+1, :] == iota_B^T)             (B, blk) bf16
        out[:, f*B:(f+1)*B] += gh . ohT^T              MXU NT dot_general

Inputs are feature-major (rows on the LANE axis) because TPU memory
tiles pad the minor-most dim to 128 lanes — a row-major (N, 28) matrix
would physically occupy 4.5x its size in HBM. The one-hot is built
TRANSPOSED in that same layout and contracted with an NT dot_general;
an earlier version transposed the bins tile per block, which cost
~2 ms/pass and serialized against the int8 MXU stream (1.75x on the
quantized path). The channel axis is padded 3 -> 8 (bf16x2-split
grad/hess + count, see histogram.build_gh8) to match the f32 sublane
tile; accumulation rides the grid-constant output block.

The MXU operand is one of three. bf16 with the compare one-hot above;
s8 with the byte-SWAR one-hot (_swar_onehot, 1.65x the compare's rate)
for quantized levels within +/-127, summed in an s32 block; and, for
the int16 program's 256-level channels on every pass, the same s8
operands with each level split into two limbs (_limb_lhs: five s8 rows
a slot, the count channel, in {0, 1}, having no high limb), whose s32
sums are recombined exactly per row block and added into the f32
block: the bf16 path's histograms to the bit (_nat_kernel has the
argument). The bf16 operand is left to the bf16x2 program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .histogram import CH, HIST_BLK, NAT_CH, VMEM_LIMIT_BYTES


# features whose one-hot matmuls are unrolled in one piece of kernel
# code. Up to this many columns the whole feature loop is unrolled (the
# kernels every chip record before the 137-column ranking cell was
# measured with); past it the kernel loops over equal groups of at most
# this many, so Mosaic's compile time and program size stop growing
# with the column count (137 columns unrolled: 63-75 s for the 8-slot
# kernel alone and over 11 minutes for the 32-slot one on the sandbox's
# compiler, against a few seconds per kernel at 28).
FEATURE_UNROLL = 32


# lanes of a column in the output block where two columns share one
# one-hot tile (columns_per_matmul): half an MXU tile's 128 output lanes
PAIR_STRIDE = 64


def columns_per_matmul(B: int) -> int:
    """Columns whose one-hots one matmul of the feature loop contracts:
    two where each fills at most half of the MXU's 128 output lanes and
    more than a quarter (32 < B <= 64: `max_bin=63`, what the reference
    recommends on an accelerator), else one. Read from the bin count
    alone, where the kernel is built."""
    return 2 if PAIR_STRIDE // 2 < B <= PAIR_STRIDE else 1


def column_stride(B: int) -> int:
    """Lanes from one column to the next in a kernel's output block: B,
    or PAIR_STRIDE under the pair, whose matmul lands on a whole
    128-lane slab (the lanes past B of a column stay zero and
    hist_out_flat drops them)."""
    return PAIR_STRIDE if columns_per_matmul(B) == 2 else B


def feature_groups(F: int, B: int) -> tuple:
    """(groups, features per group) of the kernels' feature loop at B
    bins; a group is whole matmuls (columns_per_matmul), and the last
    group may run past F (its extra columns are cut off outside)."""
    G = -(-F // FEATURE_UNROLL)
    per = columns_per_matmul(B)
    return G, -(-F // (G * per)) * per


def hist_out_block(rows: int, F: int, B: int, whole: bool = False) -> tuple:
    """Shape of a histogram kernel's resident output block (the whole
    table's, or one feature block's): (rows, columns * stride) while the
    feature loop is unrolled whole (`whole`: at any F, the single-leaf
    kernel), else (groups, rows, per_group * stride), indexed by the
    loop's group. The stride is column_stride's and the columns whole
    matmuls: (rows, F*B) off the pair."""
    G, Fg = feature_groups(F, B)
    if whole:
        per = columns_per_matmul(B)
        G, Fg = 1, -(-F // per) * per
    width = Fg * column_stride(B)
    return (rows, width) if G == 1 else (G, rows, width)


def feature_blocks(F: int, feat_block: int) -> int:
    """Feature blocks of a kernel call: 1 while the whole table is one
    (F, HIST_BLK) bins tile (`feat_block` 0 or >= F), else the grid's
    leading dimension (histogram.hist_plan sizes the block: whole
    FEATURE_UNROLL groups, so a block's columns are its loop groups)."""
    if not feat_block or feat_block >= F:
        return 1
    assert feat_block % FEATURE_UNROLL == 0, feat_block
    return -(-F // feat_block)


def hist_out_flat(out: jax.Array, F: int, B: int) -> jax.Array:
    """A kernel's output block as (rows, F*B): without the columns past
    F of the last group or matmul and, at a stride past B, without each
    column's pad lanes."""
    st = column_stride(B)
    if out.ndim == 3:
        G, rows, width = out.shape
        out = out.transpose(1, 0, 2).reshape(rows, G * width)
    rows, width = out.shape
    if st != B:
        out = out.reshape(rows, width // st, st)[:, :F, :B]
        return out.reshape(rows, F * B)
    return out if width == F * B else out[:, :F * B]


def _accum_features(bins_ref, out_ref, contribution, *, F: int, B: int,
                    last=None):
    """out[.., m*w:(m+1)*w] += contribution(bins rows of matmul m) for
    every matmul of the bins tile's columns (one column and w = B
    lanes, or a pair and 128: columns_per_matmul): the kernels' one loop
    over the columns, unrolled whole into a 2-D block (hist_out_block;
    the single-leaf kernels always), else by groups into a 3-D one.
    `last` is the tile's last real row where that is not F - 1 (the
    ragged last feature block of a blocked call, _block_last)."""
    per = columns_per_matmul(B)
    w = per * column_stride(B)
    G, Fg = feature_groups(F, B)
    if len(out_ref.shape) == 2:
        for m in range(-(-F // per)):
            # an odd F pairs its last column with itself, into lanes
            # that hist_out_flat drops
            cols = [min(f, F - 1) for f in range(m * per, (m + 1) * per)]
            out_ref[:, m * w : (m + 1) * w] += contribution(
                *(bins_ref[f : f + 1, :] for f in cols))
        return
    if last is None:
        last = F - 1

    def group(j, carry):
        for m in range(Fg // per):
            # past the last column the group re-reads it into columns
            # that hist_out_flat drops
            rows = [jnp.minimum(j * Fg + f, last)
                    for f in range(m * per, (m + 1) * per)]
            out_ref[j, :, m * w : (m + 1) * w] += contribution(
                *(bins_ref[pl.ds(row, 1), :] for row in rows))
        return carry

    lax.fori_loop(0, G, group, 0)


def _block_last(F: int, feat_block: int):
    """Last real row of this grid step's bins tile in a blocked call
    (grid = (feature blocks, row blocks)): the last block's tile runs
    past the table's F columns, and what lies there is not data."""
    return jnp.minimum(F - pl.program_id(0) * feat_block, feat_block) - 1


def _accum_hist_nt(bins_ref, lhs, out_ref, *, F, B, blk, dt, acc_t,
                   iota_bT=None, last=None):
    """Shared accumulate loop: one NT matmul per column, or per pair of
    columns (columns_per_matmul), the one-hot built TRANSPOSED (rows,
    blk) directly from the bins tile's native (F, blk) layout — the
    former per-block (blk, F) int32 transpose cost ~2 ms/pass at 1M rows
    and serialized against the int8 MXU stream.

    The pair: at 33..64 bins a column's product (M, B) fills at most
    half of an MXU tile's 128 output lanes and costs a whole tile, so
    two columns share one (128, blk) one-hot tile, the second on
    SUBLANES 64..127 (_tile_key: one select, one compare), and one
    matmul fills all 128 lanes of a lane-aligned slab of the output
    block. An earlier grouping (PR 6) concatenated 255-bin one-hots on
    the LANE axis, where every column already fills 255 of 256 lanes:
    it had a relayout to pay and no empty lanes to win, and was slower.
    Here a concat of two (64, blk) one-hots on the sublane axis was
    measured too: 3.9% slower per tree than the select (PERF.md section
    6, PR 31). What the pair gives: PERF_LEDGER.jsonl, PR 31,
    epsilon-wide.train.

    `iota_bT` passes the (rows, blk) row-iota from a VMEM scratch
    buffer written once at grid step 0 (see _oh_iota_init) so the
    constant is block-resident instead of re-materialized every step x
    feature."""
    if iota_bT is None:
        iota_bT = lax.broadcasted_iota(
            jnp.int32, _oh_iota_shape(B, blk, False), 0)
    # once, not per matmul
    lower = _lower_rows(iota_bT.shape, columns_per_matmul(B))

    def contribution(*bins_rows):
        ohT = (_tile_key(bins_rows, lower) == iota_bT).astype(dt)
        return lax.dot_general(
            lhs, ohT, (((1,), (1,)), ((), ())),
            preferred_element_type=acc_t,
        )

    _accum_features(bins_ref, out_ref, contribution, F=F, B=B, last=last)


def _lower_rows(shape: tuple, columns: int):
    """Mask of the rows of a pair's one-hot tile (or of its packed byte
    iota), of `shape`, that are the first column's: the lower half.
    None where the tile is one column's."""
    if columns == 1:
        return None
    return lax.broadcasted_iota(jnp.int32, shape, 0) < shape[0] // 2


def _tile_key(bins_rows, lower, rep: int = 1):
    """What each row of one matmul's one-hot tile compares its iota
    with, times `rep`: the column's (1, blk) bins, or under the pair
    the first column's over the `lower` rows (_lower_rows) and the
    second's + PAIR_STRIDE over the upper (bins < B <= PAIR_STRIDE: the
    pad rows of either half match nothing)."""
    keys = [row if k == 0 else row + k * PAIR_STRIDE
            for k, row in enumerate(bins_rows)]
    if rep != 1:
        keys = [key * rep for key in keys]
    return keys[0] if len(keys) == 1 else jnp.where(lower, *keys)


def _oh_iota_shape(B: int, blk: int, int8: bool) -> tuple:
    """Shape of the persistent one-hot iota scratch (one VMEM buffer
    per kernel invocation, written at grid step 0 and reused by every
    later step): the compare path persists the (rows, blk) row iota of
    one matmul's one-hot tile (B rows, or the pair's 128), the
    byte-SWAR path the packed (ceil(rows/4), blk) byte iota."""
    rows = columns_per_matmul(B) * column_stride(B)
    if int8:
        return (-(-rows // 4), blk)
    return (rows, blk)


def _oh_iota_init(shape: tuple, int8: bool):
    """Value for the persistent iota scratch (see _oh_iota_shape)."""
    bg = lax.broadcasted_iota(jnp.int32, shape, 0)
    if int8:
        return bg * (4 * _SWAR_REP) + 0x03020100
    return bg


def _nat_kernel(bins_ref, gh_ref, slot_ref, out_ref, iota_ref, *hi_ref,
                F: int, B: int, blk: int, S: int, nat_ch: int,
                int8: bool = False, oh_shift: int = 0,
                feat_block: int = 0, limbs: bool = False):
    """Slot-packed natural-order histogram: rows carry a slot id; the
    weight matrix W packs (slot x channel) onto the MXU's M axis —
    W[(s, c), r] = gh[c, r] * (slot[r] == s) — so one (S*nat_ch, blk) @
    (blk, B) matmul per feature accumulates ALL slots' histograms. With
    S*nat_ch ~ 125 of the MXU's 128 M rows useful, up to 25 slots (42
    under quantized training's 3 integer channels) cost the wall time
    the single-leaf kernel spends on 8 rows.

    The output block is grid-constant (index_map (0, 0)) so it stays
    VMEM-resident across grid steps — accumulate into it directly
    instead of a scratch copy (a separate scratch doubled the scoped
    VMEM footprint and capped S at ~25 of the 16 MB budget).

    With `int8` (quantized training, levels within +/-127): W and the
    one-hot are s8, the MXU accumulates s32 — twice the bf16 rate on
    v5e and the block sums are exact integers (the TPU analog of the
    reference's int16/int32 histogram buffers, bin.h:63-81). Worst-case
    block sum 127 * blk << 2^31; cross-block accumulation rides the s32
    output block.

    With `limbs` (the int16 program's 256-level channels, which s8
    cannot hold) the MXU is the int8 one too: each level is split into
    two s8 limbs, v = 16 * hi + lo (_limb_lhs), stacked on the M axis,
    and contracted with the same SWAR one-hot; each row block's two s32
    limb sums are recombined exactly in s32 and added into the f32
    output block (_limb_sum). W has five rows a slot, not six: the
    count channel is the in-bag mask, in {0, 1} on every path that
    reaches a kernel (bagging, GOSS and by-query masks times the
    Dataset's padding `valid`, a mesh shard's rows included:
    rounds.py builds it as build_gh8_quant(grad * mask, hess * mask,
    mask)), so its high limb is zero on every row and is neither built
    nor multiplied; its level sum is its low limb's. `hi_ref` is the
    VMEM scratch that interleaves the high limbs' sums back into (slot,
    channel) rows (_limb_hi_shape). That is the bf16 path's arithmetic
    to the bit: the limbs are taken of the levels as bf16 holds them
    (_bf16_levels), and there a block's product is an exact integer as
    well (|v| <= 256, 2048 rows: below 2^24 in the MXU's f32
    accumulator) and enters the f32 block in the same order.
    Cross-block sums stay in f32 (a cell of the hessian channel passes
    2^31 long before a pass ends).

    With `feat_block` (a table too wide for one bins tile,
    histogram.hist_plan) the grid is (feature blocks, row blocks): the
    bins tile and the output block are ONE feature block's, resident
    while the rows sweep and written back once per block, so the VMEM
    need is a function of the block and not of F; gh and the slot
    vector are re-read per block (36 B a row against 4 B x block
    columns of bins) and W is rebuilt from them."""
    hi_ref = hi_ref[0] if limbs else None
    last = None
    if feat_block:
        i = pl.program_id(1)
        last = _block_last(F, feat_block)
        F = feat_block  # the tile's columns from here on
    else:
        i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        iota_ref[...] = _oh_iota_init(iota_ref.shape, int8 or limbs)
        if limbs:
            hi_ref[...] = jnp.zeros_like(hi_ref)

    iota = iota_ref[...]  # VMEM-persistent one-hot iota (step-invariant)
    slot = slot_ref[0, :]  # (blk,) int32
    gh = gh_ref[...]  # (CH, blk) f32; rows 0..nat_ch-1 are live
    iota_s = lax.broadcasted_iota(jnp.int32, (S, blk), 0)
    if int8 or limbs:
        sl32 = (slot[None, :] == iota_s).astype(jnp.int32)  # (S, blk)
        _accum_s8(bins_ref, out_ref, sl32, gh[:nat_ch, :], B=B, blk=blk,
                  oh_shift=oh_shift, iota=iota, limbs=limbs, F=F,
                  last=last, hi_ref=hi_ref)
        return
    sl = (slot[None, :] == iota_s).astype(jnp.bfloat16)  # (S, blk)
    g5 = gh[:nat_ch, :].astype(jnp.bfloat16)  # (nat_ch, blk)
    W = (sl[:, None, :] * g5[None, :, :]).reshape(S * nat_ch, blk)

    _accum_hist_nt(bins_ref, W, out_ref, F=F, B=B, blk=blk,
                   dt=jnp.bfloat16, acc_t=jnp.float32, iota_bT=iota,
                   last=last)


def _accum_s8(bins_ref, out_ref, sel32, gh, *, F: int, B: int, blk: int,
              oh_shift: int, iota, limbs: bool, last=None, hi_ref=None):
    """The int8 MXU's feature loop: W[(s, c), r] = level(gh[c, r]) *
    sel32[s, r] as s8 (or as its limbs, five rows a slot: _limb_lhs),
    contracted with the byte-SWAR one-hot (1.65x the compare+cast rate
    on the VPU-bound end) into s32. The int8 path adds the s32 products
    into an s32 block, scaled by the one-hot's byte value
    (_swar_divisor); the limb path recombines them through `hi_ref`
    into an f32 block, unscaled (_limb_sum). Mosaic has no elementwise
    i8 multiply (only the MXU dot is int8-legal): the levels are masked
    in i32, then narrowed."""
    S, ch = sel32.shape[0], gh.shape[0]
    if limbs:
        W = _limb_lhs(sel32, _bf16_levels(gh))
    else:
        g32 = gh.astype(jnp.int32)
        W = (sel32[:, None, :] * g32[None, :, :]).reshape(
            S * ch, blk).astype(jnp.int8)

    def contribution(*bins_rows):
        oh = _swar_onehot(bins_rows, B, blk, oh_shift, iota_p=iota)
        res = lax.dot_general(
            W, oh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return _limb_sum(res, hi_ref, S, oh_shift) if limbs else res

    _accum_features(bins_ref, out_ref, contribution, F=F, B=B, last=last)


# a level v of the int16 program's channels (grad in [-129, 129], hess
# in [0, 256], count in {0, 1}: _bf16_levels) as two s8 limbs: v =
# LIMB_BASE * hi + lo with lo = v & 15 in [0, 15] and hi = v >> 4
# (floor) in [-9, 16]. The count's hi is 0 on every row, so W carries
# LIMB_ROWS s8 rows a slot: the three low limbs and the high limbs of
# grad and hess (_limb_lhs)
LIMB_SHIFT = 4
LIMB_BASE = 1 << LIMB_SHIFT
LIMB_ROWS = 5
# lanes of the limb path's interleave scratch (_limb_hi_shape): Mosaic
# strides sublanes only in a memref exactly one vreg of lanes wide
_LIMB_LANES = 128


def _bf16_levels(gh):
    """(ch, blk) f32 integer levels -> i32, as the bf16 operand holds
    them: the limb path's W is the bf16 path's to the bit. The
    quantizer's stochastic rounding (quantize.discretize_gradients_int)
    lands one level past its range about 1.5e-5 of the time at the top
    (x + u rounds up in f32): grad +-129, which bf16 holds, and hess
    257, which it holds as 256."""
    return gh.astype(jnp.bfloat16).astype(jnp.float32).astype(jnp.int32)


def _limb_lhs(sel32, g32):
    """(S, blk) 0/1 slot masks and (3, blk) i32 levels (grad, hess,
    count) -> the (LIMB_ROWS S, blk) s8 W of the limb path: the low
    limbs' rows (s, c), then the high limbs of grad for every slot, then
    those of hess. The count, last, is in {0, 1} (_nat_kernel) and has
    no high limb. The masks select whole levels, so masking the limbs
    is limbing the masked level."""
    S, blk = sel32.shape
    ch = g32.shape[0]
    lo = sel32[:, None, :] * (g32 & (LIMB_BASE - 1))[None, :, :]
    hi = (g32[:ch - 1] >> LIMB_SHIFT)[:, None, :] * sel32[None, :, :]
    W = jnp.concatenate([lo.reshape(S * ch, blk),
                         hi.reshape((ch - 1) * S, blk)])
    return W.astype(jnp.int8)


def _limb_hi_shape(S: int, B: int) -> tuple:
    """The limb path's interleave scratch: one matmul's high limb sums
    in (slot, channel) rows, by 128-lane chunks of its output lanes
    (the one-hot tile's rows, _oh_iota_shape). The count's rows are
    zeroed at grid step 0 and never written."""
    w = columns_per_matmul(B) * column_stride(B)
    return (-(-w // _LIMB_LANES), 3 * S, _LIMB_LANES)


def _limb_sum(res, hi_ref, S: int, oh_shift: int):
    """(LIMB_ROWS S, w) s32 limb sums of one row block (_limb_lhs rows,
    scaled by the SWAR one-hot's byte) -> the block's (3 S, w) f32 level
    sums in (slot, channel) rows, exactly. The high rows of grad and
    hess are stored at sublane stride 3 into `hi_ref` (_limb_hi_shape),
    whose count rows stay zero, and read back whole: (S, 3, w) high sums
    beside the low rows. A block's level sum is at most 256 x 2048 =
    2^19 in magnitude (_bf16_levels), so 16 hi + lo = that x the byte
    (2^7 at oh_shift 0) is an exact s32 and an exact f32, and the
    power-of-two divide is exact."""
    M, w = 3 * S, res.shape[1]
    for q in range(hi_ref.shape[0]):
        a, b = q * _LIMB_LANES, min(w, (q + 1) * _LIMB_LANES)
        for c in range(2):
            hi_ref[q, pl.ds(c, S, stride=3), :b - a] = (
                res[M + c * S : M + (c + 1) * S, a:b])
    hi = jnp.concatenate([hi_ref[q] for q in range(hi_ref.shape[0])],
                         axis=1)
    c = hi[:, :w] * LIMB_BASE + res[:M]
    return c.astype(jnp.float32) * (1.0 / _swar_divisor(oh_shift))


def _swar_divisor(oh_shift: int) -> float:
    """SWAR one-hot byte value: -128 unshifted (0x80 as s8), else
    positive 128 >> shift."""
    return -128.0 if oh_shift == 0 else float(128 >> oh_shift)


# every kernel states its scoped-VMEM limit instead of inheriting the
# compiler's default (which moves between toolchains): the slot caps in
# histogram._round_caps are compile limits established AT this value
_VMEM = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)
# the histogram grid walks row blocks accumulating into grid-constant
# output blocks: steps are NOT parallelizable, tell Mosaic so instead
# of letting it infer (the chip-resident schedule contract, ISSUE 12)
_ARBITRARY = pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                  vmem_limit_bytes=VMEM_LIMIT_BYTES)
# a blocked call's grid (feature blocks, row blocks): a feature block's
# output accumulates over its row steps, and the one iota scratch is
# rewritten at each block's first
_ARBITRARY_2D = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT_BYTES)


@functools.partial(
    jax.jit,
    static_argnames=("num_slots", "num_bins", "blk", "interpret", "nat_ch",
                     "int8", "oh_shift", "feat_block", "limbs"),
)
def hist_nat_tpu(
    bins_fm: jax.Array,  # (F, N) int32, natural row order
    gh8: jax.Array,  # (CH, N) f32
    slot: jax.Array,  # (N,) int32 in [0, num_slots]
    num_slots: int,
    num_bins: int,
    blk: int = HIST_BLK,
    interpret: bool = False,
    nat_ch: int = NAT_CH,
    int8: bool = False,
    oh_shift: int = 0,
    feat_block: int = 0,  # columns per feature block; 0: the whole table
    limbs: bool = False,  # 256-level integer channels on the int8 MXU
) -> jax.Array:
    """(S*nat_ch, F*B) f32 packed per-slot channel histograms (exact
    integer sums computed in s32 when int8, per row block in s32 limbs
    when `limbs`: see _nat_kernel). One pass over the bin matrix at
    any F: past one bins tile (`feat_block`, see _nat_kernel) the
    feature axis is the grid's leading dimension."""
    assert not (int8 and limbs)
    F, N = bins_fm.shape
    assert N % blk == 0, (N, blk)
    assert gh8.shape == (CH, N), gh8.shape
    B = num_bins
    S = num_slots
    nb = N // blk
    nfb = feature_blocks(F, feat_block)
    if nfb == 1:
        feat_block = 0
        block = out_shape = hist_out_block(S * nat_ch, F, B)
        grid = (nb,)

        def rows(height):
            return pl.BlockSpec((height, blk), lambda i: (0, i),
                                memory_space=pltpu.VMEM)

        bins_spec = rows(F)
        out_spec = pl.BlockSpec(
            block, lambda i: (0,) * len(block), memory_space=pltpu.VMEM)
    else:
        # always by groups, also where a block is one group
        groups = feat_block // FEATURE_UNROLL
        block = (groups, S * nat_ch, FEATURE_UNROLL * column_stride(B))
        out_shape = (nfb * groups,) + block[1:]
        grid = (nfb, nb)

        def rows(height):
            return pl.BlockSpec((height, blk), lambda j, i: (0, i),
                                memory_space=pltpu.VMEM)

        bins_spec = pl.BlockSpec((feat_block, blk), lambda j, i: (j, i),
                                 memory_space=pltpu.VMEM)
        out_spec = pl.BlockSpec(block, lambda j, i: (j, 0, 0),
                                memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_nat_kernel, F=F, B=B, blk=blk, S=S, nat_ch=nat_ch,
                          int8=int8, oh_shift=oh_shift,
                          feat_block=feat_block, limbs=limbs),
        grid=grid,
        in_specs=[bins_spec, rows(CH), rows(1)],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(
            out_shape, jnp.int32 if int8 else jnp.float32
        ),
        scratch_shapes=[
            pltpu.VMEM(_oh_iota_shape(B, blk, int8 or limbs), jnp.int32),
        ] + ([pltpu.VMEM(_limb_hi_shape(S, B), jnp.int32)] if limbs else []),
        compiler_params=_ARBITRARY if nfb == 1 else _ARBITRARY_2D,
        interpret=interpret,
    )(bins_fm, gh8, slot.reshape(1, N))
    out = hist_out_flat(out, F, B)
    if not int8:
        return out
    return out.astype(jnp.float32) * (1.0 / _swar_divisor(oh_shift))


_SWAR_REP = 0x01010101
_SWAR_M7 = 0x7F7F7F7F
_SWAR_M8 = -2139062144  # 0x80808080 as i32


def _swar_onehot(bins_rows: tuple, B: int, blk: int, oh_shift: int,
                 iota_p=None):
    """(1, blk) i32 bin values -> (B, blk) s8 one-hot, 4 bins per i32;
    two such rows (the pair, columns_per_matmul) -> their (128, blk)
    tile, the second column's one-hot on rows 64..127.

    The straight `bins == iota` compare + s8 cast costs ~4.4 ms per
    1M x 28 x 256 pass — the VPU floor of every histogram pass (i32
    vectors hold 1024 elements; s8/i16/bf16 compares don't lower in
    this Mosaic). This packs FOUR bin rows into each i32 lane (byte j
    of packed row bg is bin 4bg+j), replicates the row's bin value
    into all four bytes, and marks equal bytes with a carry-free SWAR
    zero-byte test:

        t  = (bins * 0x01010101) ^ iota_packed
        oh = ~(((t & 0x7F7F7F7F) + 0x7F7F7F7F) | t) & 0x80808080

    (the textbook `(t - REP) & ~t & M8` test is WRONG here: a hit at
    even byte j borrows into byte j+1, falsely marking bins^iota == 1,
    i.e. every even-bin hit would also count its odd neighbor). The
    i32 result bitcasts to (B, blk) s8 — pltpu.bitcast unpacks bytes
    onto sublanes exactly in bin order — with value -0x80 >> oh_shift
    at hits; callers divide the s32 sums by -(128 >> oh_shift).
    Measured 1.65x faster than compare+cast (2.45 vs 4.05 ms/pass).

    oh_shift trades VPU ops for s32 headroom: 0 keeps bytes at +/-128
    (fastest, sums scaled 128x), 4 shifts to +/-8 (two extra ops,
    16x more accumulation headroom).

    `iota_p` passes the packed byte iota from a VMEM scratch written at
    grid step 0 (_oh_iota_init) instead of re-materializing the
    constant every step x feature; a scratch sized for a pair serves a
    single row by its first rows."""
    rows = B if len(bins_rows) == 1 else len(bins_rows) * PAIR_STRIDE
    B4 = -(-rows // 4)  # pad to a byte multiple; extra rows sliced off
    if iota_p is None:
        iota_p = _oh_iota_init((B4, blk), True)
    elif iota_p.shape[0] != B4:
        iota_p = iota_p[:B4]
    lower = _lower_rows(iota_p.shape, len(bins_rows))
    t = _tile_key(bins_rows, lower, _SWAR_REP) ^ iota_p
    z = ~(((t & _SWAR_M7) + _SWAR_M7) | t) & _SWAR_M8
    if oh_shift:
        # arithmetic >> smears the top byte's sign bit; the mask keeps
        # only the intended per-byte marker bit
        z = (z >> oh_shift) & (_SWAR_REP * (0x80 >> oh_shift))
    oh = pltpu.bitcast(z, jnp.int8)
    return oh if 4 * B4 == rows else oh[:rows, :]


def _round_kernel(
    params_ref, coh_ref, cat_ref, bins_ref,  # inputs every round has
    *refs,  # gh, pleaf | hist out, pleaf out | iota scratch; with
    # route_only: pleaf | pleaf out | iota scratch
    F: int, B: int, blk: int, S: int, nat_ch: int, int8: bool,
    oh_shift: int, efb: bool, has_cat: bool, route_only: bool = False,
    with_slot: bool = False, limbs: bool = False,
):
    """Fused round step: partition decision + slot-packed histograms
    in ONE data pass. With `route_only` (the round that spends the last
    of a tree's leaf budget: no child of it can ever split) the pass
    stops after the partition decision: no gradient input, no histogram
    output block, no one-hot, no MXU contraction. `with_slot` (routing
    only) adds each row's histogram slot as a second blocked output:
    what a round at width hands to the blocked slot-keyed pass
    (hist_nat_tpu) that follows it over the whole table, this pass
    having seen the round's split columns alone.

    Compile-time contracts (no host callbacks, no f64, jaxpr size
    budget) are enforced by the `hist_round_fused` entry of
    analysis/jaxpr_audit.py — the trace is audited abstractly on CPU,
    so kernel drift fails tier-1 before it ever reaches hardware.

    The rounds grower's per-round extras — the (G, N) split-column
    select (2.2 ms), the (N, S) membership matmul, the row->leaf
    update and the histogram-slot assignment — all touch the same
    bins/pleaf data this kernel already streams. Fusing them in makes
    them free:

    - `fb[s, r]` (each row's split-column bin) is a tiny in-kernel
      (S, F) @ (F, blk) f32 MXU contraction against the per-slot
      column one-hot — no dynamic sublane loads, exact to 2^24;
    - membership/threshold/default-direction/EFB-decode are (S, blk)
      vector ops against per-slot scalar columns of `params_ref`;
    - the new row->leaf vector is written as a second blocked output;
    - the smaller-child side picks each row's histogram slot, and the
      slot-packed W build + one-hot contraction proceed as in
      _nat_kernel (SWAR one-hot on the int8 and the limb paths).

    params columns (S, 16) i32: 0 sel_leaf, 1 device column, 2
    threshold bin, 3 default_left, 4 NaN bin (-1 none), 5 left-smaller,
    6 new leaf id, 7 efb off_lo, 8 efb mfb (-1 direct), 9 efb width.
    Pad slots carry sel_leaf = L (matched only by invalid rows, whose
    gh channels are zero and whose new id is L: harmless by
    construction, same argument as the XLA path in rounds.py)."""
    i = pl.program_id(0)
    slot_out_ref = None
    if route_only and with_slot:
        pleaf_ref, pl_out_ref, slot_out_ref, *scratch = refs
        gh_ref = out_ref = None
    elif route_only:
        pleaf_ref, pl_out_ref, *scratch = refs
        gh_ref = out_ref = None
    else:
        gh_ref, pleaf_ref, out_ref, pl_out_ref, *scratch = refs
    # scratch layout (_round_iotas): the compare iota of the bf16
    # bins one-hots, then the byte-SWAR iota of the int8 bins one-hots
    # and of the cat one-hot, each only where the mode builds such a
    # one-hot. All written once at step 0, VMEM-resident after. Last,
    # on the limb path, its interleave scratch (_limb_hi_shape).
    want_cmp, want_swar = _round_iotas(int8 or limbs, has_cat, route_only)
    iota_cmp_ref = scratch.pop(0) if want_cmp else None
    iota_swar_ref = scratch.pop(0) if want_swar else None
    hi_ref = scratch.pop(0) if limbs and not route_only else None

    @pl.when(i == 0)
    def _init():
        if out_ref is not None:
            out_ref[...] = jnp.zeros_like(out_ref)
        if iota_cmp_ref is not None:
            iota_cmp_ref[...] = _oh_iota_init(iota_cmp_ref.shape, False)
        if iota_swar_ref is not None:
            iota_swar_ref[...] = _oh_iota_init(iota_swar_ref.shape, True)
        if hi_ref is not None:
            hi_ref[...] = jnp.zeros_like(hi_ref)

    iota_swar = None if iota_swar_ref is None else iota_swar_ref[...]
    pleaf = pleaf_ref[...]  # (1, blk) i32
    sel = params_ref[:, 0:1]  # (S, 1) i32
    thr = params_ref[:, 2:3].astype(jnp.float32)
    dl = params_ref[:, 3:4] != 0
    nanb = params_ref[:, 4:5].astype(jnp.float32)
    new_id = params_ref[:, 6:7]

    memb = pleaf == sel  # (S, blk)
    fb = lax.dot_general(
        coh_ref[...], bins_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    )  # (S, blk) — slot s's split-column bin per row
    if efb:
        lo = params_ref[:, 7:8].astype(jnp.float32)
        mfb = params_ref[:, 8:9].astype(jnp.float32)
        wid = params_ref[:, 9:10].astype(jnp.float32)
        t = fb - lo
        in_r = (t >= 0.0) & (t < wid)
        dec = jnp.where(in_r, t + (t >= mfb).astype(jnp.float32), mfb)
        fb = jnp.where(mfb >= 0.0, dec, fb)
    gl = (fb <= thr) | (dl & (fb == nanb))  # (S, blk)
    if has_cat:
        # categorical slots: go left iff the row's bin is in the
        # slot's category set. The row's OWN split-column bin (merge
        # over disjoint memberships) gets a single-feature one-hot and
        # one (S, B) @ (B, blk) contraction against the per-slot masks
        # — the (L*B,) flat gather this replaces costs ~10 ms at 1M
        # rows (an element gather per row; the chip has no vector gather).
        is_cat_s = params_ref[:, 10:11] != 0  # (S, 1)
        fb_own = jnp.sum(jnp.where(memb, fb, 0.0), axis=0,
                         keepdims=True)  # (1, blk) f32 integer-valued
        ohfb = _swar_onehot((fb_own.astype(jnp.int32),), B, blk, 7,
                            iota_p=iota_swar)  # 0/1 s8
        hits = lax.dot_general(
            cat_ref[...], ohfb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (S, blk): mask[s, fb_own[r]]
        # mask algebra, not jnp.where: a select between two bool
        # vectors makes Mosaic (libtpu 0.0.34) truncate i8 -> i1 on a
        # (S, blk) vector, which it cannot lower
        gl = (is_cat_s & (hits > 0)) | (~is_cat_s & gl)

    # new per-row leaf ids: memberships are disjoint, so summing the
    # masked deltas over the slot axis applies at most one update
    delta = jnp.where(memb & ~gl, new_id - pleaf, 0)
    pl_out_ref[...] = pleaf + jnp.sum(delta, axis=0, keepdims=True)
    if route_only and not with_slot:
        return

    if not route_only:
        gh = gh_ref[...]  # (CH, blk) f32
    small = params_ref[:, 5:6] != 0
    side = memb & (gl == small)  # rows feeding slot s's histogram
    if route_only:
        # at most one slot claims a row; a row none claims gets S, the
        # slot-keyed kernels' trash id
        iota_s = lax.broadcasted_iota(jnp.int32, (S, blk), 0)
        slot_out_ref[...] = S + jnp.sum(
            jnp.where(side, iota_s - S, 0), axis=0, keepdims=True)
        return
    if int8 or limbs:
        side_i = side.astype(jnp.int32)
        _accum_s8(bins_ref, out_ref, side_i, gh[:nat_ch, :], F=F, B=B,
                  blk=blk,
                  oh_shift=oh_shift, iota=iota_swar, limbs=limbs,
                  hi_ref=hi_ref)
    else:
        sideb = side.astype(jnp.bfloat16)
        gb = gh[:nat_ch, :].astype(jnp.bfloat16)
        W = (sideb[:, None, :] * gb[None, :, :]).reshape(S * nat_ch, blk)
        _accum_hist_nt(bins_ref, W, out_ref, F=F, B=B, blk=blk,
                       dt=jnp.bfloat16, acc_t=jnp.float32,
                       iota_bT=iota_cmp_ref[...])


def _round_iotas(swar: bool, has_cat: bool, route_only: bool) -> tuple:
    """(compare iota?, byte-SWAR iota?) of a round kernel's scratch:
    the bf16 histogram one-hots compare against the first, the byte-SWAR
    ones (`swar`: the int8 and the limb paths) and the categorical
    own-bin one-hot unpack the second."""
    return (not route_only and not swar,
            has_cat or (swar and not route_only))


def _round_call(bins_fm, gh8, pleaf, params, col_onehot, cat_mask, *,
                num_slots: int, num_bins: int, nat_ch: int, int8: bool,
                oh_shift: int, efb: bool, blk: int, interpret: bool,
                with_slot: bool = False, limbs: bool = False):
    """The one pallas_call behind hist_round_tpu and route_round_tpu:
    `gh8 is None` asks for the routing pass alone (_round_kernel's
    route_only), which has neither the gradient input nor the
    grid-constant histogram block; `with_slot` for its rows' histogram
    slots beside their new leaves."""
    F, N = bins_fm.shape
    assert N % blk == 0, (N, blk)
    S = num_slots
    route_only = gh8 is None
    has_cat = cat_mask is not None
    if cat_mask is None:
        cat_mask = jnp.zeros((S, num_bins), jnp.int8)
    # persistent one-hot iota scratch (see _round_kernel): part of the
    # kernel's explicit VMEM block schedule, accounted against the
    # scoped budget by histogram._round_caps callers
    scratch = [
        pltpu.VMEM(_oh_iota_shape(num_bins, blk, swar), jnp.int32)
        for swar, want in zip((False, True),
                              _round_iotas(int8 or limbs, has_cat,
                                           route_only))
        if want
    ]
    if limbs and not route_only:
        scratch.append(pltpu.VMEM(_limb_hi_shape(S, num_bins), jnp.int32))

    def const(shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape),
                            memory_space=pltpu.VMEM)

    def rows(height):
        return pl.BlockSpec((height, blk), lambda i: (0, i),
                            memory_space=pltpu.VMEM)

    ins = [(const((S, 16)), params), (const((S, F)), col_onehot),
           (const((S, num_bins)), cat_mask), (rows(F), bins_fm),
           (rows(1), pleaf.reshape(1, N))]
    outs = [(rows(1), jax.ShapeDtypeStruct((1, N), jnp.int32))]
    if with_slot:
        assert route_only
        outs.append((rows(1), jax.ShapeDtypeStruct((1, N), jnp.int32)))
    if not route_only:
        block = hist_out_block(S * nat_ch, F, num_bins)
        ins.insert(4, (rows(CH), gh8))
        outs.insert(0, (const(block), jax.ShapeDtypeStruct(
            block, jnp.int32 if int8 else jnp.float32)))
    res = pl.pallas_call(
        functools.partial(
            _round_kernel, F=F, B=num_bins, blk=blk, S=S, nat_ch=nat_ch,
            int8=int8, oh_shift=oh_shift, efb=efb, has_cat=has_cat,
            route_only=route_only, with_slot=with_slot, limbs=limbs,
        ),
        grid=(N // blk,),
        in_specs=[spec for spec, _ in ins],
        out_specs=[spec for spec, _ in outs],
        out_shape=[shape for _, shape in outs],
        scratch_shapes=scratch,
        # the routing pass accumulates nothing across grid steps, but
        # its iota scratch is still written at step 0 only
        compiler_params=_ARBITRARY,
        interpret=interpret,
    )(*(a for _, a in ins))
    if with_slot:
        return tuple(r.reshape(N) for r in res)
    *out, pl_new = res
    return (*(hist_out_flat(o, F, num_bins) for o in out),
            pl_new.reshape(N))


@functools.partial(
    jax.jit,
    static_argnames=("num_slots", "num_bins", "nat_ch", "int8", "oh_shift",
                     "efb", "blk", "interpret", "limbs"),
)
def hist_round_tpu(
    bins_fm: jax.Array,  # (F, N) int32, natural row order
    gh8: jax.Array,  # (CH, N) f32
    pleaf: jax.Array,  # (N,) int32 row -> leaf
    params: jax.Array,  # (S, 16) int32 per-slot split params
    col_onehot: jax.Array,  # (S, F) f32 one-hot of the split column
    num_slots: int,
    num_bins: int,
    nat_ch: int,
    int8: bool = False,
    oh_shift: int = 0,
    efb: bool = False,
    cat_mask=None,  # (S, B) s8 per-slot category sets, or None
    blk: int = HIST_BLK,
    interpret: bool = False,
    limbs: bool = False,  # 256-level integer channels on the int8 MXU
):
    """One fused pass -> ((S*nat_ch, F*B) histograms, (N,) new row->leaf).

    int8 histogram sums come back scaled by -(128 >> oh_shift) (SWAR
    one-hot bytes); callers divide once on the (S*ch, F*B) output.
    `limbs` sums come back as f32 level sums, those of the bf16 path to
    the bit (_nat_kernel)."""
    assert not (int8 and limbs)
    return _round_call(
        bins_fm, gh8, pleaf, params, col_onehot, cat_mask,
        num_slots=num_slots, num_bins=num_bins, nat_ch=nat_ch, int8=int8,
        oh_shift=oh_shift, efb=efb, blk=blk, interpret=interpret,
        limbs=limbs)


@functools.partial(
    jax.jit,
    static_argnames=("num_slots", "num_bins", "efb", "blk", "interpret",
                     "with_slot"),
)
def route_round_tpu(
    bins_fm: jax.Array,  # (F, N) int32, natural row order
    pleaf: jax.Array,  # (N,) int32 row -> leaf
    params: jax.Array,  # (S, 16) int32 per-slot split params
    col_onehot: jax.Array,  # (S, F) f32 one-hot of the split column
    num_slots: int,
    num_bins: int,
    efb: bool = False,
    cat_mask=None,  # (S, B) s8 per-slot category sets, or None
    blk: int = HIST_BLK,
    interpret: bool = False,
    with_slot: bool = False,
):
    """hist_round_tpu's second output alone: the (N,) new row->leaf of
    a round whose children will never be searched; with `with_slot`
    the pair (new row->leaf, (N,) histogram slot of each row, num_slots
    where it feeds none) of a round at width. Its own name on
    purpose: a device trace names a Pallas call by its jitted wrapper,
    and the histogram kernels' readers take every call named
    hist_round_tpu / hist_nat_tpu for a histogram pass."""
    res = _round_call(
        bins_fm, None, pleaf, params, col_onehot, cat_mask,
        num_slots=num_slots, num_bins=num_bins, nat_ch=0, int8=False,
        oh_shift=0, efb=efb, blk=blk, interpret=interpret,
        with_slot=with_slot)
    return res if with_slot else res[0]


def _take_kernel(idx_ref, tab_ref, out_ref, *, L: int, k: int, blk: int):
    """out[:, r] = tab[:, idx[r]] as a one-hot MXU contraction.

    A (N,) vector gather from a small table costs ~1 ms per 1M rows on
    TPU (no vector-gather hardware); this does the same lookup as
    (k, L) @ (L, blk) one-hot matmuls per tile, ~0.1 ms for the whole
    array. HIGHEST precision: table VALUES
    are arbitrary f32 (leaf outputs) and the default TPU matmul would
    round them to bf16; with a 0/1 one-hot operand the HIGHEST-precision
    product is exact."""
    idx = idx_ref[0, :]  # (blk,) int32
    iota_l = lax.broadcasted_iota(jnp.int32, (L, blk), 0)
    onehot = (idx[None, :] == iota_l).astype(jnp.float32)  # (L, blk)
    out_ref[...] = lax.dot_general(
        tab_ref[...], onehot, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    )


@functools.partial(jax.jit, static_argnames=("blk", "interpret"))
def take_small_tpu(
    tab: jax.Array,  # (k, L) f32 — k table columns, L entries each
    idx: jax.Array,  # (N,) int32; out-of-range rows produce 0
    blk: int = HIST_BLK,
    interpret: bool = False,
) -> jax.Array:
    """(k, N) f32: tab[:, idx] via per-tile one-hot contraction."""
    k, L = tab.shape
    N = idx.shape[0]
    assert N % blk == 0, (N, blk)
    nb = N // blk
    return pl.pallas_call(
        functools.partial(_take_kernel, L=L, k=k, blk=blk),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, blk), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((k, L), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((k, blk), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((k, N), jnp.float32),
        compiler_params=_VMEM,
        interpret=interpret,
    )(idx.reshape(1, N), tab)


def _segsum_kernel(idx_ref, val_ref, out_ref, *, L: int, k: int, blk: int):
    """out[:, l] += sum over rows r with idx[r] == l of val[:, r] —
    per-leaf reductions (RenewTreeOutput sums) as a one-hot MXU
    contraction instead of an XLA scatter-add (which serializes on TPU).
    Out-of-range idx (invalid rows, idx == L or -1) match nothing.
    HIGHEST precision: values are arbitrary f32 gradients."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    idx = idx_ref[0, :]  # (blk,) int32
    iota_l = lax.broadcasted_iota(jnp.int32, (blk, L), 1)
    onehot = (idx[:, None] == iota_l).astype(jnp.float32)  # (blk, L)
    out_ref[...] += lax.dot_general(
        val_ref[...], onehot, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    )


@functools.partial(jax.jit, static_argnames=("num_out", "blk", "interpret"))
def seg_sum_tpu(
    vals: jax.Array,  # (k, N) f32
    idx: jax.Array,  # (N,) int32; out-of-range rows contribute nothing
    num_out: int,
    blk: int = HIST_BLK,
    interpret: bool = False,
) -> jax.Array:
    """(k, num_out) f32 per-index sums of vals columns."""
    k, N = vals.shape
    assert N % blk == 0, (N, blk)
    nb = N // blk
    return pl.pallas_call(
        functools.partial(_segsum_kernel, L=num_out, k=k, blk=blk),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, blk), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((k, blk), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((k, num_out), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((k, num_out), jnp.float32),
        compiler_params=_ARBITRARY,
        interpret=interpret,
    )(idx.reshape(1, N), vals)


def _hist_kernel(bins_ref, gh_ref, out_ref, *, F: int, B: int, blk: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = gh_ref[...].astype(jnp.bfloat16)  # (CH, blk)
    _accum_hist_nt(bins_ref, g, out_ref, F=F, B=B, blk=blk,
                   dt=jnp.bfloat16, acc_t=jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_bins", "blk", "interpret"))
def hist_tpu(
    bins_fm: jax.Array, gh8: jax.Array, num_bins: int, blk: int = HIST_BLK,
    interpret: bool = False,
) -> jax.Array:
    """(F, N) int32 bins + (CH, N) f32 channels -> (CH, F, B) f32.

    N must be a multiple of blk; callers pad rows with gh == 0.
    """
    F, N = bins_fm.shape
    assert N % blk == 0, (N, blk)
    assert gh8.shape == (CH, N), gh8.shape
    B = num_bins
    nb = N // blk
    block = hist_out_block(CH, F, B, whole=True)

    out = pl.pallas_call(
        functools.partial(_hist_kernel, F=F, B=B, blk=blk),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((F, blk), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((CH, blk), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(block, lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(block, jnp.float32),
        compiler_params=_ARBITRARY,
        interpret=interpret,
    )(bins_fm, gh8)
    return hist_out_flat(out, F, B).reshape(CH, F, B)
