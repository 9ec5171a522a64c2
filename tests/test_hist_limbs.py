"""The int16 program's histogram passes on the int8 MXU as two s8 limbs
(pallas_hist._limb_lhs / _limb_sum), five s8 rows a slot since the
count channel, in {0, 1}, has no high limb: element for element the bf16
path's f32 histograms, under the Pallas interpreter. Each row block's
limb sums are recombined exactly in s32 and added into the f32 output
block in the order the bf16 path adds its exact block products, so the
two must agree to the bit, not to a tolerance. The schedule puts
every pass of the 256-level program on limbs and no pass of the int8
or bf16x2 programs (rounds.hist_schedule), and the gauges
lgbmtpu_hist_limbs{width} and lgbmtpu_hist_limb_rows{width} say so.
The limb kernels at the cells' real shapes for a described v5e:
test_aot_v5e.py."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.learner import pallas_hist, rounds as rounds_mod
from lightgbm_tpu.learner.histogram import HIST_BLK, build_gh8_quant
from lightgbm_tpu.learner.pallas_hist import hist_nat_tpu, hist_round_tpu
from lightgbm_tpu.obs import default_registry

from test_pallas_kernels import _grow_case, interp  # noqa: F401


def _levels(rs, n, variant="plain"):
    """(8, n) int16-program channels: grad in [-128, 128], hess in
    [0, 256], count 1; `extreme` puts every row at |grad| 128 (its sign
    by row block) and hess 256, so a block's partial is at its largest;
    `overflow` draws the one level past each end that the quantizer's
    stochastic rounding can land on, grad +-129 and hess 257 (which the
    bf16 operand holds as 256); `bagged` draws the count, the in-bag
    mask, from {0, 1} and zeroes grad and hess where it is 0, as the
    rounds grower builds them (build_gh8_quant(grad * mask, hess * mask,
    mask))."""
    if variant == "extreme":
        g = np.where((np.arange(n) // HIST_BLK) % 2 == 0, 128.0, -128.0)
        h = np.full(n, 256.0)
    else:
        top = 1 if variant == "overflow" else 0
        g = rs.randint(-128 - top, 129 + top, n).astype(np.float64)
        h = rs.randint(0, 257 + top, n).astype(np.float64)
    mask = np.ones(n, np.float32)
    if variant == "bagged":
        mask = (rs.rand(n) < 0.6).astype(np.float32)
        assert 0 < mask.sum() < n
    return build_gh8_quant(jnp.asarray(g * mask, jnp.float32),
                           jnp.asarray(h * mask, jnp.float32),
                           jnp.asarray(mask))


def _nat_oracle(bins, gh8, slot, S, B):
    """(S * 3, F * B) exact sums in NumPy of the levels as bf16 holds
    them."""
    gh8 = gh8.astype(jnp.bfloat16).astype(jnp.float32)
    bins, gh8, slot = map(np.asarray, (bins, gh8, slot))
    F = bins.shape[0]
    out = np.zeros((S, 3, F, B))
    for s in range(S):
        m = slot == s
        for f in range(F):
            for c in range(3):
                out[s, c, f] = np.bincount(bins[f, m], weights=gh8[c, m],
                                           minlength=B)[:B]
    return out.reshape(S * 3, F * B)


def _round_operands(rs, F, B, S, variant, n):
    """One round kernel call's operands over `n` rows spread on 24
    leaves; `variant` cat turns three slots categorical, efb adds the
    bundle decode columns."""
    pleaf = jnp.asarray(rs.randint(0, 24, n).astype(np.int32))
    col = rs.randint(0, F, S)
    p = np.zeros((S, 16), np.int32)
    p[:, 0] = rs.permutation(24)[:S]
    p[:, 1] = col
    p[:, 2] = rs.randint(0, B, S)
    p[:, 3] = rs.randint(0, 2, S)
    p[:, 4] = np.where(rs.rand(S) < 0.5, B - 1, -1)
    p[:, 5] = rs.randint(0, 2, S)
    p[:, 6] = 24 + np.arange(S)
    p[:, 8] = -1
    cat_mask = None
    if variant == "efb":
        p[:, 7] = rs.randint(0, B // 2, S)
        p[:, 9] = rs.randint(2, B // 2, S)
        p[:, 8] = np.where(rs.rand(S) < 0.7,
                           rs.randint(0, 2, S) * (p[:, 9] - 1), -1)
    if variant == "cat":
        p[:3, 10] = 1
        cat_mask = jnp.asarray(rs.randint(0, 2, (S, B)).astype(np.int8))
    coh = (col[:, None] == np.arange(F)[None, :]).astype(np.float32)
    return pleaf, jnp.asarray(p), jnp.asarray(coh), cat_mask


@pytest.mark.parametrize("kernel,columns,bins,slots,variant", [
    ("round", 3, 255, 8, "plain"),  # one column a one-hot tile
    ("round", 5, 63, 16, "plain"),  # two columns a tile (the pair)
    ("round", 3, 255, 8, "cat"),  # the kernels' category test
    ("round", 4, 256, 8, "efb"),  # the bundle decode's operands
    ("round", 2, 255, 8, "extreme"),
    ("nat", 3, 255, 1, "plain"),  # the root pass
    ("nat", 40, 63, 8, "blocked"),  # two feature blocks, the last ragged
    ("nat", 2, 255, 1, "extreme"),
    ("round", 3, 255, 16, "overflow"),
    ("nat", 3, 255, 8, "overflow"),
    ("round", 3, 255, 24, "bagged"),  # the rank cell's 48-slot chunk
    ("nat", 3, 255, 16, "bagged"),
    ("nat", 40, 63, 8, "bagged"),  # two loop groups, the pair
])
def test_limb_pass_equals_the_bf16_pass(kernel, columns, bins, slots,
                                        variant):
    rs = np.random.RandomState(40 + columns + bins + slots)
    n = 2 * HIST_BLK
    extreme = variant == "extreme"
    bins_fm = (jnp.zeros((columns, n), jnp.int32) if extreme else
               jnp.asarray(rs.randint(0, bins, (columns, n)), jnp.int32))
    gh8 = _levels(rs, n, variant)
    if kernel == "nat":
        slot = (jnp.zeros(n, jnp.int32) if extreme else
                jnp.asarray(rs.randint(0, slots + 1, n), jnp.int32))
        kw = dict(nat_ch=3, interpret=True,
                  feat_block=32 if variant == "blocked" else 0)
        ref = hist_nat_tpu(bins_fm, gh8, slot, slots, bins, **kw)
        out = hist_nat_tpu(bins_fm, gh8, slot, slots, bins, limbs=True,
                           **kw)
        np.testing.assert_array_equal(
            np.asarray(ref), _nat_oracle(bins_fm, gh8, slot, slots, bins))
    else:
        pleaf, params, coh, cat_mask = _round_operands(
            rs, columns, bins, slots, variant, n)
        kw = dict(efb=variant == "efb", cat_mask=cat_mask, interpret=True)
        ref, pl_ref = hist_round_tpu(bins_fm, gh8, pleaf, params, coh,
                                     slots, bins, 3, **kw)
        out, pl_out = hist_round_tpu(bins_fm, gh8, pleaf, params, coh,
                                     slots, bins, 3, limbs=True, **kw)
        np.testing.assert_array_equal(np.asarray(pl_out), np.asarray(pl_ref))
    assert out.dtype == ref.dtype == jnp.float32
    assert out.shape == ref.shape == (slots * 3, columns * bins)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    if extreme and kernel == "nat":  # the largest block partials occur
        assert float(jnp.max(jnp.abs(out))) == 256 * n
    # the W the limb path builds: five s8 rows a slot
    sel = jnp.asarray(rs.randint(0, 2, (slots, n)), jnp.int32)
    W = pallas_hist._limb_lhs(sel, pallas_hist._bf16_levels(gh8[:3]))
    assert W.shape == (pallas_hist.LIMB_ROWS * slots, n) == (5 * slots, n)
    assert W.dtype == jnp.int8


def _spec(program):
    from lightgbm_tpu.learner import GrowerSpec

    quant = program != "bf16x2"
    return GrowerSpec(num_leaves=255, num_bins=255, max_depth=-1,
                      rounds_slots=48 if quant else 25, has_cat=False,
                      quant=quant, quant_int8=program == "int8",
                      quant_levels={"int16": 256, "int8": 127}.get(program, 0))


@pytest.mark.parametrize("program,limbs", [
    ("int16", True), ("int8", False), ("bf16x2", False)])
def test_schedule_puts_the_int16_passes_on_limbs(interp, program, limbs):
    from lightgbm_tpu.obs.metrics import record_hist_schedule

    sched = rounds_mod.hist_schedule(_spec(program), 512 * HIST_BLK, 28)
    assert sched.fused and sched.limbs is limbs
    assert sched.calls[0][0] == "root" and len(sched.calls) >= 4
    record_hist_schedule(sched, 28)
    snap = default_registry().snapshot()
    assert dict(snap["lgbmtpu_hist_limbs"]) == {
        f'{{width="{w}"}}': int(limbs) for w, _ in sched.calls}
    assert dict(snap["lgbmtpu_hist_limb_rows"]) == {
        f'{{width="{w}"}}': 5 if limbs else 0 for w, _ in sched.calls}


def _kernels_on(monkeypatch, limbs=None):
    """Wrap the two histogram kernels: the returned list records what
    the program asks of each call (its `limbs`), and `limbs` True or
    False overrides it."""
    asked = []

    def wrap(kernel):
        def call(*a, **kw):
            asked.append(kw.get("limbs"))
            if limbs is not None:
                kw = {**kw, "limbs": limbs}
            return kernel(*a, **kw)
        return call

    for name in ("hist_round_tpu", "hist_nat_tpu"):
        monkeypatch.setattr(pallas_hist, name,
                            wrap(getattr(pallas_hist, name)))
    return asked


def test_limb_tree_equals_the_bf16_tree(interp, monkeypatch):
    """A 63-leaf int16 tree whose every pass runs on limbs: every array
    of the tree and the row -> leaf vector equal the same program's
    with every pass on the bf16 operand."""
    kw = dict(rounds_slots=48, has_cat=False, num_leaves=63, quant=True,
              quant_levels=256)
    limb = _grow_case(kw, quant=True, smooth=True, with_stats=True)
    asked = _kernels_on(monkeypatch, limbs=False)
    jax.clear_caches()  # the kernels are looked up when the grower traces
    bf16 = _grow_case(kw, quant=True, smooth=True, with_stats=True)
    assert asked and all(asked)
    assert all(int(n) > 0 for n in limb[5]["rounds"][:2])
    for a, b in zip(jax.tree.leaves(limb[4]), jax.tree.leaves(bf16[4])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(limb[1], bf16[1])


def test_limb_mesh_model_equals_the_bf16_mesh_model(mesh4, monkeypatch):
    """tree_learner=data over four devices: the int16 program's kernels
    run under the interpreter inside the grower's shard_map, one shard
    of whole row blocks a device, and their histograms cross the mesh
    on the integer reduce-scatter wire. The model text with every pass
    on limbs is the one with every pass on the bf16 operand, byte for
    byte."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting import _FUSED_STEP_CACHE
    from lightgbm_tpu.parallel import data_parallel
    from test_tree_learner_data import (_padded_to_whole_blocks, _problem4,
                                        _train4)

    def fresh():
        _FUSED_STEP_CACHE.clear()
        data_parallel._GROWERS.clear()
        jax.clear_caches()

    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(lgb.basic.Dataset, "construct",
                        _padded_to_whole_blocks(lgb.basic.Dataset.construct))
    X, y = _problem4(61)
    fresh()
    try:
        with monkeypatch.context() as m:
            asked = _kernels_on(m)
            limb = _train4(X, y, {"tree_learner": "data"}, rounds=2)
        g = limb._gbdt
        assert g._mesh.devices.size == 4 and g.dev["bins"].shape[1] == (
            4 * HIST_BLK)
        assert g.hist_wire_resolved.startswith("rs_")
        assert asked and all(asked)
        fresh()
        asked = _kernels_on(monkeypatch, limbs=False)
        bf16 = _train4(X, y, {"tree_learner": "data"}, rounds=2)
        assert asked and all(asked)
        assert limb.model_to_string() == bf16.model_to_string()
    finally:
        fresh()
