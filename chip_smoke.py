"""Chip smoke: the default program, end to end, on the TPU — or a failure.

    python chip_smoke.py            # from the root of a copy of the repo

One process (a chip belongs to one process at a time) drives the main
path through the public entry points at the full width of the one shape
every chip record of this repo is about — the bench.py synthetic Higgs
generator, 1,000,000 x 28, num_leaves=255, max_bin=255, binary/auc, a
100k-row held-out valid set, every other parameter default:

1. kernel self-check: every Pallas entry the two programs below use,
   compiled by Mosaic at the full-width shapes, against the XLA
   formulation beside it in learner/histogram.py (integer paths must
   match exactly); then the same entries at 63 bins (max_bin=63, the
   reference's accelerator setting), where the kernels' feature loop
   takes two columns per one-hot tile;
2. lgb.train, default parameters (on a TPU: rounds grower, int16
   3-channel histograms, chunk-scan dispatch), 2 x the smallest chunk
   rung, then the same again to show the rounds that follow set-up
   compile nothing;
3. the same with use_quantized_grad / num_grad_quant_bins=4 (the int8
   s8 x s8 -> s32 kernel);
4. device scoring of the forest just trained (Booster.predict
   device="tpu", and ModelRegistry / BucketDispatcher requests) against
   the host tree-walker to 1e-5, with zero host-fallback scores;
5. with >= 4 devices, the training step again with tree_learner=data
   over all of them.

Every check raises; no phase is wrapped in try/except, so any failure
is a non-zero exit. A backend that is not a TPU is refused before any
work (jax falls back to the CPU by itself when no TPU initialises — the
script does not inherit that). The last line of stdout is one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROWS, FEATS, LEAVES, MAX_BIN = 1_000_000, 28, 255, 255
# rows of the training bin matrix the kernel self-check streams: the
# kernels' compile limits depend on width (columns x bins x slots), the
# row count only sets the grid length
CHECK_BLOCKS = 64
# the bin count of the second kernel self-check: the kernels pair columns
# at 33..64 bins (pallas_hist.columns_per_matmul)
PAIR_BINS = 63
SCORE_TOL = 1e-5
AUC_FLOOR = 0.8  # "well above chance" after a handful of rounds
MULTICHIP_AUC_BAND = 2e-3  # the band tests/test_tree_learner_data.py pins
# everything else is the default: tpu_growth_mode and tpu_hist_dtype
# stay `auto` and resolve on the chip
PARAMS = {"objective": "binary", "metric": "auc", "num_leaves": LEAVES,
          "max_bin": MAX_BIN, "verbosity": -1}


def check(ok, what: str) -> None:
    """Raise (never `assert`: -O must not turn the smoke into a no-op)."""
    if not ok:
        raise AssertionError(what)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu() -> dict:
    import jax

    dev = jax.devices()[0]
    if jax.default_backend() != "tpu":
        sys.exit(
            f"[chip_smoke] FAIL: backend is {jax.default_backend()!r} "
            f"({dev.device_kind}, {len(jax.devices())} device(s)), not "
            "'tpu' — this script proves the program on the chip and "
            "does not run anywhere else"
        )
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def versions() -> dict:
    from importlib import metadata

    import jax
    import jaxlib

    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": metadata.version("libtpu"),
            "numpy": np.__version__, "python": sys.version.split()[0]}


# ---------------------------------------------------------------- kernels
def _histogram_module():
    """learner/__init__ re-exports the histogram FUNCTION, which shadows
    the submodule on attribute import."""
    import importlib

    return importlib.import_module("lightgbm_tpu.learner.histogram")


def _round_reference(bins, gh8, pleaf, params, S, B, quant):
    """XLA formulation of the fused round step: the partition decision
    as plain array ops, the slot histograms through
    histogram._hist_nat_fallback."""
    import jax.numpy as jnp

    from lightgbm_tpu.learner.histogram import _hist_nat_fallback

    fb = jnp.take(bins, params[:, 1], axis=0)  # (S, N) split-column bins
    memb = pleaf[None, :] == params[:, 0:1]
    gl = (fb <= params[:, 2:3]) | (
        (params[:, 3:4] != 0) & (fb == params[:, 4:5]))
    pl_new = pleaf + jnp.sum(
        jnp.where(memb & ~gl, params[:, 6:7] - pleaf[None, :], 0), axis=0)
    side = memb & (gl == (params[:, 5:6] != 0))
    slot = jnp.where(jnp.any(side, axis=0),
                     jnp.argmax(side, axis=0), S).astype(jnp.int32)
    return _hist_nat_fallback(bins, gh8, slot, S, B, quant=quant), pl_new


def kernel_selfcheck(bins, num_bins: int) -> list:
    """One Mosaic-compiled call per Pallas entry of the training
    programs vs its XLA formulation. `bins` is a (G, N) int32 slice of
    the real training bin matrix. Returns summary lines."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.learner.histogram import (
        build_gh8, build_gh8_quant, can_hist_round, hist_nat_slots,
        hist_round, int8_oh_shift, route_round, seg_sum, take_cols,
    )

    H = _histogram_module()
    G, N = bins.shape
    B = num_bins
    rs = np.random.RandomState(5)
    ones = jnp.ones(N, jnp.float32)
    lines = []

    def rand_levels(lo, hi):
        return jnp.asarray(rs.randint(lo, hi + 1, N).astype(np.float32))

    layouts = [
        # name, slots the program packs per pass, quant, int8, gh8
        ("nat_ch=3 bf16 (int16 default)", 48, True, False,
         build_gh8_quant(rand_levels(-128, 128), rand_levels(0, 256), ones)),
        ("nat_ch=3 int8 (quantized)", 48, True, True,
         build_gh8_quant(rand_levels(-2, 2), rand_levels(0, 4), ones)),
        ("nat_ch=5 bf16x2", 25, False, False,
         build_gh8(jnp.asarray(rs.randn(N).astype(np.float32)),
                   jnp.asarray((rs.rand(N) + 0.5).astype(np.float32)),
                   ones)),
    ]
    for name, S, quant, int8, gh8 in layouts:
        levels = 4 if int8 else 256
        shift = int8_oh_shift(N, levels) if int8 else 0
        check(shift is not None, f"{name}: no safe SWAR shift")
        s_max = H._round_s_max(G, B, quant, int8)
        check(can_hist_round(N, S, G, B, quant, int8=int8),
              f"{name}: fused round gate closed at N={N} S={S} G={G} B={B}")
        # each row sits in one of S leaves; slot s splits leaf s
        pleaf = jnp.asarray(rs.randint(0, S, N).astype(np.int32))
        col = rs.randint(0, G, S)
        params = np.zeros((S, 16), np.int32)
        params[:, 0] = np.arange(S)
        params[:, 1] = col
        params[:, 2] = rs.randint(0, B - 1, S)
        params[:, 3] = rs.randint(0, 2, S)
        params[:, 4] = np.where(rs.rand(S) < 0.5, -1, B - 1)
        params[:, 5] = rs.randint(0, 2, S)
        params[:, 6] = S + np.arange(S)
        params[:, 8] = -1
        params = jnp.asarray(params)
        coh = jnp.asarray(np.eye(G, dtype=np.float32)[col])
        t0 = time.perf_counter()
        out, pl_new = hist_round(bins, gh8, pleaf, params, coh, S, B,
                                 quant=quant, int8=int8, oh_shift=shift)
        out = np.asarray(out)
        dt = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            ref, pl_ref = _round_reference(bins, gh8, pleaf, params, S, B,
                                           quant)
            slot = jnp.asarray(rs.randint(0, S + 1, N).astype(np.int32))
            nat_ref = np.asarray(H._hist_nat_fallback(
                bins, gh8, slot, S, B, quant=quant))
        nat = np.asarray(hist_nat_slots(bins, gh8, slot, S, B, quant=quant,
                                        int8=int8, oh_shift=shift))
        check(np.array_equal(np.asarray(pl_new), np.asarray(pl_ref)),
              f"hist_round_tpu {name}: row->leaf differs from XLA")
        # the routing-only pass of a tree's last round: one call at
        # the program's full slot count, no histogram
        pl_route = route_round(bins, pleaf, params, coh, S, B)
        check(np.array_equal(np.asarray(pl_route), np.asarray(pl_ref)),
              f"route_round_tpu S={S}: row->leaf differs from XLA")
        check(np.abs(np.asarray(ref)).sum() > 0, f"{name}: empty reference")
        if quant:  # integer sums: exact or wrong
            check(np.array_equal(out, np.asarray(ref)),
                  f"hist_round_tpu {name}: integer histogram != XLA")
            check(np.array_equal(nat, nat_ref),
                  f"hist_nat_tpu {name}: integer histogram != XLA")
        else:
            np.testing.assert_allclose(out, np.asarray(ref),
                                       atol=2e-3, rtol=1e-4)
            np.testing.assert_allclose(nat, nat_ref, atol=2e-3, rtol=1e-4)
        lines.append(
            f"hist_round_tpu + route_round_tpu + hist_nat_tpu {name}: S={S} "
            f"(one-chunk cap {s_max}) G={G} B={B} N={N} ok "
            f"({'exact' if quant else 'atol 2e-3'}; first call {dt:.1f}s)"
        )

    # take / seg-sum at the leaf-table width the programs use
    L = LEAVES
    tab = jnp.asarray(rs.randn(8, L).astype(np.float32))
    idx = jnp.asarray(rs.randint(-1, L + 1, N).astype(np.int32))
    got = np.asarray(take_cols(tab, idx))
    safe = jnp.clip(idx, 0, L - 1)
    ref = np.asarray(jnp.where(((idx >= 0) & (idx < L))[None, :],
                               jnp.take(tab, safe, axis=1), 0.0))
    check(np.array_equal(got, ref), "take_small_tpu != jnp.take")
    vals = jnp.asarray(rs.randint(-8, 9, (2, N)).astype(np.float32))
    got = np.asarray(seg_sum(vals, idx, L))
    in_range = (idx >= 0) & (idx < L)
    ref = np.asarray(jnp.zeros((2, L), jnp.float32).at[
        :, jnp.where(in_range, idx, L)].add(
            jnp.where(in_range[None, :], vals, 0.0), mode="drop"))
    check(np.array_equal(got, ref), "seg_sum_tpu != XLA scatter-add")
    lines.append(f"take_small_tpu, seg_sum_tpu: L={L} N={N} ok (exact)")
    return lines


# --------------------------------------------------------------- training
def train_phase(name, lgb, ds, vs, extra: dict, want_dtype: str,
                rounds: int) -> dict:
    """lgb.train twice on the same constructed data: the first run pays
    trace + compile, the second must compile nothing."""
    from lightgbm_tpu.analysis.retrace import compile_counters
    from lightgbm_tpu.learner.histogram import can_hist_round

    params = {**PARAMS, **extra}

    def run():
        evals: dict = {}
        t0 = time.perf_counter()
        bst = lgb.train(dict(params), ds, num_boost_round=rounds,
                        valid_sets=[ds, vs], valid_names=["train", "valid"],
                        callbacks=[lgb.record_evaluation(evals)])
        return bst, evals, time.perf_counter() - t0

    bst, evals, first_s = run()
    before = compile_counters()
    bst2, evals2, again_s = run()
    after = compile_counters()
    new_compiles = after["backend_compiles"] - before["backend_compiles"]
    new_traces = after["jaxpr_traces"] - before["jaxpr_traces"]

    g = bst._gbdt
    G, N = g.dev["bins"].shape
    check(g.spec.rounds_slots > 0, f"{name}: not the rounds grower")
    check(g.hist_dtype == want_dtype,
          f"{name}: hist_dtype {g.hist_dtype!r}, expected {want_dtype!r}")
    check(can_hist_round(N, g.spec.rounds_slots, G, g.spec.num_bins,
                         g.spec.quant, int8=g.spec.quant_int8),
          f"{name}: the fused round kernel's gate is closed for "
          f"N={N} S={g.spec.rounds_slots} G={G} B={g.spec.num_bins}")
    check(not g._force_sync, f"{name}: forced onto the sync loop: "
                             f"{g._force_sync_reason}")
    check(g.fused_dispatch_count == 2 and len(g._f_program.chunks) == 1,
          f"{name}: expected one scan executable dispatched twice, got "
          f"{g.fused_dispatch_count} dispatches of "
          f"{sorted(g._f_program.chunks)}")
    check(new_compiles == 0 and new_traces == 0,
          f"{name}: {new_compiles} compiles / {new_traces} traces after "
          "warm-up")
    check(bst.num_trees() == rounds, f"{name}: {bst.num_trees()} trees")
    for which in ("train", "valid"):
        auc = evals[which]["auc"]
        check(len(auc) == rounds and np.all(np.isfinite(auc)),
              f"{name}: {which} auc {auc}")
        check(auc[-1] > auc[0], f"{name}: {which} auc not improving {auc}")
    check(evals["valid"]["auc"][-1] > AUC_FLOOR,
          f"{name}: valid auc {evals['valid']['auc'][-1]} <= {AUC_FLOOR}")
    check(evals2["valid"]["auc"] == evals["valid"]["auc"],
          f"{name}: the repeated run diverged")
    return {
        "bst": bst, "first_s": first_s, "again_s": again_s,
        "valid_auc": evals["valid"]["auc"][-1],
        "train_auc": evals["train"]["auc"][-1],
        "line": (
            f"{name}: growth=rounds(S={g.spec.rounds_slots}) "
            f"hist_dtype={g.hist_dtype} chunk scans "
            f"(rung {sorted(g._f_program.chunks)} x"
            f"{g.fused_dispatch_count}) {rounds} rounds: "
            f"first run {first_s:.1f}s (trace+compile+rounds), repeat "
            f"{again_s:.1f}s with 0 compiles; auc train "
            f"{evals['train']['auc'][-1]:.4f} valid "
            f"{evals['valid']['auc'][-1]:.4f}"
        ),
    }


# ---------------------------------------------------------------- scoring
def score_phase(bst, Xv) -> str:
    from lightgbm_tpu.obs.metrics import default_registry
    from lightgbm_tpu.serving import ModelRegistry

    host = bst.predict(Xv)  # host tree-walker
    dev = bst.predict(Xv, device="tpu")
    err = float(np.max(np.abs(dev - host)))
    check(np.all(np.isfinite(dev)) and err <= SCORE_TOL,
          f"Booster.predict(device='tpu') off the host walker by {err}")
    reg = ModelRegistry(warmup=True)
    reg.load("smoke", bst, num_features=Xv.shape[1])
    worst = err
    # one request per ladder shape class: single row, mid-bucket,
    # larger than the top bucket (chunks), and one through the queue
    for rows, via_queue in ((1, False), (300, False), (5000, False),
                            (64, True)):
        got = np.asarray(reg.predict("smoke", Xv[:rows],
                                     via_queue=via_queue))
        e = float(np.max(np.abs(got - host[:rows])))
        check(e <= SCORE_TOL, f"registry request of {rows} rows off by {e}")
        worst = max(worst, e)
    reg.unload("smoke")  # joins the microbatch worker
    fallbacks = sum(default_registry().snapshot().get(
        "lgbmtpu_serve_host_fallback_total", {}).values())
    check(fallbacks == 0, f"{fallbacks} chunks were scored on the host")
    return (f"scoring: predict(device='tpu') on {Xv.shape[0]} rows and 4 "
            f"registry requests within {worst:.2e} of the host walker "
            f"(tol {SCORE_TOL}); host_fallback_total=0")


# -------------------------------------------------------------- multichip
def multichip_phase(lgb, data, rounds: int, one_chip_auc: float) -> str:
    import jax

    X, y, Xv, yv = data
    n = jax.device_count()
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    vs = lgb.Dataset(Xv, label=yv, reference=ds, free_raw_data=False)
    evals: dict = {}
    bst = lgb.train(
        {**PARAMS, "tree_learner": "data"}, ds, num_boost_round=rounds, valid_sets=[vs], valid_names=["valid"],
        callbacks=[lgb.record_evaluation(evals)])
    g = bst._gbdt
    check(g._mesh is not None and g._mesh.devices.size == n,
          f"mesh {g._mesh} does not span {n} devices")
    held = {s.device for s in g.dev["bins"].addressable_shards}
    check(len(held) == n,
          f"bin-matrix shards on {len(held)} device(s), not {n}")
    check(g.spec.rounds_slots > 0 and g.hist_dtype == "int16",
          f"multichip: {g.spec.rounds_slots} slots, {g.hist_dtype}")
    auc = evals["valid"]["auc"][-1]
    check(abs(auc - one_chip_auc) <= MULTICHIP_AUC_BAND,
          f"valid auc {auc} vs one-chip {one_chip_auc}")
    return (f"multichip: tree_learner=data over {n} devices, bin shards "
            f"on {len(held)} distinct devices, valid auc {auc:.4f} "
            f"(one chip {one_chip_auc:.4f})")


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter
    ).parse_args(argv)

    t_start = time.perf_counter()
    import jax

    device = require_tpu()

    import lightgbm_tpu as lgb
    from bench import synthetic_higgs
    from lightgbm_tpu import native
    from lightgbm_tpu._cache import CACHE_DIR, ensure_compile_cache
    from lightgbm_tpu.analysis.retrace import ensure_installed
    from lightgbm_tpu.config import DEFAULT_CHUNK_LADDER
    from lightgbm_tpu.learner.histogram import HIST_BLK

    H = _histogram_module()
    ensure_installed()  # count traces/compiles from the start
    cache_dir = ensure_compile_cache()
    cache_before = len(os.listdir(cache_dir)) if os.path.isdir(
        cache_dir) else 0
    say(f"platform={device['platform']} device_kind={device['kind']} "
        f"devices={device['count']} versions={versions()}")
    say(f"compile cache: {cache_dir} ({cache_before} entries at start; "
        f"{'the in-checkout default' if cache_dir == CACHE_DIR else 'placed from outside'})")

    rounds = 2 * min(DEFAULT_CHUNK_LADDER)  # the same rung, twice
    data = synthetic_higgs(ROWS, FEATS)
    X, y, Xv, yv = data
    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    ds.construct()
    vs = lgb.Dataset(Xv, label=yv, reference=ds, free_raw_data=False)
    vs.construct()
    construct_s = time.perf_counter() - t0
    say(f"Dataset.construct ({ROWS}+{Xv.shape[0]} rows): "
        f"{construct_s:.1f}s; native fastparse: {native.status()}")

    binned = ds._binned
    bins = binned.device_arrays()["bins"][:, :CHECK_BLOCKS * HIST_BLK]
    t0 = time.perf_counter()
    kernel_lines = kernel_selfcheck(bins, binned.max_num_bin)
    kernel_lines += kernel_selfcheck(bins % PAIR_BINS, PAIR_BINS)
    kernels_s = time.perf_counter() - t0
    for ln in kernel_lines:
        say("kernel self-check: " + ln)

    default = train_phase("train default", lgb, ds, vs, {}, "int16", rounds)
    say(default["line"])
    quant = train_phase(
        "train quantized", lgb, ds, vs,
        {"use_quantized_grad": True, "num_grad_quant_bins": 4}, "int8",
        rounds)
    say(quant["line"])

    score_line = score_phase(default["bst"], Xv)
    say(score_line)

    if jax.device_count() >= 4:
        multichip_line = multichip_phase(lgb, data, rounds,
                                         default["valid_auc"])
    else:
        multichip_line = f"multichip: not run ({jax.device_count()} device)"
    say(multichip_line)

    check(not H._gate_warned,
          f"Pallas gates missed on the TPU: {sorted(H._gate_warned)}")
    cache_after = len(os.listdir(cache_dir))
    setup_s = construct_s + (default["first_s"] - default["again_s"]) + (
        quant["first_s"] - quant["again_s"])
    say(f"set-up (construct + trace/compile of both programs): "
        f"{setup_s:.1f}s; kernel self-check {kernels_s:.1f}s; "
        f"compile cache entries {cache_before} -> {cache_after}; "
        f"total {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
