"""Sweep the slot-packed nat histogram kernel over (S, blk) on a live
chip, plus an int8-MXU feasibility probe. Prints one JSON line per
measurement.

Methodology: each config is timed as R data-dependent kernel calls
inside ONE jit followed by a scalar device_get; per-call time =
(t - t_baseline) / R where the baseline jit carries the same dependency
chain without the kernel. (`block_until_ready` does synchronize on the
current runtime — PR 21 — so timing around it works too.)"""

import json
import sys
import time

import numpy as np

sys.path.insert(0, "/root/repo")


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from lightgbm_tpu.learner.histogram import build_gh8, build_gh8_quant
    from lightgbm_tpu.learner.pallas_hist import hist_nat_tpu

    print(json.dumps({"platform": jax.devices()[0].platform}), flush=True)

    rs = np.random.RandomState(0)
    F, B = 28, 256
    N = 61 * 16384  # 999424: divisible by 2048 / 8192 / 16384
    bins = jnp.asarray(rs.randint(0, 255, (F, N)).astype(np.int32))
    g = jnp.asarray(rs.randn(N).astype(np.float32))
    h = jnp.asarray((rs.rand(N) * 0.25).astype(np.float32))
    ones = jnp.ones(N, jnp.float32)
    gh8 = build_gh8(g, h, ones)
    gh8q = build_gh8_quant(
        jnp.asarray(rs.randint(-2, 3, N).astype(np.float32)),
        jnp.asarray(rs.randint(0, 5, N).astype(np.float32)),
        ones,
    )
    R = 20

    def timed(make_body):
        """make_body(acc_scalar) -> new acc_scalar, run R times in-jit."""

        def loop():
            def body(_, acc):
                return make_body(acc)

            return lax.fori_loop(0, R, body, jnp.float32(0.0))

        f = jax.jit(loop)
        float(f())  # compile + run once
        t0 = time.time()
        out = float(f())
        t = time.time() - t0
        del out
        return t / R

    # baseline: dependency-chain cost alone (gh8 materialization)
    def base_body(acc):
        gh = gh8 + acc * 0.0
        return acc + gh[0, 0]

    t_base = timed(base_body)
    print(json.dumps({"metric": "baseline_chain_ms",
                      "value": round(t_base * 1e3, 3)}), flush=True)

    def run(S, blk, ghx, nat_ch, tag):
        slot = jnp.asarray(rs.randint(0, S + 1, N).astype(np.int32))

        def body(acc):
            gh = ghx + acc * 0.0
            out = hist_nat_tpu(bins, gh, slot, S, B, blk=blk,
                               nat_ch=nat_ch)
            return acc + out[0, 0]

        try:
            t = timed(body) - t_base
            flops = 2.0 * S * nat_ch * N * B * F
            print(json.dumps({
                "metric": f"{tag}_S{S}_blk{blk}_ms",
                "value": round(t * 1e3, 2),
                "tf_s": round(flops / max(t, 1e-9) / 1e12, 1),
                "per_split_ms": round(t * 1e3 / S, 3),
            }), flush=True)
        except Exception as e:  # noqa: BLE001
            print(json.dumps({
                "metric": f"{tag}_S{S}_blk{blk}_ms",
                "error": str(e)[-400:],
            }), flush=True)

    for S in (1, 8, 25, 50):
        for blk in (2048, 8192):
            run(S, blk, gh8, 5, "nat")
    for S in (25, 42, 80):
        for blk in (2048, 8192):
            run(S, blk, gh8q, 3, "natq")

    # ---- int8 MXU probe: does Mosaic lower s8 x s8 -> s32 dot? ----
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def _k(a_ref, b_ref, o_ref):
        o_ref[...] = lax.dot_general(
            a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    M, K, Nn = 256, 2048, 1024
    a = jnp.asarray(rs.randint(-4, 5, (M, K)).astype(np.int8))
    b = jnp.asarray(rs.randint(0, 2, (K, Nn)).astype(np.int8))
    try:
        pc = pl.pallas_call(
            _k,
            out_shape=jax.ShapeDtypeStruct((M, Nn), jnp.int32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        )
        out = np.asarray(jax.jit(pc)(a, b))
        ref = np.asarray(a, np.int32) @ np.asarray(b, np.int32)
        print(json.dumps({
            "metric": "int8_dot_probe", "exact": bool((out == ref).all()),
        }), flush=True)
    except Exception as e:  # noqa: BLE001
        print(json.dumps({
            "metric": "int8_dot_probe", "error": str(e)[-300:],
        }), flush=True)


if __name__ == "__main__":
    main()
