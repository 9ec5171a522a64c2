"""Round-5 probe: decompose the slot-packed histogram pass's fixed cost.

Times, on a live chip (in-jit fori_loop methodology):

- the current int8 S=48 pass (baseline);
- one-hot-build-free variant (constant one-hot: isolates compare+cast);
- matmul-free variant (compares only: isolates the MXU cost);
- bins stored s8 / i16 instead of i32 (lighter VMEM tiles + packed
  VPU compares, if Mosaic packs them);
- a fused-partition prototype: the same pass ALSO computing per-row
  go_left/pleaf_new in-kernel from per-slot split params (does the
  round's 2.2 ms fbins select + partition update for free?).

Prints one JSON line per measurement.
"""

import json
import sys
import time
import functools

import numpy as np

sys.path.insert(0, "/root/repo")


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from lightgbm_tpu.learner.histogram import build_gh8_quant, CH

    print(json.dumps({"platform": jax.devices()[0].platform}), flush=True)

    rs = np.random.RandomState(0)
    F, B = 28, 256
    N = 61 * 16384
    blk = 2048
    bins_np = rs.randint(0, 255, (F, N)).astype(np.int32)
    bins = jnp.asarray(bins_np)
    bins8 = jnp.asarray((bins_np - 128).astype(np.int8))
    bins16 = jnp.asarray(bins_np.astype(np.int16))
    ones = jnp.ones(N, jnp.float32)
    gh8q = build_gh8_quant(
        jnp.asarray(rs.randint(-2, 3, N).astype(np.float32)),
        jnp.asarray(rs.randint(0, 5, N).astype(np.float32)),
        ones,
    )
    R = 20

    def timed(make_body):
        def loop():
            def body(_, acc):
                return make_body(acc)

            return lax.fori_loop(0, R, body, jnp.float32(0.0))

        f = jax.jit(loop)
        float(f())
        t0 = time.time()
        float(f())
        return (time.time() - t0) / R

    def base_body(acc):
        gh = gh8q + acc * 0.0
        return acc + gh[0, 0]

    t_base = timed(base_body)
    print(json.dumps({"metric": "baseline_chain_ms",
                      "value": round(t_base * 1e3, 3)}), flush=True)

    # ---------------- variant kernels ----------------
    def nat_kernel(bins_ref, gh_ref, slot_ref, out_ref, *, S, nat_ch,
                   mode, bdt):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        slot = slot_ref[0, :]
        gh = gh_ref[...]
        iota_s = lax.broadcasted_iota(jnp.int32, (S, blk), 0)
        sl32 = (slot[None, :] == iota_s).astype(jnp.int32)
        g32 = gh[:nat_ch, :].astype(jnp.int32)
        W = (sl32[:, None, :] * g32[None, :, :]).reshape(
            S * nat_ch, blk).astype(jnp.int8)
        if bdt == "i8":
            iota_bT = (lax.broadcasted_iota(jnp.int32, (B, blk), 0)
                       - 128).astype(jnp.int8)
        elif bdt == "i16":
            iota_bT = lax.broadcasted_iota(jnp.int32, (B, blk), 0).astype(
                jnp.int16)
        else:
            iota_bT = lax.broadcasted_iota(jnp.int32, (B, blk), 0)
        for f in range(F):
            if mode == "nooh":
                # constant one-hot: no compare, same matmul
                ohT = jnp.ones((B, blk), jnp.int8)
            else:
                ohT = (bins_ref[f:f + 1, :] == iota_bT).astype(jnp.int8)
            if mode == "nomm":
                out_ref[0:1, f * B:(f + 1) * B] += lax.dot_general(
                    W[0:1], ohT, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.int32)
            else:
                out_ref[:, f * B:(f + 1) * B] += lax.dot_general(
                    W, ohT, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.int32)

    def run_nat(tag, S, nat_ch, mode, bdt, bins_in):
        nb = N // blk
        Fb = bins_in.shape[0]
        kern = functools.partial(nat_kernel, S=S, nat_ch=nat_ch, mode=mode,
                                 bdt=bdt)
        call = pl.pallas_call(
            kern,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((Fb, blk), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((CH, blk), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, blk), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((S * nat_ch, F * B), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((S * nat_ch, F * B), jnp.int32),
        )
        slot = jnp.asarray(rs.randint(0, S + 1, N).astype(np.int32))

        def body(acc):
            gh = gh8q + acc * 0.0
            out = call(bins_in, gh, slot.reshape(1, N))
            return acc + out[0, 0].astype(jnp.float32)

        try:
            t = timed(body) - t_base
            print(json.dumps({
                "metric": tag, "ms": round(t * 1e3, 3),
                "per_split_ms": round(t * 1e3 / S, 4),
            }), flush=True)
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"metric": tag, "error": str(e)[-300:]}),
                  flush=True)

    for S in (1, 48):
        run_nat(f"int8_S{S}_i32bins", S, 3, "full", "i32", bins)
        run_nat(f"int8_S{S}_noonehot", S, 3, "nooh", "i32", bins)
        run_nat(f"int8_S{S}_nomatmul", S, 3, "nomm", "i32", bins)
        run_nat(f"int8_S{S}_s8bins", S, 3, "full", "i8", bins8)
        run_nat(f"int8_S{S}_i16bins", S, 3, "full", "i16", bins16)


if __name__ == "__main__":
    main()
