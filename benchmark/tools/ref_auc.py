"""Reference quality for a configuration's ``quality.ref_auc``: valid AUC
per round of the f32-channel program (``tpu_hist_dtype=bf16x2``, no
gradient quantization, one chip) on the configuration's data, per seed.
Run once on the chip, outside any window; paste the printed object into
the configuration file.

    python3 benchmark/tools/ref_auc.py --config higgs --seeds 1,2,3 --rounds 12
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

QUANT_KEYS = ("use_quantized_grad", "num_grad_quant_bins",
              "quant_train_renew_leaf", "tree_learner")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rounds", type=int, required=True)
    a = ap.parse_args()

    from benchmark.harness import device
    from benchmark.harness.manifest import load_plugin
    from lightgbm_tpu._cache import ensure_compile_cache

    ensure_compile_cache()
    device.require_accelerator(1)
    import lightgbm_tpu as lgb

    cfg = json.loads(
        (ROOT / "benchmark" / "configs" / f"{a.config}.json").read_text())
    data = cfg["dataset"]
    params = {k: v for k, v in cfg["params"].items()
              if k not in QUANT_KEYS}
    params["tpu_hist_dtype"] = "bf16x2"
    gen = load_plugin(ROOT, "datasets", data["generator"])
    out = {}
    for seed in (int(s) for s in a.seeds.split(",")):
        X, y, Xv, yv = gen.make(seed, data["rows"], data["valid_rows"],
                                data["features"])
        ds = lgb.Dataset(X, label=y, params=dict(params))
        vs = lgb.Dataset(Xv, label=yv, reference=ds)
        evals: dict = {}
        bst = lgb.train(dict(params), ds, num_boost_round=a.rounds,
                        valid_sets=[vs], valid_names=["valid"],
                        callbacks=[lgb.record_evaluation(evals)])
        if bst._gbdt.hist_dtype != "bf16x2":
            raise SystemExit(f"resolved to {bst._gbdt.hist_dtype}")
        out[str(seed)] = [float(v) for v in
                          evals["valid"][params["metric"]]]
        print(f"[ref_auc] seed {seed}: {out[str(seed)]}", file=sys.stderr,
              flush=True)
        del ds, vs, bst
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
