"""The tree audit against lgb.train on a 20k-row case (the chip's
default program, Pallas kernels under the interpreter): it passes on the
model as trained and fails on a perturbed leaf value or leaf count of
tree 1 or tree 2, a worse root split, a model grown from 4-level
channels held to the 256-level floor, and an AUC outside the band."""

import copy
import json
import re

import pytest

import lightgbm_tpu as lgb
from benchmark.datasets import synthetic_higgs
from benchmark.references import tree_audit

from conftest import ADDONS

CFG = json.loads((ADDONS / "configs" / "tiny.json").read_text())
SEED, ROUNDS = 1, 4


def train(params, hist_dtype):
    d = CFG["dataset"]
    X, y, Xv, yv = synthetic_higgs.make(SEED, d["rows"], d["valid_rows"],
                                        d["features"])
    ds = lgb.Dataset(X, label=y, params=dict(params), free_raw_data=False)
    vs = lgb.Dataset(Xv, label=yv, reference=ds, free_raw_data=False)
    evals = {}
    bst = lgb.train(dict(params), ds, num_boost_round=ROUNDS,
                    valid_sets=[vs], valid_names=["valid"],
                    callbacks=[lgb.record_evaluation(evals)])
    assert bst._gbdt.hist_dtype == hist_dtype
    assert bst._gbdt.spec.rounds_slots > 0
    return dict(model=bst.model_to_string(), X=X, y=y, Xv=Xv, yv=yv,
                bins=ds._binned.bins, auc=evals["valid"]["auc"])


@pytest.fixture(scope="module")
def trained():
    return train(CFG["params"], "int16")


def run_audit(t, model=None, quality=None, seed=SEED, auc=None):
    return tree_audit.audit(
        model or t["model"], t["X"], t["y"], t["Xv"], t["yv"], t["bins"],
        auc or t["auc"], CFG["params"], quality or CFG["quality"], seed)


def edit_tree(model: str, tree: int, field: str, fn) -> str:
    """Rewrite entry 3 of ``field`` in the block of tree ``tree``."""
    m = list(re.finditer(rf"\n{field}=([^\n]*)", model))[tree - 1]
    vals = m.group(1).split(" ")
    vals[3] = fn(vals[3])
    return model[:m.start(1)] + " ".join(vals) + model[m.end(1):]


def test_audit_agrees_with_lgb_train(trained):
    v = run_audit(trained)
    assert v["problems"] == []
    f = v["facts"]
    for k in (1, 2):
        assert f[f"tree{k}_leaf_count_mismatches"] == 0
        assert f[f"tree{k}_leaf_value_max_abs_err"] < 1e-6
        assert f[f"tree{k}_root_gain_share"] > 0.95
        assert 0.9999 < f[f"tree{k}_gain_share"] < 1 + 1e-9
    assert abs(f["valid_auc_host"] - f["valid_auc_device"][-1]) < 1e-6


@pytest.mark.parametrize("tree", [1, 2])
@pytest.mark.parametrize("field, fn, says", [
    # 4e-4 is what a bf16 gradient sum would do; 1e-4 is the tolerance
    ("leaf_value", lambda v: repr(float(v) + 4e-4), "leaf 3 value"),
    ("leaf_count", lambda v: str(int(v) + 1), "leaf counts differ"),
])
def test_audit_fails_on_a_perturbed_tree(trained, tree, field, fn, says):
    v = run_audit(trained,
                  model=edit_tree(trained["model"], tree, field, fn))
    assert any(p.startswith(f"tree {tree}:") and says in p
               for p in v["problems"]), v["problems"]


def test_audit_sees_a_loss_of_channel_precision(trained):
    """The same data grown from 4-level channels (the quantized program)
    passes its own floor and fails the 256-level floor, on tree 2 alone:
    tree 1's gradients take two values, so tree 1 cannot tell."""
    params = {k: v for k, v in CFG["params"].items()
              if k != "tpu_hist_dtype"}
    params.update(use_quantized_grad=True, num_grad_quant_bins=4,
                  quant_train_renew_leaf=True)
    coarse = train(params, "int8")
    v = run_audit(coarse, auc=coarse["auc"])
    assert [p for p in v["problems"] if "exact gain" in p] == [
        p for p in v["problems"] if p.startswith("tree 2:")] != []
    assert v["facts"]["tree2_gain_share"] < 0.9999
    q = dict(CFG["quality"], tree2_gain_share_min=0.99)
    assert not any("exact gain" in p for p in run_audit(
        coarse, quality=q, auc=coarse["auc"])["problems"])


def test_audit_fails_on_a_poor_root_split(trained):
    # move the root threshold far from the optimum: every count and
    # leaf value is then wrong as well, and the gain share says why
    m = re.search(r"\nthreshold=(\S+)", trained["model"])
    bad = trained["model"][:m.start(1)] + "2.5" + trained["model"][m.end(1):]
    v = run_audit(trained, model=bad)
    assert any("tree 1: the exact gain" in p for p in v["problems"])


def test_audit_holds_auc_to_the_reference(trained):
    q = copy.deepcopy(CFG["quality"])
    q["ref_auc"][str(SEED)] = [a + 0.05 for a in q["ref_auc"][str(SEED)]]
    assert any("outside" in p for p in run_audit(trained, quality=q)[
        "problems"])
    # an unrecorded seed is held to the mean of the recorded values, on
    # both sides
    assert run_audit(trained, seed=99)["problems"] == []
    for off in (0.05, -0.05):
        q["ref_auc"] = {"5": [trained["auc"][-1] + off] * ROUNDS}
        assert any("mean of seeds [5]" in p for p in run_audit(
            trained, quality=q, seed=99)["problems"])
    q["ref_auc"] = {}
    assert any("no f32-channel reference" in p for p in run_audit(
        trained, quality=q)["problems"])


def test_audit_checks_the_device_eval(trained):
    off = list(trained["auc"])
    off[-1] += 1e-4
    assert any("device eval" in p
               for p in run_audit(trained, auc=off)["problems"])
    flat = [trained["auc"][-1]] * ROUNDS
    assert any("does not rise" in p
               for p in run_audit(trained, auc=flat)["problems"])
