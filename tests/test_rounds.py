"""Round-batched growth (tpu_growth_mode=rounds, rounds.py) and the
slot-packed histogram used by it (reference CUDA all-leaves batching,
cuda_histogram_constructor.cu)."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import BinnedDataset
from lightgbm_tpu.learner import GrowerSpec, grow_tree, make_split_params


def _grow(ds, params, spec, seed=3):
    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    d = ds.device_arrays()
    N = ds.num_rows_padded()
    F = ds.num_used_features
    grad = jnp.asarray(rs.randn(N).astype(np.float32)) * d["valid"]
    hess = (jnp.ones(N, jnp.float32) * 0.25) * d["valid"]
    return grow_tree(
        d["bins"], d["nan_bin"], d["num_bins"], d["mono"], d["is_cat"],
        grad, hess, d["valid"], jnp.ones(F, bool), params, spec,
        valid=d["valid"],
    )


@pytest.fixture(scope="module")
def small_ds():
    rs = np.random.RandomState(11)
    X = rs.randn(4096, 8).astype(np.float32)
    cfg = Config({"max_bin": 63, "min_data_in_leaf": 5})
    return BinnedDataset.from_numpy(X, cfg)


def test_rounds_matches_greedy_unbound_budget(small_ds):
    """With a non-binding leaf budget, round-batched growth IS greedy:
    both split exactly the positive-gain leaves (the sequential
    oracle against the natural-order rounds grower, rounds.py)."""
    cfg = Config({"num_leaves": 512, "max_bin": 63, "min_data_in_leaf": 40,
                  "min_gain_to_split": 0.5})
    params = make_split_params(cfg)
    vals = {}
    variants = {
        "seq": dict(),
        "nat_rounds": dict(rounds_slots=25),
        "nat_rounds_small_k": dict(rounds_slots=4),
    }
    for name, kw in variants.items():
        spec = GrowerSpec(num_leaves=512, num_bins=small_ds.max_num_bin,
                          max_depth=-1, **kw)
        tree, row_leaf = _grow(small_ds, params, spec)
        rl = np.asarray(row_leaf)[: small_ds.num_data]
        vals[name] = np.asarray(tree.leaf_value)[rl]
    for name in variants:
        np.testing.assert_allclose(vals[name], vals["seq"], atol=1e-5,
                                   err_msg=name)


def test_nat_rounds_tree_consistency(small_ds):
    """Natural-order rounds with a BOUND budget: internally consistent
    tree, full budget used, positive gains."""
    cfg = Config({"num_leaves": 31, "max_bin": 63, "min_data_in_leaf": 5})
    params = make_split_params(cfg)
    spec = GrowerSpec(num_leaves=31, num_bins=small_ds.max_num_bin,
                      max_depth=-1, rounds_slots=25)
    tree, row_leaf = _grow(small_ds, params, spec)
    nn = int(tree.num_nodes)
    assert nn == 30
    rl = np.asarray(row_leaf)[: small_ds.num_data]
    lc = np.bincount(rl, minlength=31).astype(float)
    np.testing.assert_allclose(lc, np.asarray(tree.leaf_count))
    assert (np.asarray(tree.node_gain)[:nn] > 0).all()


def test_nat_rounds_max_depth(small_ds):
    cfg = Config({"num_leaves": 64, "max_bin": 63, "min_data_in_leaf": 5})
    params = make_split_params(cfg)
    spec = GrowerSpec(num_leaves=64, num_bins=small_ds.max_num_bin,
                      max_depth=3, rounds_slots=25)
    tree, _ = _grow(small_ds, params, spec)
    assert int(tree.num_nodes) <= 7
    assert int(np.max(np.asarray(tree.leaf_depth))) <= 3


def test_growth_mode_via_train_api():
    rs = np.random.RandomState(5)
    X = rs.randn(3000, 6)
    y = (X[:, 0] + X[:, 1] ** 2 + 0.3 * rs.randn(3000) > 1).astype(float)
    from sklearn.metrics import roc_auc_score

    preds = {}
    for mode in ("exact", "rounds"):
        params = dict(objective="binary", num_leaves=15, min_data_in_leaf=5,
                      verbosity=-1, tpu_growth_mode=mode)
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        bst = lgb.train(params, ds, num_boost_round=5)
        preds[mode] = bst.predict(X)
        assert roc_auc_score(y, preds[mode]) > 0.85


def test_hist_nat_slots_matches_bruteforce():
    import jax.numpy as jnp

    from lightgbm_tpu.learner.histogram import build_gh8, hist_nat_slots

    rs = np.random.RandomState(0)
    N, F, B, S = 4096, 4, 31, 6
    bins = jnp.asarray(rs.randint(0, B, (F, N)).astype(np.int32))
    grad = rs.randn(N).astype(np.float32)
    hess = (rs.rand(N) + 0.5).astype(np.float32)
    gh8 = build_gh8(jnp.asarray(grad), jnp.asarray(hess),
                    jnp.ones(N, jnp.float32))
    slot = rs.randint(0, S + 1, N).astype(np.int32)  # S = trash slot
    out = np.asarray(hist_nat_slots(bins, gh8, jnp.asarray(slot), S, B))
    bn = np.asarray(bins)
    gh3 = np.stack([grad, hess, np.ones(N, np.float32)])
    for s in range(S):
        m = slot == s
        for f in range(F):
            for c in range(3):
                ref = np.bincount(bn[f][m], weights=gh3[c][m], minlength=B)[:B]
                np.testing.assert_allclose(out[s, c, f], ref, atol=2e-4,
                                           rtol=1e-4)


def test_rounds_forced_splits_match_exact(tmp_path):
    """forcedsplits_filename on the rounds grower (ISSUE 14): the
    forced phase applies exactly one plan split per round (so
    Tree::Split leaf numbering matches the BFS plan), then best-gain
    growth resumes. With a non-binding leaf budget both growers are
    greedy past the forced prefix, so the full model must match the
    sequential exact oracle."""
    import json as _json

    rs = np.random.RandomState(7)
    X = rs.randn(4000, 6)
    y = (1.2 * X[:, 0] + X[:, 1] ** 2 + 0.3 * rs.randn(4000) > 0.8
         ).astype(float)
    p = tmp_path / "forced.json"
    p.write_text(_json.dumps({
        "feature": 0, "threshold": 0.0,
        "left": {"feature": 1, "threshold": 0.5},
    }))
    preds, models = {}, {}
    for mode in ("exact", "rounds"):
        params = dict(objective="binary", num_leaves=256,
                      min_data_in_leaf=40, min_gain_to_split=0.5,
                      verbosity=-1, tpu_growth_mode=mode,
                      forcedsplits_filename=str(p))
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        bst = lgb.train(params, ds, num_boost_round=3)
        preds[mode] = bst.predict(X)
        models[mode] = bst._gbdt.models
    for t in models["rounds"]:
        assert int(t.split_feature[0]) == 0  # the forced root split
    np.testing.assert_allclose(preds["rounds"], preds["exact"],
                               rtol=1e-4, atol=1e-5)


def test_grower_capability_matrix_raises(small_ds):
    """The combinations that remain genuinely unsupported after the
    grower unification must still raise instead of silently training
    wrong (ISSUE 14 satellite): the sequential oracle rejects
    voting x forced, and the rounds grower rejects a forced spec with
    no plan and monotone intermediate/advanced combined with voting or
    forced splits."""
    cfg = Config({"num_leaves": 8, "max_bin": 63, "min_data_in_leaf": 5})
    params = make_split_params(cfg)
    B = small_ds.max_num_bin

    # sequential oracle: voting + forced splits
    spec = GrowerSpec(num_leaves=8, num_bins=B, max_depth=-1,
                      voting_k=2, n_forced=1)
    with pytest.raises(ValueError, match="sequential oracle"):
        _grow(small_ds, params, spec)

    # rounds grower: spec.n_forced without the forced= plan
    spec = GrowerSpec(num_leaves=8, num_bins=B, max_depth=-1,
                      rounds_slots=4, n_forced=1)
    with pytest.raises(ValueError, match="forced"):
        _grow(small_ds, params, spec)

    # rounds grower: monotone intermediate/advanced x voting / forced
    for combo in (dict(voting_k=2, axis_name=None),
                  dict(n_forced=1)):
        spec = GrowerSpec(num_leaves=8, num_bins=B, max_depth=-1,
                          rounds_slots=4, mono_mode=2, **combo)
        with pytest.raises(ValueError, match="monotone"):
            _grow(small_ds, params, spec)


def _extras_problem(n=3000, f=8, seed=11):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, f)
    w = rs.randn(f)
    y = X @ w + 0.5 * np.sin(2 * X[:, 0]) + 0.2 * rs.randn(n)
    return X, y


@pytest.mark.parametrize("extra", [
    {"extra_trees": True},
    {"feature_fraction_bynode": 0.6},
    {"cegb_penalty_split": 0.05, "cegb_tradeoff": 1.0},
])
def test_rounds_per_node_extras_quality(extra):
    """extra_trees / feature_fraction_bynode / CEGB on the rounds fast
    path. Quality must stay in family with
    the exact grower's."""
    import lightgbm_tpu as lgb

    X, y = _extras_problem()
    mse = {}
    for mode in ("exact", "rounds"):
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        bst = lgb.train(
            dict({"objective": "regression", "num_leaves": 31,
                  "verbosity": -1, "learning_rate": 0.15,
                  "min_data_in_leaf": 5, "tpu_growth_mode": mode}, **extra),
            ds, num_boost_round=15,
        )
        mse[mode] = float(np.mean((bst.predict(X) - y) ** 2))
    assert mse["rounds"] <= mse["exact"] * 1.3, (extra, mse)
    assert mse["rounds"] < 0.5 * float(np.var(y)), (extra, mse)


def test_rounds_interaction_constraints_structural():
    """Interaction constraints on the rounds path: every root-to-leaf
    path's feature set must fit inside ONE declared group (ColSampler
    interaction filtering semantics)."""
    import lightgbm_tpu as lgb

    X, y = _extras_problem(f=6)
    groups = [[0, 1, 2], [3, 4, 5]]
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train(
        {"objective": "regression", "num_leaves": 31, "verbosity": -1,
         "interaction_constraints": "[0,1,2],[3,4,5]",
         "min_data_in_leaf": 5, "tpu_growth_mode": "rounds"},
        ds, num_boost_round=10,
    )
    model = bst.dump_model()

    def walk(node, path):
        if "split_feature" not in node:
            return
        p2 = path | {node["split_feature"]}
        assert any(p2 <= set(g) for g in groups), p2
        walk(node["left_child"], p2)
        walk(node["right_child"], p2)

    for t in model["tree_info"]:
        walk(t["tree_structure"], set())


# ------------------------------------------------ how a round writes the pool
_POOL_L, _POOL_F, _POOL_B, _POOL_N = 255, 4, 16, 512
_POOL_SIZE = _POOL_L * 3 * _POOL_F * _POOL_B


@pytest.fixture(scope="module")
def pool_body():
    """The while body of a small 255-leaf, 48-slot grower's jaxpr."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.analysis.jaxpr_audit import iter_eqns
    from lightgbm_tpu.learner.rounds import grow_tree_rounds

    spec = GrowerSpec(num_leaves=_POOL_L, num_bins=_POOL_B, max_depth=-1,
                      rounds_slots=48, quant=True, quant_levels=256,
                      has_cat=False)
    F, N = _POOL_F, _POOL_N
    closed = jax.make_jaxpr(
        lambda b, g, h, sc: grow_tree_rounds(
            b, jnp.full(F, -1, jnp.int32), jnp.full(F, _POOL_B, jnp.int32),
            jnp.zeros(F, jnp.int32), jnp.zeros(F, bool), g, h,
            jnp.ones(N, jnp.float32), jnp.ones(F, bool),
            make_split_params(Config({})), spec, gh_scale=sc)
    )(jnp.zeros((F, N), jnp.int32), jnp.zeros(N, jnp.float32),
      jnp.ones(N, jnp.float32), jnp.ones(2, jnp.float32))
    (loop,) = [e for e in iter_eqns(closed) if e.primitive.name == "while"]
    return loop.params["body_jaxpr"].jaxpr


@pytest.mark.parametrize("branch", ["8", "16", "32", "48", "route"])
def test_round_writes_the_pool_once_in_the_carry(pool_body, branch):
    """No rung of the ladder's switch and neither side of the routing
    round's cond returns the histogram pool: a branch hands back its
    round's <= 2S child rows, and the body scatters them into the
    loop's carry ONCE, after the cond (returned whole from a branch,
    the compiled grower turned and copied the pool seven times a round:
    PERF.md section 6, PR 33)."""
    (outer,) = [e for e in pool_body.eqns if e.primitive.name == "cond"]
    route, ladder = (outer.params["branches"][1].jaxpr,
                     outer.params["branches"][0].jaxpr)
    (switch,) = [e for e in ladder.eqns if e.primitive.name == "cond"]
    rungs = dict(zip(["8", "16", "32", "48"],
                     (b.jaxpr for b in switch.params["branches"])))
    assert len(rungs) == len(switch.params["branches"]) == 4
    taken = route if branch == "route" else rungs[branch]
    assert not any(e.primitive.name == "cond" for e in route.eqns)
    # the pool goes IN (a read-only operand) and does not come out
    assert sum(v.aval.size == _POOL_SIZE for v in taken.invars) == 1
    for j in (taken, ladder):
        assert _POOL_SIZE not in {v.aval.size for v in j.outvars}
    assert _POOL_SIZE not in {v.aval.size for v in outer.outvars}
    # every branch returns the same 2S padded rows and their ids
    rows = 2 * 48 * 3 * _POOL_F * _POOL_B
    assert sum(v.aval.size == rows for v in taken.outvars) == 1
    # one scatter into the pool in the whole body, after the cond, and
    # it is the carry's own
    after = pool_body.eqns[pool_body.eqns.index(outer) + 1:]
    writes = [e for e in pool_body.eqns
              if e.primitive.name.startswith("scatter")
              and e.outvars[0].aval.size == _POOL_SIZE]
    assert len(writes) == 1 and writes[0] in after
    assert writes[0].invars[0] in pool_body.invars
    assert writes[0].outvars[0] in pool_body.outvars


def test_round_sections_are_traced_under_their_device_phase(pool_body):
    """The grower names its device work from inside (timer.device_phase):
    the loop body's equations carry the phase of their section in their
    name stack, the innermost last; the pool's one scatter is
    `learner.pool_write`, and what carries none is the loop's own
    plumbing (the ladder's switch, the routing round's cond, the round
    counters)."""
    import re

    from jax.extend.core import ClosedJaxpr, Jaxpr

    from lightgbm_tpu.timer import DEVICE_PHASES, DEVICE_PREFIX

    def phase(eqn, above=None):
        found = re.findall(re.escape(DEVICE_PREFIX) + r"([a-z_.]+)",
                           str(eqn.source_info.name_stack))
        return found[-1] if found else above

    by_phase = {}

    def walk(jaxpr, above):
        # a sub-jaxpr's name stacks are relative to the equation that
        # holds it: its phase reaches down
        for e in jaxpr.eqns:
            here = phase(e, above)
            by_phase.setdefault(here, []).append(e.primitive.name)
            for p in e.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    if isinstance(sub, (ClosedJaxpr, Jaxpr)):
                        walk(getattr(sub, "jaxpr", sub), here)

    walk(pool_body, None)
    n_eqns = sum(len(v) for v in by_phase.values())
    assert set(by_phase) - {None} == {
        "learner.select", "learner.route", "learner.hist",
        "learner.subtract", "learner.split_search", "learner.pool_write"}
    assert set(by_phase) - {None} <= set(DEVICE_PHASES)
    (write,) = [e for e in pool_body.eqns
                if e.primitive.name.startswith("scatter")
                and e.outvars[0].aval.size == _POOL_SIZE]
    assert phase(write) == "learner.pool_write"
    assert "top_k" in by_phase["learner.select"]
    assert "top_k" not in by_phase["learner.split_search"]
    assert len(by_phase[None]) < 0.02 * n_eqns
    assert set(by_phase[None]) <= {
        "cond", "scatter-add", "select_n", "convert_element_type", "add",
        "lt", "le", "ge", "gt", "and", "sub", "min", "eq", "pjit", "jit",
        "broadcast_in_dim", "squeeze", "mul", "concatenate", "reshape",
        "dynamic_slice", "dynamic_update_slice", "clamp"}
