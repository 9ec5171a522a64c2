"""The traversal's category test as bit words (PR 37): a categorical
node's set of left-going bins rides `tree.traverse_tree_bins`'s per-node
table as 16-bit words (`tree.cat_mask_words`) and a row's verdict is a
word select and a bit test, where the parent gathered one element of
`node_cat_mask` a row a level. The leaves must be the gather's, row for
row: against a plain NumPy walk over the binned matrix on random trees,
and against the host walker (`Tree.predict_leaf` on the raw rows) on
trained models; on both routes of `take_cols` (the one-hot kernel,
interpreted, and the `jnp.take` fallback), EFB-bundled, and replicated
under a two-device `row_mesh`."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.learner import GrowerSpec
from lightgbm_tpu.learner.histogram import HIST_BLK
from lightgbm_tpu.parallel.data_parallel import _tree_arrays_structure
from lightgbm_tpu.tree import (
    CAT_WORD_BITS, cat_mask_words, num_cat_words, traverse_tree_bins,
    tree_to_arrays)


@pytest.fixture
def route(request, monkeypatch):
    """take_cols by the interpreted one-hot kernel, or by jnp.take; the
    jit caches key on shapes, not on the environment."""
    import jax

    jax.clear_caches()
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET",
                       "1" if request.param == "kernel" else "0")
    yield request.param
    jax.clear_caches()


# ---------------------------------------------------------------- words
@pytest.mark.parametrize("B,W", [
    (2, 1), (16, 1), (17, 2), (24, 2), (25, 2), (63, 4), (255, 16), (256, 16)])
def test_packed_words_hold_every_bin_of_every_node(B, W):
    rs = np.random.RandomState(B)
    max_nodes = 37
    mask = rs.rand(max_nodes, B) < 0.4
    mask[0] = True  # a full set: the largest word a node can carry
    mask[1] = False
    words = np.asarray(cat_mask_words(mask))
    assert num_cat_words(B) == W
    assert words.shape == (W, max_nodes) and words.dtype == np.float32
    # exact non-negative integers an f32 one-hot contraction returns whole
    assert (words >= 0).all() and (words < 2 ** 24).all()
    ints = words.astype(np.int64)
    assert np.array_equal(ints, words)
    b = np.arange(W * CAT_WORD_BITS)
    bit = (ints[b // CAT_WORD_BITS] >> (b % CAT_WORD_BITS)[:, None]) & 1
    assert np.array_equal(bit[:B].T.astype(bool), mask)
    assert not bit[B:].any()  # the last word's padding


# --------------------------------------------------------- random trees
def _random_tree(rs, n_nodes, max_nodes, G, B, is_cat_col, nan_bin,
                 default_left_share=0.5):
    """TreeArrays of a random tree in the grower's convention (node i
    splits a leaf: left keeps its number, right is leaf i + 1)."""
    left = np.zeros(max_nodes, np.int32)
    right = np.zeros(max_nodes, np.int32)
    where = {0: None}  # leaf -> (parent node, side)
    for i in range(n_nodes):
        leaf = int(rs.choice(sorted(where)))
        at = where[leaf]
        if at is not None:
            (left if at[1] == 0 else right)[at[0]] = i
        left[i], right[i] = ~leaf, ~(i + 1)
        where[leaf], where[i + 1] = (i, 0), (i, 1)
    feat = rs.randint(0, G, max_nodes).astype(np.int32)
    import jax.numpy as jnp

    return _tree_arrays_structure(
        GrowerSpec(num_leaves=max_nodes + 1, num_bins=B, max_depth=-1)
    )._replace(
        num_nodes=jnp.int32(n_nodes), node_feature=jnp.asarray(feat),
        node_bin=jnp.asarray(rs.randint(0, B, max_nodes).astype(np.int32)),
        node_default_left=jnp.asarray(
            rs.rand(max_nodes) < default_left_share),
        node_cat=jnp.asarray(is_cat_col[feat]),
        node_cat_mask=jnp.asarray(
            (rs.rand(max_nodes, B) < 0.5) & is_cat_col[feat][:, None]),
        node_left=jnp.asarray(left), node_right=jnp.asarray(right))


def _walk(arrays, bins, nan_bin):
    """Plain walk of the binned rows, NumPy's own element gathers."""
    a = {k: np.asarray(v) for k, v in arrays._asdict().items()}
    n = bins.shape[1]
    if int(a["num_nodes"]) == 0:
        return np.zeros(n, np.int64)
    cur = np.zeros(n, np.int64)
    rows = np.arange(n)
    while (cur >= 0).any():
        k = np.maximum(cur, 0)
        f = a["node_feature"][k]
        b = bins[f, rows]
        num = (b <= a["node_bin"][k]) | (
            a["node_default_left"][k] & (nan_bin[f] >= 0) & (b == nan_bin[f]))
        go_left = np.where(a["node_cat"][k], a["node_cat_mask"][k, b], num)
        nxt = np.where(go_left, a["node_left"][k], a["node_right"][k])
        cur = np.where(cur >= 0, nxt, cur)
    return ~cur


# name: (leaves, split nodes, columns, bins, categorical columns)
_TREES = {
    "mixed_255_leaves": (255, 254, 9, 255, (0, 2, 3, 7)),
    "every_row_in_the_other_bin": (31, 30, 5, 63, (0, 1, 2, 3, 4)),
    "nan_goes_default_left": (63, 62, 6, 17, (1, 4)),
    "stump": (31, 0, 4, 255, (0, 1)),
    "one_split": (2, 1, 3, 25, (0, 1, 2)),
    "partly_grown": (255, 40, 8, 256, (0, 5)),
}


@pytest.mark.parametrize("route", ["kernel", "take"], indirect=True)
@pytest.mark.parametrize("name", list(_TREES))
def test_the_bit_test_reaches_the_leaves_of_a_plain_walk(name, route):
    import jax
    import jax.numpy as jnp

    L, n_nodes, G, B, cats = _TREES[name]
    rs = np.random.RandomState(len(name))
    is_cat_col = np.isin(np.arange(G), cats)
    # a numerical column's NaN bin is its last; a categorical column's
    # last bin is its other bin, which no set holds (binning.py)
    nan_bin = np.where(is_cat_col | (rs.rand(G) < 0.3), -1, B - 1) \
        .astype(np.int32)
    N = 2 * HIST_BLK
    bins = rs.randint(0, B, (G, N)).astype(np.int32)
    arrays = _random_tree(
        rs, n_nodes, L - 1, G, B, is_cat_col, nan_bin,
        default_left_share=0.9 if name == "nan_goes_default_left" else 0.5)
    if name == "every_row_in_the_other_bin":
        bins[:, ::2] = B - 1
        arrays = arrays._replace(
            node_cat_mask=arrays.node_cat_mask.at[:, B - 1].set(False))
    if name == "nan_goes_default_left":
        bins[:, ::3] = B - 1
    want = _walk(arrays, bins, nan_bin)
    got = jax.jit(traverse_tree_bins)(
        arrays, jnp.asarray(bins), jnp.asarray(nan_bin))
    np.testing.assert_array_equal(np.asarray(got), want)
    if n_nodes:
        assert len(np.unique(want)) > 1


# ------------------------------------------------------- trained models
def _table(rs, n):
    """Two categorical columns (one past max_cat_to_onehot), a numerical
    column with NaN, and four mutually exclusive sparse columns that EFB
    merges into one device column."""
    carrier = rs.randint(0, 20, n).astype(np.float64)
    origin = np.minimum(rs.zipf(1.5, n), 60).astype(np.float64)
    dep = rs.rand(n) * 24
    dep[rs.rand(n) < 0.1] = np.nan
    owner = rs.randint(0, 8, n)
    sparse = np.zeros((n, 4))
    for j in range(4):
        sparse[owner == j, j] = rs.rand(int((owner == j).sum())) + 0.5
    X = np.column_stack([carrier, origin, dep, sparse])
    z = (np.sin(carrier) + (origin % 3 == 0) + np.nan_to_num(dep, nan=30) / 24
         + sparse @ np.array([1.0, -1.0, 0.5, -0.5]))
    y = (z + 0.3 * rs.randn(n) > np.median(z)).astype(np.float64)
    return X, y


_PARAMS = {
    "objective": "binary", "num_leaves": 31, "max_bin": 63,
    "min_data_in_leaf": 5, "categorical_feature": "0,1", "verbosity": -1,
    "min_data_per_group": 10, "cat_smooth": 1.0,
}


@pytest.fixture(scope="module")
def trained():
    rs = np.random.RandomState(11)
    X, y = _table(rs, 20000)
    Xv, yv = _table(rs, HIST_BLK)
    Xv[::3, 0] = 77  # carriers and airports no training row has
    Xv[1::3, 1] = 999
    Xv[2::7, 0] = -1  # a negative code
    out = {}
    for name, bundle in (("plain", False), ("efb", True)):
        params = dict(_PARAMS, enable_bundle=bundle)
        ds = lgb.Dataset(X, label=y, params=dict(params),
                         free_raw_data=False).construct()
        vs = lgb.Dataset(Xv, label=yv, reference=ds).construct()
        bst = lgb.train(params, ds, num_boost_round=3, valid_sets=[vs])
        out[name] = (bst, vs, Xv)
    return out


@pytest.mark.parametrize("route", ["kernel", "take"], indirect=True)
@pytest.mark.parametrize("name", [
    "valid_only_category", "efb_bundle", "replicated_on_two_devices"])
def test_device_leaves_are_the_host_walkers(trained, name, route):
    """Every tree of a trained model over the valid rows: the device's
    leaves equal `Tree.predict_leaf` on the raw rows, with valid-only,
    negative and missing values routed right at categorical nodes."""
    import jax
    from jax.sharding import Mesh

    from lightgbm_tpu.learner.histogram import row_mesh

    bst, vs, Xv = trained["efb" if name == "efb_bundle" else "plain"]
    g = bst._gbdt
    binned = vs._binned
    dev = binned.device_arrays()
    assert (dev.get("bundle") is not None) == (name == "efb_bundle")
    if name == "efb_bundle":
        assert dev["bins"].shape[0] < binned.num_total_features
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",)) \
        if name == "replicated_on_two_devices" else None

    def on_rows(arrays, bins, nan_bin, bundle):
        with row_mesh(mesh):
            return traverse_tree_bins(arrays, bins, nan_bin, bundle)

    walk = jax.jit(on_rows)
    n_cat = 0
    for t in g.models:
        arrays = tree_to_arrays(t, binned)
        n_cat += int(np.asarray(arrays.node_cat).sum())
        args = (arrays, dev["bins"], dev["nan_bin"], dev.get("bundle"))
        got = np.asarray(walk(*args))[:len(Xv)]
        np.testing.assert_array_equal(got, t.predict_leaf(Xv))
        if mesh is not None and route == "kernel":
            # replicated: HIST_BLK rows do not split over two devices
            assert "shard_map" in str(jax.make_jaxpr(on_rows)(*args))
    assert n_cat >= 3


# ----------------------------------------------------- the traced table
def _while_tables(has_cat, max_nodes=254, B=255, G=8, N=HIST_BLK):
    """(dtype, rows, columns) of the 2-D operands of the traversal's
    `while`: the per-node table and the bin matrix."""
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(0)
    is_cat = np.arange(G) % 2 == 0
    arrays = _random_tree(rs, 9, max_nodes, G, B, is_cat,
                          np.full(G, -1, np.int32))
    jaxpr = jax.make_jaxpr(
        lambda a, b, n: traverse_tree_bins(a, b, n, has_cat=has_cat))(
        arrays, jnp.zeros((G, N), jnp.int32), jnp.full(G, -1, jnp.int32))
    loops = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "while"]
    assert len(loops) == 1
    return [(str(v.aval.dtype),) + v.aval.shape for v in loops[0].invars
            if v.aval.ndim == 2]


@pytest.mark.parametrize("has_cat,rows", [(False, 8), (True, 8 + 16)])
def test_the_per_node_table_of_the_traced_traversal(has_cat, rows):
    """A numerical table keeps the parent's 8-row table; a categorical
    one adds the W word rows to the SAME table, and the loop carries no
    (max_nodes, B) mask."""
    assert sorted(_while_tables(has_cat)) == [
        ("float32", rows, 254), ("int32", 8, HIST_BLK)]
