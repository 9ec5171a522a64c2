"""LightGBM model text, read without the program, and a plain NumPy
tree walker over raw feature values.

This is the benchmark's independent reading of a model: the tree audit
routes the training rows through it and recomputes the valid margins
with it. Only what the benchmark's models contain is handled (numerical
splits, no missing values in the data); anything else raises."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

# rows are routed in blocks on a few threads (NumPy's indexing and
# comparisons release the interpreter lock): the audit of 2e7 to 3e7 rows
# is outside every metric but inside every run's time
ROUTE_BLOCK = 1 << 20
ROUTE_THREADS = 8

_INT_FIELDS = ("split_feature", "decision_type", "left_child",
               "right_child", "leaf_count")
_FLOAT_FIELDS = ("threshold", "leaf_value")


@dataclass
class PlainTree:
    """One tree in the reference's array layout: internal node ``i``
    sends a row left when ``x[split_feature[i]] <= threshold[i]``; a
    child ``c < 0`` is leaf ``~c``."""

    num_leaves: int
    split_feature: np.ndarray
    threshold: np.ndarray
    decision_type: np.ndarray
    left_child: np.ndarray
    right_child: np.ndarray
    leaf_value: np.ndarray
    leaf_count: np.ndarray


def _array(text: str, dtype) -> np.ndarray:
    text = text.strip()
    return np.array(text.split(" "), dtype=dtype) if text else np.zeros(
        0, dtype)


def parse(model_str: str) -> Tuple[Dict[str, str], List[PlainTree]]:
    """(header fields, trees) of a model string."""
    head, _, rest = model_str.partition("\nTree=")
    header = dict(ln.split("=", 1) for ln in head.split("\n") if "=" in ln)
    body = rest.split("\nend of trees")[0]
    trees = []
    for block in (("Tree=" + body).split("\nTree=") if rest else []):
        kv = dict(ln.split("=", 1) for ln in block.split("\n") if "=" in ln)
        if int(kv.get("num_cat", "0")) or kv.get("is_linear", "0") == "1":
            raise ValueError("categorical or linear trees are not "
                             "handled by the benchmark's plain walker")
        ints = {k: _array(kv.get(k, ""), np.int64) for k in _INT_FIELDS}
        flts = {k: _array(kv.get(k, ""), np.float64)
                for k in _FLOAT_FIELDS}
        trees.append(PlainTree(
            num_leaves=int(kv["num_leaves"]),
            split_feature=ints["split_feature"],
            threshold=flts["threshold"],
            decision_type=ints["decision_type"],
            left_child=ints["left_child"],
            right_child=ints["right_child"],
            leaf_value=flts["leaf_value"],
            leaf_count=ints["leaf_count"],
        ))
    return header, trees


def _route_block(tree: PlainTree, X: np.ndarray) -> np.ndarray:
    node = np.zeros(X.shape[0], np.int64)
    active = np.arange(X.shape[0])
    while active.size:
        nd = node[active]
        x = X[active, tree.split_feature[nd]]
        child = np.where(x <= tree.threshold[nd],
                         tree.left_child[nd], tree.right_child[nd])
        node[active] = child
        active = active[child >= 0]
    return ~node


def route(tree: PlainTree, X: np.ndarray) -> np.ndarray:
    """Leaf index of every row of ``X`` (rows, features), level by
    level over the rows still above a leaf."""
    n = X.shape[0]
    if tree.num_leaves <= 1:
        return np.zeros(n, np.int64)
    if np.any(tree.decision_type & 1):
        raise ValueError("categorical split in a plain tree")
    blocks = [X[i:i + ROUTE_BLOCK] for i in range(0, n, ROUTE_BLOCK)]

    def one(block: np.ndarray) -> np.ndarray:
        if np.isnan(block).any():
            raise ValueError("the plain walker handles no missing values")
        return _route_block(tree, block)

    if len(blocks) == 1:
        return one(blocks[0])
    with ThreadPoolExecutor(ROUTE_THREADS) as pool:
        return np.concatenate(list(pool.map(one, blocks)))


def predict_raw(trees: Sequence[PlainTree], X: np.ndarray) -> np.ndarray:
    """Raw margin (float64): the sum of every tree's leaf value."""
    if X.shape[0] > ROUTE_BLOCK or len(trees) < 2:
        return sum((t.leaf_value[route(t, X)] for t in trees),
                   np.zeros(X.shape[0], np.float64))
    with ThreadPoolExecutor(ROUTE_THREADS) as pool:  # small X: by tree
        return sum(pool.map(lambda t: t.leaf_value[route(t, X)], trees),
                   np.zeros(X.shape[0], np.float64))
