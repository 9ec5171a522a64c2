"""Device-resident metric evaluation for the fused training loop.

The reference evaluates metrics on host every iteration
(GBDT::EvalAndCheckEarlyStopping, gbdt.cpp:482). A device->host
readback waits for the device to drain, so per-iteration host eval
would serialize host and device. Instead each metric gets
a traced evaluator closed over padded device label/weight arrays; the
fused iteration computes all metric values into one small (m,) f32
vector per iteration, and the engine fetches a whole chunk of them in a
single device_get.

Semantics mirror lightgbm_tpu.metrics (reference src/metric/*.hpp):
weighted means over valid (non-padding) rows, raw-score transforms per
metric, exact tie-handled AUC via one device sort.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import numpy as np

from .config import Config


def _weights(meta_weight, valid):
    """Effective per-row weights: user weights (or 1) zeroed on padding."""
    import jax.numpy as jnp

    if meta_weight is None:
        return valid
    return meta_weight * valid


def _wmean(vals, w):
    import jax.numpy as jnp

    return jnp.sum(vals * w) / jnp.sum(w)


def _sigmoid(x, s):
    import jax.numpy as jnp

    return 1.0 / (1.0 + jnp.exp(-s * x))


def _make_pointwise(name: str, cfg: Config, label, w):
    """Returns fn(score_1d) -> scalar for pointwise metrics, or None."""
    import jax.numpy as jnp

    eps = 1e-15
    if name == "l2":
        return lambda s: _wmean((s - label) ** 2, w)
    if name == "rmse":
        return lambda s: jnp.sqrt(_wmean((s - label) ** 2, w))
    if name == "l1":
        return lambda s: _wmean(jnp.abs(s - label), w)
    if name == "r2":

        def _r2(s):
            ybar = _wmean(label, w)
            ss_res = jnp.sum(w * (label - s) ** 2)
            ss_tot = jnp.sum(w * (label - ybar) ** 2)
            return jnp.where(ss_tot > 0, 1.0 - ss_res / ss_tot, 0.0)

        return _r2
    if name == "quantile":
        a = cfg.alpha

        def _q(s):
            d = label - s
            return _wmean(jnp.where(d >= 0, a * d, (a - 1.0) * d), w)

        return _q
    if name == "huber":
        a = cfg.alpha

        def _h(s):
            d = jnp.abs(s - label)
            return _wmean(
                jnp.where(d <= a, 0.5 * d * d, a * (d - 0.5 * a)), w
            )

        return _h
    if name == "fair":
        c = cfg.fair_c

        def _f(s):
            x = jnp.abs(s - label)
            return _wmean(c * x - c * c * jnp.log1p(x / c), w)

        return _f
    if name == "poisson":

        def _p(s):
            # score is the raw (log) margin, prediction = exp(score)
            return _wmean(jnp.exp(s) - label * s, w)

        return _p
    if name == "mape":
        return lambda s: _wmean(
            jnp.abs((label - s) / jnp.maximum(1.0, jnp.abs(label))), w
        )
    if name == "gamma":

        def _g(s):
            p = jnp.exp(s)
            return _wmean(
                label / p + s - 1.0
                - jnp.where(label > 0, jnp.log(jnp.maximum(label, eps)), 0.0),
                w,
            )

        return _g
    if name == "gamma_deviance":

        def _gd(s):
            p = jnp.exp(s)
            r = label / jnp.maximum(p, eps)
            return 2.0 * _wmean(r - jnp.log(jnp.maximum(r, eps)) - 1.0, w)

        return _gd
    if name == "tweedie":
        rho = cfg.tweedie_variance_power

        def _t(s):
            p = jnp.exp(s)
            a = label * jnp.exp((1.0 - rho) * s) / (1.0 - rho)
            b = jnp.exp((2.0 - rho) * s) / (2.0 - rho)
            return _wmean(-a + b, w)

        return _t
    if name in ("binary_logloss",):
        sg = cfg.sigmoid

        def _bl(s):
            p = jnp.clip(_sigmoid(s, sg), eps, 1.0 - eps)
            return _wmean(
                -(label * jnp.log(p) + (1.0 - label) * jnp.log(1.0 - p)), w
            )

        return _bl
    if name == "binary_error":
        sg = cfg.sigmoid

        def _be(s):
            p = _sigmoid(s, sg)
            return _wmean(
                ((p > 0.5) != (label > 0.5)).astype(jnp.float32), w
            )

        return _be
    if name in ("cross_entropy", "xentropy"):
        sg = 1.0

        def _xe(s):
            p = jnp.clip(_sigmoid(s, sg), eps, 1.0 - eps)
            return _wmean(
                -(label * jnp.log(p) + (1.0 - label) * jnp.log(1.0 - p)), w
            )

        return _xe
    return None


def _make_auc(label, w):
    """Exact weighted AUC with tie handling via one device sort
    (reference src/metric/binary_metric.hpp AUCMetric). Sorts
    (score, posw, negw) ascending and accumulates per-tie-group
    gp*(cum_neg_before + 0.5*gn) fully vectorized."""
    import jax.numpy as jnp
    from jax import lax

    posw = w * (label > 0)
    negw = w * (label <= 0)

    def _auc(s):
        # padding rows have w == 0 so their position is irrelevant
        sk, pw, nw = lax.sort((s, posw, negw), num_keys=1)
        cn = jnp.cumsum(nw)  # inclusive neg-weight prefix
        cp = jnp.cumsum(pw)
        # tie-group boundaries on the sorted scores
        start = jnp.concatenate([jnp.ones(1, bool), sk[1:] != sk[:-1]])
        # forward-fill the group-start exclusive prefix: cn_excl is
        # non-decreasing, so a cummax over masked starts is a fill
        cn_excl = cn - nw
        cp_excl = cp - pw
        gstart_cn = lax.associative_scan(jnp.maximum, jnp.where(start, cn_excl, -1.0))
        gstart_cp = lax.associative_scan(jnp.maximum, jnp.where(start, cp_excl, -1.0))
        # per-element group-neg total: group end value - group start value;
        # group end via reverse fill of (next-start -> inclusive value)
        end = jnp.concatenate([sk[1:] != sk[:-1], jnp.ones(1, bool)])
        gend_cn = lax.associative_scan(
            jnp.minimum, jnp.where(end, cn, jnp.inf), reverse=True
        )
        gn = gend_cn - gstart_cn
        # each positive contributes w * (neg strictly below + 0.5 * ties)
        auc_sum = jnp.sum(pw * (gstart_cn + 0.5 * gn))
        tot_p = cp[-1]
        tot_n = cn[-1]
        ok = (tot_p > 0) & (tot_n > 0)
        return jnp.where(ok, auc_sum / jnp.maximum(tot_p * tot_n, 1e-30), 1.0)

    return _auc


def _make_multiclass(name: str, cfg: Config, label, w, num_class: int):
    import jax
    import jax.numpy as jnp

    eps = 1e-15
    lab_i = label.astype(jnp.int32)

    if name in ("multi_logloss",):

        def _ml(score):  # (K, N)
            lse = jax.nn.logsumexp(score, axis=0)
            picked = jnp.take_along_axis(score, lab_i[None, :], axis=0)[0]
            return _wmean(lse - picked, w)

        return _ml
    if name == "multi_error":
        k_top = cfg.multi_error_top_k

        def _me(score):
            if k_top <= 1:
                pred = jnp.argmax(score, axis=0)
                return _wmean((pred != lab_i).astype(jnp.float32), w)
            true_s = jnp.take_along_axis(score, lab_i[None, :], axis=0)[0]
            rank = jnp.sum(score > true_s[None, :], axis=0)
            return _wmean((rank >= k_top).astype(jnp.float32), w)

        return _me
    return None


class DeviceEvalSet:
    """All metrics of one dataset as a single traced fn(score)->(m,) f32."""

    def __init__(
        self,
        cfg: Config,
        metric_names: List[str],
        higher_better: List[bool],
        label,
        weight,
        valid,
        num_class: int,
        group=None,
    ):
        import jax.numpy as jnp

        self.names = metric_names
        self.higher_better = higher_better
        w = _weights(weight, valid)
        fns = []
        ndcg_factory = None
        map_factory = None
        for nm in metric_names:
            base = nm.split("@")[0]  # display names may carry "@k"
            if base == "ndcg":
                if ndcg_factory is None:
                    ndcg_factory = _make_ndcg_factory(cfg, label, group)
                fns.append((ndcg_factory(int(nm.split("@")[1])), False))
                continue
            if base == "map":
                if map_factory is None:
                    map_factory = _make_map_factory(cfg, label, group)
                fns.append((map_factory(int(nm.split("@")[1])), False))
                continue
            if num_class > 1 and base in ("multi_logloss", "multi_error"):
                fns.append((_make_multiclass(base, cfg, label, w, num_class), True))
                continue
            if base == "auc":
                fns.append((_make_auc(label, w), False))
                continue
            f = _make_pointwise(base, cfg, label, w)
            if f is not None:
                fns.append((f, False))
                continue
            hf = _make_host_fallback(
                nm, cfg, label, weight, valid, num_class, group=group
            )
            if hf is None:
                raise NotImplementedError(nm)
            fns.append((hf, True))  # gets the full (K, N) score
        self._fns = fns

    def __call__(self, score):
        """score (K, Np); returns (m,) f32."""
        import jax.numpy as jnp

        vals = []
        for f, is_multi in self._fns:
            vals.append(f(score) if is_multi else f(score[0]))
        return jnp.stack(vals) if vals else jnp.zeros(0, jnp.float32)


def _make_ndcg_factory(cfg: Config, label, group):
    """Shared (Q, M) layout for all ndcg@k fns of one dataset; the per-k
    sorts trace into the same step, so XLA CSEs them."""
    import jax.numpy as jnp

    from .learner.ranking import (
        build_query_layout,
        check_label_range,
        default_label_gain,
        ndcg_at,
    )

    npad = int(label.shape[0])
    layout = build_query_layout(np.asarray(group), npad)
    gains = list(cfg.label_gain)
    if not gains:
        gains = list(default_label_gain(int(np.asarray(label).max())))
    check_label_range(np.asarray(label), len(gains))
    gain_dev = jnp.asarray(np.asarray(gains), jnp.float32)
    label_dev = jnp.asarray(label, jnp.float32)

    def factory(k: int):
        def f(s):
            return ndcg_at(layout, s, label_dev, gain_dev, [k])[0]

        return f

    return factory


_warned_host_fallback: set = set()


def _make_host_fallback(nm: str, cfg: Config, label, weight, valid,
                        num_class: int, group=None):
    """Last-resort evaluator for a VALID metric string with no device
    implementation: compute it on host via
    metrics.py inside a `jax.pure_callback`, so the traced eval vector
    keeps its shape and a drift between `supported_names` and the
    device implementations degrades to a warning instead of crashing.

    Warned once per metric name: the callback reintroduces the
    per-iteration device->host sync the device metrics exist to avoid
    — it is a correctness net, not a fast path. Returns None only
    when metrics.py does not know the name either (a genuinely invalid string)."""
    from . import log
    from . import metrics as host_metrics

    base = nm.split("@")[0]
    cls = host_metrics._METRICS.get(base)
    if cls is None:
        return None
    import jax
    import jax.numpy as jnp

    m = cls(cfg)
    # label/weight/valid may be TRACERS (the memoized fused step
    # constructs DeviceEvalSet inside the trace with fold arrays as jit
    # arguments) — so they ride the callback as OPERANDS; all host-side
    # masking/init happens inside the callback body on concrete values
    group_h = None if group is None else np.asarray(group)
    has_w = weight is not None
    if nm not in _warned_host_fallback:
        _warned_host_fallback.add(nm)
        log.warning(
            f"metric {nm!r} has no device implementation; computing it "
            "on host each eval via a callback (one device->host sync "
            "per iteration — expect slower fused-loop throughput)"
        )

    def _host(score, lab, wt, val) -> np.float32:
        mask = np.asarray(val) > 0
        m.init(
            np.asarray(lab)[mask],
            np.asarray(wt)[mask] if has_w else None,
            group_h,
        )
        s = np.asarray(score, np.float64)[:, mask]
        res = m.eval(s if num_class > 1 else s[0])
        return np.float32(res[0][1])

    w_arg = weight if has_w else valid  # placeholder operand when unweighted

    def f(score):
        return jax.pure_callback(
            _host, jax.ShapeDtypeStruct((), jnp.float32),
            score, label, w_arg, valid,
        )

    return f


def _make_map_factory(cfg: Config, label, group):
    """Device MAP@k (map_metric.hpp) over the shared (Q, M) layout —
    keeps metric=map ranking configs on the fused device loop."""
    import jax.numpy as jnp

    from .learner.ranking import build_query_layout, map_at

    npad = int(label.shape[0])
    layout = build_query_layout(np.asarray(group), npad)
    label_dev = jnp.asarray(label, jnp.float32)

    def factory(k: int):
        def f(s):
            return map_at(layout, s, label_dev, [k])[0]

        return f

    return factory


# metric names the device path supports (superset check happens at build)
def supported_names(metric_objs) -> Optional[Tuple[List[str], List[bool]]]:
    """Map host Metric objects -> (display names, higher_better) if all
    are device-implementable, else None. Multi-valued metrics (ndcg@k
    per eval_at entry) expand to one display name per value, matching
    the host metric's eval() tuples."""
    names, hb = [], []
    _ok = {
        "l2", "rmse", "l1", "r2", "quantile", "huber", "fair", "poisson",
        "mape", "gamma", "gamma_deviance", "tweedie", "binary_logloss",
        "binary_error", "cross_entropy", "auc", "multi_logloss",
        "multi_error", "ndcg", "map",
    }
    for m in metric_objs:
        if m.name not in _ok:
            return None
        if m.name in ("ndcg", "map"):
            if getattr(m, "group", None) is None:
                return None
            ks = list(m.config.eval_at) or [1, 2, 3, 4, 5]
            for k in ks:
                names.append(f"{m.name}@{k}")
                hb.append(True)
            continue
        display = m.name
        if m.name == "multi_error":
            k = getattr(m.config, "multi_error_top_k", 1)
            if k > 1:
                display = f"multi_error@{k}"  # match host MultiErrorMetric
        names.append(display)
        hb.append(m.higher_better)
    return names, hb
