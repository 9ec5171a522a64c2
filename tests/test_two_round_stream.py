"""Streamed two_round text loading (dataset_loader.cpp:210 two_round +
:1399 two-pass extract): the whole-file loader
materializes O(file) host memory, the streamed path O(chunk) + the
binned matrix."""

from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

import lightgbm_tpu as lgb

REPO = Path(__file__).resolve().parent.parent


def _write_csv(path, n=20000, f=6, seed=0, group=False):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, f)
    w = rs.randn(f)
    y = (X @ w > 0).astype(np.float64)
    cols = [y] + [X[:, j] for j in range(f)]
    np.savetxt(path, np.column_stack(cols), delimiter=",", fmt="%.6f")
    return X, y


def test_two_round_matches_whole_file(tmp_path):
    """two_round=true must produce the SAME binned dataset and the same
    trained model as the whole-file loader."""
    p = tmp_path / "data.csv"
    _write_csv(p)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 5}
    ds_full = lgb.Dataset(str(p), params=dict(params))
    ds_full.construct()
    ds_stream = lgb.Dataset(str(p), params=dict(params, two_round=True))
    ds_stream.construct()
    np.testing.assert_array_equal(ds_full._binned.bins,
                                  ds_stream._binned.bins)
    np.testing.assert_array_equal(ds_full._binned.metadata.label,
                                  ds_stream._binned.metadata.label)

    b1 = lgb.train(dict(params), ds_full, num_boost_round=5)
    b2 = lgb.train(dict(params), ds_stream, num_boost_round=5)
    Xp = np.asarray(_write_csv(tmp_path / "probe.csv", n=200, seed=1)[0])
    np.testing.assert_allclose(b1.predict(Xp), b2.predict(Xp), rtol=1e-6)


def test_two_round_sidecars_and_header(tmp_path):
    p = tmp_path / "data.csv"
    X, y = _write_csv(p, n=3000)
    rs = np.random.RandomState(2)
    w = 0.5 + rs.rand(3000)
    np.savetxt(tmp_path / "data.csv.weight", w, fmt="%.5f")
    ds = lgb.Dataset(str(p), params={"two_round": True, "verbosity": -1})
    ds.construct()
    np.testing.assert_allclose(ds._binned.metadata.weight, w, atol=1e-4)


def test_two_round_bounded_memory(tmp_path):
    """A ~120 MB CSV whose float64 matrix is ~115 MB: the streamed
    loader's peak PYTHON-HEAP allocation (tracemalloc covers numpy
    buffers) must stay under half the matrix; the whole-file loader
    peaks at >= the matrix."""
    import tracemalloc

    p = tmp_path / "big.csv"
    rs = np.random.RandomState(0)
    f = 8
    n = 1_600_000
    with open(p, "w") as fh:
        chunk = 100_000
        wv = rs.randn(f)
        for lo in range(0, n, chunk):
            X = rs.randn(chunk, f)
            y = (X @ wv > 0).astype(np.float64)
            np.savetxt(fh, np.column_stack([y] + [X[:, j] for j in range(f)]),
                       delimiter=",", fmt="%.5f")
    mat_bytes = n * (f + 1) * 8

    def peak_of(two_round: bool) -> int:
        tracemalloc.start()
        ds = lgb.Dataset(str(p), params={"two_round": two_round,
                                         "verbosity": -1})
        ds.construct()
        assert ds._binned.num_data == n
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        return peak

    peak_stream = peak_of(True)
    peak_full = peak_of(False)
    # the whole-file loader must hold the float64 matrix; the streamed
    # one holds chunk buffers + the sample + the int bin matrix
    # (~78 MB measured vs ~134 MB, chunk_rows=65536)
    assert peak_full >= mat_bytes, (peak_full, mat_bytes)
    assert peak_stream < peak_full - mat_bytes // 3, (
        peak_stream, peak_full, mat_bytes)


def test_two_round_reference_falls_back_to_train_mappers(tmp_path):
    """A validation Dataset built from a file with reference= must be
    binned with the TRAINING set's mappers — the streamed path cannot
    honor that, so it must fall back to the whole-file loader."""
    ptr = tmp_path / "train.csv"
    pv = tmp_path / "valid.csv"
    _write_csv(ptr, n=4000, seed=0)
    _write_csv(pv, n=1000, seed=5)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "two_round": True}
    tr = lgb.Dataset(str(ptr), params=dict(params))
    tr.construct()
    va = lgb.Dataset(str(pv), params=dict(params), reference=tr)
    va.construct()
    va_plain = lgb.Dataset(str(pv), params={"verbosity": -1}, reference=tr)
    va_plain.construct()
    np.testing.assert_array_equal(va._binned.bins, va_plain._binned.bins)
    # same mappers object semantics: identical bin upper bounds
    for a, b in zip(va._binned.mappers, tr._binned.mappers):
        np.testing.assert_array_equal(
            np.asarray(a.upper_bounds), np.asarray(b.upper_bounds))


def test_no_auto_stream_above_1gb(tmp_path, monkeypatch, capsys):
    """Streaming requires EXPLICIT two_round=true (ADVICE r5 low): a
    text file crossing the 1 GB threshold must NOT silently switch bin
    boundaries to the reservoir-sampled streamed path — it keeps the
    whole-file loader and warns about the opt-in."""
    import os as _os

    p = tmp_path / "data.csv"
    _write_csv(p, n=4000)
    real_getsize = _os.path.getsize
    monkeypatch.setattr(
        _os.path, "getsize",
        lambda q: (2 << 30) if str(q) == str(p) else real_getsize(q),
    )
    streamed = []
    import lightgbm_tpu.parsers as parsers

    real_stream = parsers.load_text_file_two_round
    monkeypatch.setattr(
        parsers, "load_text_file_two_round",
        lambda *a, **k: streamed.append(1) or real_stream(*a, **k),
    )
    ds = lgb.Dataset(str(p), params={"verbosity": 1})
    ds.construct()
    assert not streamed, "auto-enabled streamed two_round without opt-in"
    err = capsys.readouterr()
    assert "two_round" in err.err + err.out  # the parity-deviation warning
    # explicit opt-in still streams
    ds2 = lgb.Dataset(str(p), params={"two_round": True, "verbosity": -1})
    ds2.construct()
    assert streamed
