"""Guards on the chip-facing seam (all tiny): the kernel module imports
under the installed JAX, one function places the compile cache, the
scripts that measure the chip refuse a CPU backend, the dispatcher does
not hide a scorer that cannot compile, and chip_smoke.py cannot rot."""

from __future__ import annotations

import importlib
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]


def _run(args, **env):
    e = {k: v for k, v in os.environ.items()
         if k != "JAX_COMPILATION_CACHE_DIR"}
    e.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *args], cwd=REPO, env=e,
                          capture_output=True, text=True, timeout=300)


# ------------------------------------------------------------ kernels
def test_kernel_module_imports_without_interpret_flag(monkeypatch):
    """An API removal in jax.experimental.pallas must fail THIS test,
    not every kernel case at once: the module builds its compiler
    params at import, interpret flag or not."""
    monkeypatch.delenv("LGBM_TPU_PALLAS_INTERPRET", raising=False)
    mod = importlib.reload(
        importlib.import_module("lightgbm_tpu.learner.pallas_hist"))
    hist = importlib.import_module("lightgbm_tpu.learner.histogram")
    assert mod._ARBITRARY.vmem_limit_bytes == hist.VMEM_LIMIT_BYTES
    assert mod._VMEM.vmem_limit_bytes == hist.VMEM_LIMIT_BYTES


def test_pallas_gate_is_one_backend_query_and_warns_once(monkeypatch):
    """Off-TPU a closed gate silently selects the XLA formulation; with
    Pallas active a full-width miss warns once per (kernel, reason)."""
    from lightgbm_tpu import log

    hist = importlib.import_module("lightgbm_tpu.learner.histogram")
    monkeypatch.setattr(hist, "_gate_warned", set())
    seen = []
    monkeypatch.setattr(log, "warning", seen.append)
    monkeypatch.delenv("LGBM_TPU_PALLAS_INTERPRET", raising=False)
    assert not hist._pallas_ok("hist_tpu", hist.HIST_BLK)  # cpu backend
    assert not seen
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    assert hist._pallas_ok("hist_tpu", hist.HIST_BLK)
    assert not hist._pallas_ok("hist_tpu", hist.HIST_BLK + 1)
    assert not hist._pallas_ok("hist_tpu", hist.HIST_BLK + 1)
    assert not hist.can_hist_round(hist.HIST_BLK, 10_000, 28, 255, True)
    assert len(seen) == 2 and "hist_tpu" in seen[0] \
        and "hist_round_tpu" in seen[1]


# -------------------------------------------------------------- cache
def test_cache_dir_from_environment_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: nothing in code sets another
    directory (a fresh process, as jax reads the variable at import)."""
    want = str(tmp_path / "placed")
    r = _run(["-c", (
        "import jax\n"
        "from lightgbm_tpu._cache import ensure_compile_cache\n"
        "print(ensure_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n")],
        JAX_COMPILATION_CACHE_DIR=want)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [want, want]


def test_cache_dir_default_is_fixed_inside_the_checkout(monkeypatch):
    import jax

    from lightgbm_tpu import _cache

    assert _cache.CACHE_DIR == str(REPO / ".jax_cache")
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        assert _cache.ensure_compile_cache() == _cache.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == _cache.CACHE_DIR
        # a directory configured by the caller is not overridden either
        jax.config.update("jax_compilation_cache_dir", "/elsewhere")
        assert _cache.ensure_compile_cache() == "/elsewhere"
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_only_cache_module_configures_the_cache():
    """One function decides: no other file sets the cache directory, a
    minimum-compile-time threshold, or the environment variable."""
    offenders = []
    for pat in ("*.py", "*.sh"):
        for f in REPO.rglob(pat):
            rel = f.relative_to(REPO).as_posix()
            if rel.startswith((".", "chiprun_out/")) or rel in (
                    "lightgbm_tpu/_cache.py", "tests/test_chip_seam.py",
                    "tests/test_hist_dtype.py"):
                continue
            src = f.read_text()
            if ("jax_compilation_cache_dir" in src
                    or "persistent_cache_min_compile_time" in src
                    or "JAX_COMPILATION_CACHE_DIR" in src):
                offenders.append(rel)
    assert not offenders, offenders


# ------------------------------------------------------------ scripts
@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
def test_chip_scripts_refuse_a_cpu_backend(script):
    r = _run([script])
    assert r.returncode not in (0, None), (r.stdout, r.stderr)
    assert "'cpu'" in r.stderr, r.stderr
    # no result line: nothing JSON-shaped reaches stdout
    assert "{" not in r.stdout, r.stdout


def test_chip_smoke_help_and_dry_import():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke_under_test", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with pytest.raises(SystemExit) as exc:
        mod.main(["--help"])
    assert exc.value.code == 0
    with pytest.raises(AssertionError, match="boom"):
        mod.check(False, "boom")
    assert (mod.ROWS, mod.FEATS, mod.LEAVES, mod.MAX_BIN) == (
        1_000_000, 28, 255, 255)


# --------------------------------------------------------- dispatcher
def test_dispatcher_reraises_a_scorer_compile_error(rng):
    """A lowering / Mosaic / XLA compile error in the scorer reaches
    the caller and is NOT answered from the host walker — only the
    injected device_put fault degrades (tests/test_resilience.py keeps
    that parity case)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.metrics import default_registry
    from lightgbm_tpu.serving import ModelRegistry

    X = rng.randn(200, 4)
    y = (X[:, 0] > 0).astype(float)
    bst = lgb.train({"objective": "binary", "num_leaves": 4,
                     "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=2)
    reg = ModelRegistry()
    reg.load("m", bst)
    disp = reg._entry("m").dispatcher
    assert disp.host_fallback is not None

    def broken(*a, **k):
        raise NotImplementedError("Mosaic failed to lower the scorer")

    disp.forest.apply = broken
    c = default_registry().counter(
        "lgbmtpu_serve_host_fallback_total", labels=("entry",))
    before = c.value(entry="serve:m")
    with pytest.raises(NotImplementedError, match="Mosaic"):
        reg.predict("m", X[:8].astype(np.float32))
    assert c.value(entry="serve:m") == before
    assert not disp._fallback_warned
