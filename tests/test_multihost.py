"""Multi-host distributed training (reference src/network/ socket
cluster -> jax.distributed multi-controller; SURVEY §2.8).

Spawns two REAL processes connected by jax.distributed (Gloo CPU
collectives standing in for DCN), each holding half the rows
(pre_partition), allgathering binning samples, and growing one tree
through the data-parallel grower — both ranks must produce the
identical tree (the reference's lockstep guarantee)."""

import socket
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# Root cause of the long-standing "two pre-existing multihost failures"
# (docs/DESIGN_DECISIONS.md "Multihost tests xfail ..."): some jaxlib
# builds ship an XLA:CPU backend without cross-process collective
# support, and jax.distributed workers then die inside
# multihost_utils.process_allgather with exactly this error. That is an
# environment limitation, not a regression — xfail on the signature so
# the tier-1 gate stops carrying silent known-failures, while ANY other
# worker failure (real lockstep/parity breaks) still fails loudly.
# strict=False: on a jaxlib with Gloo CPU collectives the tests run
# and must pass.
#
# The signature drifts across jaxlib releases ("aren't implemented" vs
# "are not supported", capitalization, backend spelling), so match a
# small family of variants rather than one exact string — but ONLY
# this family: any other worker error still fails loudly.
_ENV_LIMIT_PATTERNS = (
    r"[Mm]ultiprocess computations? aren'?t implemented on the CPU "
    r"backend",
    r"[Mm]ulti[- ]?process (computations?|collectives?) (are not|aren'?t) "
    r"(supported|implemented) on (the )?(CPU|cpu)",
    r"[Cc]ross-process collectives? (are not|aren'?t) "
    r"(supported|implemented).*(CPU|cpu)",
)


def _env_limit_match(out: str):
    import re

    for pat in _ENV_LIMIT_PATTERNS:
        m = re.search(pat, out)
        if m:
            return m.group(0)
    return None


def _xfail_if_env_limited(outs) -> None:
    hits = [_env_limit_match(out) for out in outs]
    if any(hits):
        sig = next(h for h in hits if h)
        pytest.xfail(
            f"jaxlib CPU backend lacks cross-process collectives "
            f"({sig!r}); see docs/DESIGN_DECISIONS.md"
        )


@pytest.mark.timeout(600)
def test_two_process_data_parallel_lockstep():
    worker = Path(__file__).parent / "_multihost_worker.py"
    port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(i), "2", str(port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=540)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multihost worker timed out")
        outs.append(out)
    _xfail_if_env_limited(outs)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {i} failed:\n{out[-2000:]}"
        assert "MULTIHOST_OK" in out, out[-2000:]
    # both ranks report the same tree
    lines = [
        next(ln for ln in out.splitlines() if ln.startswith("MULTIHOST_OK"))
        for out in outs
    ]
    sig = [ln.split("nodes=")[1] for ln in lines]
    assert sig[0] == sig[1], lines


def test_two_process_full_train_api(tmp_path):
    """run_distributed (the dask _train analog): 2 real processes, full
    lgb.train — global binning, per-iteration eval, early stopping,
    rank-0 save — byte-identical models on both ranks."""
    worker = Path(__file__).parent / "_multihost_train_worker.py"
    port = _free_port()
    out_model = tmp_path / "dist_model.txt"
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(i), "2", str(port),
             str(out_model)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=540)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multihost train worker timed out")
        outs.append(out)
    _xfail_if_env_limited(outs)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {i} failed:\n{out[-3000:]}"
        assert "MULTIHOST_TRAIN_OK" in out, out[-3000:]
    lines = [
        next(ln for ln in out.splitlines()
             if ln.startswith("MULTIHOST_TRAIN_OK"))
        for out in outs
    ]
    sigs = [dict(kv.split("=") for kv in ln.split()[1:]) for ln in lines]
    assert sigs[0]["model"] == sigs[1]["model"], lines  # identical models
    assert sigs[0]["best_it"] == sigs[1]["best_it"], lines
    assert float(sigs[0]["auc"]) > 0.9, lines
    l1 = [
        next(ln for ln in out.splitlines()
             if ln.startswith("MULTIHOST_L1_OK"))
        for out in outs
    ]
    l1s = [dict(kv.split("=") for kv in ln.split()[1:]) for ln in l1]
    assert l1s[0]["model"] == l1s[1]["model"], l1  # renewal objective too
    assert out_model.exists()  # rank-0 save landed
    # the saved model loads and predicts in THIS process
    import lightgbm_tpu as lgb

    bst = lgb.Booster(model_file=out_model)
    assert np.isfinite(bst.predict(np.zeros((2, 8)))).all()
