"""Subprocess worker for the SIGKILL crash/resume chaos test: run the
CLI train task in a real process so a ``round:N:kill`` fault plan
(LGBMTPU_FAULT_PLAN) can SIGKILL it mid-boosting — no atexit, no
finally, no flush — and a second invocation with ``resume=auto`` must
reproduce the uninterrupted model bit for bit."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()



def main() -> int:
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    # the crash, resume and clean runs share the suite's persistent
    # compile cache: GBDT.__init__ calls _cache.ensure_compile_cache
    from lightgbm_tpu.cli import main as cli_main

    return cli_main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
