"""Seconds XLA and Mosaic really compiled in this process: JAX times
compile-or-load-from-cache as one backend-compile event, so this is
``backend_compile_s - cache_load_s`` of
``analysis.retrace.compile_counters``. Near 0 on a warm cache. A program
without the duration counters yields nothing."""

LAYER, MOVES, SOURCE = "compile", "setup_s", "program_counter"
UNIT, BETTER = "s", "lower"


def read(inp):
    from lightgbm_tpu.analysis.retrace import compile_counters

    c = compile_counters()
    if "backend_compile_s" not in c or "cache_load_s" not in c:
        return None
    return c["backend_compile_s"] - c["cache_load_s"]
