"""Seconds this process spent fetching executables from the persistent
compile cache (``analysis.retrace.compile_counters``: ``cache_load_s``,
JAX's ``cache_retrieval_time_sec`` summed over the hits). A program
without the duration counters yields nothing."""

LAYER, MOVES, SOURCE = "compile", "setup_s", "program_counter"
UNIT, BETTER = "s", "lower"


def read(inp):
    from lightgbm_tpu.analysis.retrace import compile_counters

    return compile_counters().get("cache_load_s")
