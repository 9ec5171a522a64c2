"""Per tree, the chip's idle time inside the job spans that none of the
other four parts of ``engine.host_ms_per_tree`` covers: the rest of
``engine.train`` (parameter resolution, ``engine.callbacks``,
``engine.finish``) and whatever no program span covers. This reader
also logs the whole table, every program span inside the job spans with
its calls, wall, self and idle seconds (``harness/program_spans.py``)."""

from benchmark.harness import cellrun, program_spans

LAYER, MOVES, SOURCE = "engine", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    trees = inp.rec.obs.get("trees")
    split = program_spans.split_for(inp) if trees else None
    if split is None:
        return None
    for line in program_spans.table(split):
        cellrun.log(line)
    return split.idle_s[program_spans.OTHER] / trees * 1e3
