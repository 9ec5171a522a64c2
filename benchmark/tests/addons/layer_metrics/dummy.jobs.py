"""A per-layer metric added as a file only (the data-driven proof)."""

LAYER, MOVES, SOURCE = "engine", "train_trees_per_s", "program_counter"
UNIT, BETTER = "jobs", "higher"


def read(inp):
    return inp.rec.obs.get("jobs")
