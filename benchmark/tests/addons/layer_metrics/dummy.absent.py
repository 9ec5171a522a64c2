"""A reader that finds nothing to read: the harness must leave it out."""

LAYER, MOVES, SOURCE = "engine", "train_trees_per_s", "program_counter"
UNIT, BETTER = "things", "higher"


def read(inp):
    return inp.rec.obs.get("no such observation")
