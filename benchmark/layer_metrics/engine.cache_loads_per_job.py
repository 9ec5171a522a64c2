"""Executables loaded from the persistent cache inside the window, per
job: each one means a new Booster traced and lowered its fused step
again and fetched the executable from disk. 0 where the step memo holds
(one chip); 1 where it does not (``tree_learner=data``: ``boosting.py``
``memo_ok`` requires ``_dp is None``)."""

LAYER, MOVES, SOURCE = "engine", "train_trees_per_s", "program_counter"
UNIT, BETTER = "loads", "lower"


def read(inp):
    obs = inp.rec.obs
    if not obs.get("jobs") or "cache_loads_in_window" not in obs:
        return None
    return obs["cache_loads_in_window"] / obs["jobs"]
