"""Bin-matrix bytes that went host -> device in this process
(``lgbmtpu_dataset_push_bytes_total{kind="bins"}``, process lifetime):
one copy of the train and valid bins where a Dataset's device copy is
resident and every Booster shares it (a valid set under a data mesh is
replicated: one copy a chip), one more copy per job where a Booster
pushes again. Nothing from a program that does not count its pushes."""

LAYER, MOVES, SOURCE = "dataset", "setup_s", "program_counter"
UNIT, BETTER = "GB", "lower"

COUNTER = "lgbmtpu_dataset_push_bytes_total"


def read(inp):
    from lightgbm_tpu.obs.metrics import default_registry

    series = default_registry().snapshot().get(COUNTER)
    if not series:
        return None
    return sum(v for labels, v in series.items()
               if 'kind="bins"' in labels) / 1e9
