"""Share of the training table's categorical cells (rows x categorical
columns) that sit in a column's OTHER bin: a category cut at binning
(the rare tail, or past ``max_bin``), a negative value or a NaN. Such a
row has lost its category for good: no split sends the other bin left.
From the program's gauges ``lgbmtpu_dataset_cat_other_rows`` and
``lgbmtpu_dataset_columns{kind="categorical"}``, set when the training
Dataset is constructed, and the rows the driver observed. Nothing from a
program without the gauges or a table without a categorical column."""

LAYER, MOVES, SOURCE = "dataset", "setup_s", "program_counter"
UNIT, BETTER = "%", "lower"

OTHER = "lgbmtpu_dataset_cat_other_rows"
COLUMNS = "lgbmtpu_dataset_columns"


def read(inp):
    from lightgbm_tpu.obs.metrics import default_registry

    snap = default_registry().snapshot()
    other, columns = snap.get(OTHER), snap.get(COLUMNS)
    rows = inp.rec.obs.get("rows")
    if not other or not columns or not rows:
        return None
    cat = sum(v for labels, v in columns.items()
              if 'kind="categorical"' in labels)
    if not cat:
        return None
    return 100.0 * sum(other.values()) / (rows * cat)
