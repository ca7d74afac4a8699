"""An inner join of a seeded fact table onto the pipeline's table, as a
surrogate-key load joins its facts to a dimension.

The fact table has ``rows`` rows (at most as many as the dimension has
unique keys): a key column named ``args["key"]`` and ``attrs`` normal
attribute columns.  Its keys are drawn without replacement from the
dimension rows whose key value is unique there, so each fact row matches
exactly one dimension row.  The step tracks the
fact table in the same index and captures ``fact.join(dimension, on=key,
how="inner")`` with the program's join, which emits in fact-row order.

The fact table is drawn from a generator seeded by the dimension's own key
values, so it follows the run's seed.  The row map returned (per joined
row, the dimension row it comes from) is this module's own stable sort
and ``searchsorted``, never read back from the program.

The deployment walks the join's row map on the device.  A program without
that walk (``repro.kernels.ops.gather_walk``) answers the join on the host
alone: its window runs nothing on the chip, so it cannot run this
configuration, and the step stops it at set-up, before the fact table is made.
"""
import numpy as np


def require_device_walk() -> None:
    """Raise unless the program can walk a row map on the device."""
    from repro.kernels import ops

    if not hasattr(ops, "gather_walk"):
        raise RuntimeError(
            "join_dim: this program has no device walk of a row map "
            "(repro.kernels.ops.gather_walk), so it would answer the join on "
            "the host alone; the configuration walks it on the device")


def fact_table(key_values: np.ndarray, rows: int, attrs: int, key: str):
    """The fact table's columns, and the dimension rows its keys were
    drawn from."""
    entropy = np.frombuffer(key_values[:16].tobytes(), dtype=np.uint32)
    rng = np.random.default_rng(np.random.SeedSequence(entropy.tolist()))
    _, first, counts = np.unique(key_values, return_index=True,
                                 return_counts=True)
    unique_rows = np.sort(first[counts == 1])
    drawn = rng.choice(unique_rows, size=min(rows, len(unique_rows)),
                       replace=False)
    cols = {key: key_values[drawn]}
    for j in range(attrs):
        cols[f"f{j}"] = rng.normal(0, 1, size=len(drawn)).astype(np.float32)
    return cols, drawn


def row_map(dim_keys: np.ndarray, fact_keys: np.ndarray) -> np.ndarray:
    """Per fact row (so per joined row), the dimension row with its key."""
    order = np.argsort(dim_keys, kind="stable")
    return order[np.searchsorted(dim_keys[order], fact_keys)]


def apply(d, step, values):
    from repro.dataprep.table import Table
    from repro.dataprep.tracked import track

    require_device_walk()
    args = step["args"]
    key = args["key"]
    dim_keys = values.values(key)
    cols, _ = fact_table(dim_keys, args["rows"], args["attrs"], key)
    fact = track(Table.from_columns(cols), d.index, f"{d.dataset_id}_fact")
    joined = fact.join(d, on=key, how="inner")
    return joined, row_map(dim_keys, cols[key])
