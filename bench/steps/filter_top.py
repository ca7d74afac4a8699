"""A filter that keeps the ``keep`` rows with the largest source values of
``col``, in row order (ties broken by row order), as the repository's
Table VIII COMPAS pipeline keeps its 6907 rows."""
import numpy as np


def apply(d, step, values):
    args = step["args"]
    v = values.values(args["col"])
    kept = np.sort(np.argsort(-v, kind="stable")[:args["keep"]])
    mask = np.zeros(len(v), dtype=bool)
    mask[kept] = True
    return d.filter_rows(mask, op_name=args.get("op_name", "filter")), kept
