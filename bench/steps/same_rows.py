"""A step that keeps every row where it is (impute, normalize, clip,
binarize, discretize, select or one-hot columns): the tracked table's
method ``op`` with ``args``."""


def apply(d, step, values):
    return getattr(d, step["op"])(**step.get("args", {})), None
