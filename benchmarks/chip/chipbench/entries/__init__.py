"""The program steps the window drives, built by the program's builders."""
import jax
import numpy as np


def numeric_leaves(tree):
    """{path: value} of the numeric leaves of a pytree, fetched from the
    device in one transfer; an array's value is a (nested) list."""
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    out = {}
    for path, v in flat:
        v = np.asarray(v)
        if np.issubdtype(v.dtype, np.number):
            out[jax.tree_util.keystr(path, simple=True, separator="/")] = v.tolist()
    return out
