"""The plain reference: connected components by scipy, on the host.

Independent of the program under test: it takes the edges as the
generator drew them and returns, for every vertex, the minimum vertex id
of its component, which is the labelling the system guarantees.
"""
from __future__ import annotations

import numpy as np


def component_labels(src: np.ndarray, dst: np.ndarray, n: int) -> np.ndarray:
    """scipy connected components, each labelled by its minimum vertex id."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components

    # bool entries: duplicate edges OR together instead of summing
    adj = sp.coo_matrix((np.ones(len(src), bool), (src, dst)),
                        shape=(n, n)).tocsr()
    _, comp = connected_components(adj, directed=False)
    # vertices are visited in id order, so a component's first index is
    # its minimum id
    _, first = np.unique(comp, return_index=True)
    return first[comp].astype(np.int32)


def mismatches(got: np.ndarray, want: np.ndarray) -> int:
    """Vertices whose label differs from the reference's (all of them if
    the shapes differ)."""
    got = np.asarray(got)
    if got.shape != want.shape:
        return int(want.size)
    return int(np.count_nonzero(got != want))
