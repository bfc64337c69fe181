"""Operations an exact incremental engine needs, counted from the stream.

A launch's new edges and new features change the layer-0 output of every
destination of a new edge and of every newly featured vertex. A vertex
whose layer-l output changed changes its own layer-(l+1) output and that
of each of its out-neighbours in the graph ingested so far. Each changed
output of a SAGE layer d_l -> d_{l+1} costs two matmul rows,
2 * 2 * d_l * d_{l+1} operations (W_self x and W_neigh agg); the mean
itself, the bias and the relu are left out, as are launches' own
coalescing (a vertex changed twice in one launch counts once).
"""
from __future__ import annotations

import numpy as np


def exact_flops(launch_edges: list, launch_new: list, dims) -> float:
    """launch_edges: [E_k, 2] new edges of each launch, in order;
    launch_new: ids first featured in each launch; dims: layer widths."""
    src = np.zeros(0, np.int64)
    dst = np.zeros(0, np.int64)
    total = 0.0
    for e, new in zip(launch_edges, launch_new):
        e = np.asarray(e, np.int64).reshape(-1, 2)
        src = np.concatenate([src, e[:, 0]])
        dst = np.concatenate([dst, e[:, 1]])
        changed = np.union1d(e[:, 1], np.asarray(new, np.int64))
        for li in range(len(dims) - 1):
            if li:
                out = dst[np.isin(src, changed)]
                changed = np.union1d(changed, out)
            total += len(changed) * 4.0 * dims[li] * dims[li + 1]
    return total
