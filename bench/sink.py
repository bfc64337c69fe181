"""Reading the engine's drained sink back, and holding its rows to the
reference."""
from __future__ import annotations

import numpy as np


def read_rows(pipe, ids: np.ndarray):
    """The sink rows of `ids` in one transfer: (rows, materialized mask,
    count of master vertices that are not among ids)."""
    import jax

    sink, seen = jax.device_get((pipe.sink, pipe.sink_seen))
    t = pipe.part.t
    m, s = t.master[ids], t.master_slot[ids]
    has = m >= 0
    rows = np.zeros((len(ids), sink.shape[-1]), np.float32)
    rows[has] = sink[m[has], s[has]]
    got = has.copy()
    got[has] = seen[m[has], s[has]]
    extra = int(np.count_nonzero(t.master >= 0)) - int(has.sum())
    return rows, got, extra


def rows_numbers(ref, rows_list) -> tuple:
    """(widest error over the typical bar, widest over the worst-case bar,
    rows missing) of row sets [(rows, materialized, extra)] that follow
    the reference's row order."""
    gap, worst, missing = 0.0, 0.0, 0
    for r, got, extra in rows_list:
        got = got & np.all(np.isfinite(r), axis=1)
        missing += int((~got).sum()) + abs(extra)
        if got.any():
            err = np.abs(r[got] - ref.ref[got])
            gap = max(gap, float((err / ref.typical[got]).max()))
            worst = max(worst, float((err / ref.worst[got]).max()))
    return gap, worst, missing
