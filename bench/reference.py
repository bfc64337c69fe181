"""The plain reference: GraphSAGE-mean over a static snapshot, in float32
at the highest matmul precision, with the bars that bound the engine's
bf16 rounding. Imports nothing of the program.

A SAGE-mean layer is h_v = W_self x_v + b + W_neigh mean_{u -> v} x_u,
then relu on every layer but the last. The snapshot is the multiset of
edges ingested so far; only vertices that received a feature send
messages, and a vertex with no in-edge aggregates zero.

The bars are copied from the bring-up smoke (`chip_smoke.py`). On a TPU
an f32 matmul at the default precision rounds both operands to bf16
(relative error <= u = 2**-8) and sums the products in f32. With an error
bound B on the layer's input (0 for the streamed features) the output
error is, to first order in u, at most
    B' = ((1+2u) B + 2u|x|) @ |W_s| + ((1+2u) B_agg + 2u|agg|) @ |W_n|,
B_agg the mean of B over the in-neighbours. `worst` is that bound plus
F32_SLACK (1 + |ref|) for the f32 summation order: it holds for any
rounding pattern and is loose. `typical` is the bound for independent
mean-zero rounding errors (Hoeffding), sums of squares in quadrature:
    S'^2 = 4u^2 (x^2 @ W_s^2 + agg^2 @ W_n^2) + S^2 @ W_s^2
           + S_agg^2 @ W_n^2,   S_agg^2 = mean(S^2) / in-degree,
and a sum leaves LAMBDA S with probability 2 exp(-LAMBDA^2 / 2).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

U_BF16 = 2.0 ** -8
F32_SLACK = 1e-4
LAMBDA = 8.0


@dataclass(frozen=True)
class Snapshot:
    ids: np.ndarray        # [n] sorted global ids of the featured vertices
    senders: np.ndarray    # [m] int32 rows into ids
    receivers: np.ndarray  # [m] int32
    x: np.ndarray          # [n, d_in] float32 features


def snapshot(edges: np.ndarray, feats: dict) -> Snapshot:
    """The static graph of `edges` over the vertices that carry a feature,
    relabelled 0..n-1. Every other id is isolated and featureless, so it
    changes no row."""
    ids = np.asarray(sorted(feats), np.int64)
    keep = np.isin(edges[:, 0], ids) & np.isin(edges[:, 1], ids)
    e = np.searchsorted(ids, edges[keep]).astype(np.int32)
    x = np.stack([feats[int(v)] for v in ids]).astype(np.float32)
    return Snapshot(ids=ids, senders=e[:, 0], receivers=e[:, 1], x=x)


def layer_weights(params: dict) -> list:
    """[(W_self, b, W_neigh), ...] in layer order from the engine's
    parameter tree {"l<i>": {"self": {"w", "b"}, "neigh": {"w"}}}."""
    out = []
    for i in range(len(params)):
        p = params[f"l{i}"]
        out.append((p["self"]["w"], p["self"]["b"], p["neigh"]["w"]))
    return out


def _mean(v, snap, n, dtype, n_edges=None):
    """Mean over in-neighbours along the first n_edges edges (all when
    None): the later edges aggregate into a dropped row, so every prefix
    of one snapshot runs at one shape."""
    import jax
    import jax.numpy as jnp
    m = len(snap.senders) if n_edges is None else n_edges
    recv = jnp.where(jnp.arange(len(snap.receivers)) < m, snap.receivers, n)
    s = jax.ops.segment_sum(v[snap.senders].astype(dtype), recv, n + 1)
    c = jax.ops.segment_sum(jnp.ones(len(snap.senders), dtype), recv, n + 1)
    return (s / jnp.maximum(c, jnp.asarray(1, dtype))[:, None])[:n]


def forward(params: dict, snap: Snapshot, dtype="float32", n_edges=None):
    """Rows of every snapshot vertex over its first n_edges edges,
    [n, d_out]. float32 runs at the highest matmul precision; "bfloat16"
    keeps every operand, sum and output in bfloat16 (the control, see
    `bench/tests/readings.py`)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    n = len(snap.ids)
    layers = layer_weights(params)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(snap.x, dt)
        for i, (ws, b, wn) in enumerate(layers):
            ws, b, wn = (jnp.asarray(a, dt) for a in (ws, b, wn))
            agg = _mean(x, snap, n, dt, n_edges)
            h = (jnp.matmul(x, ws, preferred_element_type=dt) + b
                 + jnp.matmul(agg, wn, preferred_element_type=dt))
            x = jax.nn.relu(h) if i < len(layers) - 1 else h
    return np.asarray(jax.device_get(x.astype(jnp.float32)))


@dataclass(frozen=True)
class Reference:
    index: dict            # global id -> row
    ref: np.ndarray        # [n, d_out] reference rows
    worst: np.ndarray      # [n, d_out] worst-case bar
    typical: np.ndarray    # [n, d_out] typical-case bar


def reference(params: dict, snap: Snapshot, n_edges=None) -> Reference:
    """Reference rows over the first n_edges edges (all when None), in
    float32 at the highest precision, and both bars."""
    import jax
    import jax.numpy as jnp

    n = len(snap.ids)
    u = U_BF16
    mean = lambda v: _mean(v, snap, n, jnp.float32, n_edges)
    m = len(snap.senders) if n_edges is None else n_edges
    live = jnp.arange(len(snap.receivers)) < m
    deg = jax.ops.segment_sum(live.astype(jnp.float32), snap.receivers,
                              n)[:, None]
    layers = layer_weights(params)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(snap.x)
        bound, sq = jnp.zeros_like(x), jnp.zeros_like(x)
        for i, (ws, b, wn) in enumerate(layers):
            agg = mean(x)
            bound = (((1 + 2 * u) * bound + 2 * u * jnp.abs(x))
                     @ jnp.abs(ws)
                     + ((1 + 2 * u) * mean(bound) + 2 * u * jnp.abs(agg))
                     @ jnp.abs(wn))
            sq_agg = mean(sq) / jnp.maximum(deg, 1.0)
            sq = ((4 * u * u * x * x + sq) @ (ws * ws)
                  + (4 * u * u * agg * agg + sq_agg) @ (wn * wn))
            h = x @ ws + b + agg @ wn
            x = jax.nn.relu(h) if i < len(layers) - 1 else h
    ref, bound, sq = (np.asarray(a) for a in jax.device_get((x, bound, sq)))
    slack = F32_SLACK * (1.0 + np.abs(ref))
    return Reference(index=dict(zip(snap.ids.tolist(), range(n))), ref=ref,
                     worst=bound + slack,
                     typical=LAMBDA * np.sqrt(sq) + slack)
