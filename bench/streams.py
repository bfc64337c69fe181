"""Traffic generation, from a traffic file's parameters and `--seed`.

Every seed gets the same work in another order. The edge stream's shape
(which ranks are hubs, how many edges each pair carries, the order they
arrive in) is drawn once from the traffic file's `structure_seed`; the
run's seed relabels the vertex ids by a random permutation of the id
space and draws the features and the weights. So two seeds differ in
which ids are hubs, which part the partitioner gives each vertex and every
number the model computes, and not in how much work a pass is.
"""
from __future__ import annotations

import numpy as np


def powerlaw_edges(rng: np.random.Generator, n_nodes: int, n_edges: int,
                   alpha: float = 1.5) -> np.ndarray:
    """Edge stream [E, 2]: both endpoints drawn with P(rank k) ~ k**-alpha
    over the id space, a self loop bumped to the next id. Copied from the
    program's `graph/graphs.py:powerlaw_edges` so that the yardstick does
    not move with the program."""
    w = (np.arange(1, n_nodes + 1, dtype=np.float64)) ** (-alpha)
    w /= w.sum()
    src = rng.choice(n_nodes, size=n_edges, p=w)
    dst = rng.choice(n_nodes, size=n_edges, p=w)
    dst = np.where(dst == src, (dst + 1) % n_nodes, dst)
    return np.stack([src, dst], axis=1).astype(np.int32)


# what each sub-seed of a run's seed draws
RELABEL, FEATURES, WEIGHTS, PARTITIONER = range(4)


def sub_seed(seed: int, use: int) -> int:
    """An independent 31-bit seed for one `use` above, from any whole
    `seed` (the driver's are larger than 32 bits)."""
    return int(np.random.SeedSequence(int(seed)).generate_state(8)[use]
               % 2 ** 31)


def edge_stream(n_ids: int, n_edges: int, alpha: float,
                structure_seed: int, seed: int) -> np.ndarray:
    """The traffic's edge stream under the run's relabelling."""
    edges = powerlaw_edges(np.random.default_rng(structure_seed), n_ids,
                           n_edges, alpha)
    perm = np.random.default_rng(sub_seed(seed, RELABEL)).permutation(n_ids)
    return perm[edges].astype(np.int32)


def feature_rows(edges: np.ndarray, d_in: int, seed: int) -> dict:
    """One standard-normal feature row for every vertex the stream touches,
    {vid: row}, in float32."""
    touched = np.unique(edges)
    rng = np.random.default_rng(sub_seed(seed, FEATURES))
    rows = rng.standard_normal((len(touched), d_in), dtype=np.float32)
    return dict(zip(touched.tolist(), rows))
