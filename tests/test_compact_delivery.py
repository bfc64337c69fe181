"""Compacted RMI delivery (`core/tick.py:deliver_rmis`).

A delivered RMI lane is mostly padding, so the tick lists its live rows
in the canonical (source part, arrival) order and runs the backend's
`deliver_add` over them in steps of `deliver_chunk` rows. These tests
pin it against the old delivery (a stable argsort of the whole lane by
(local destination, source part), then `deliver_add` over every row):
bit-equal on "xla" at every load, and on "pallas" while the live rows
fit one step (past it, to f32 round-off). They pin the `deliver_rows`
counter between the live RMIs and the delivered lanes' capacity, on one
device and on a 2-device mesh whose flushed sink stays equal to the
local run's.
"""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import needs_devices, run_forced_devices
from repro.core.delivery import PallasDelivery, XlaDelivery
from repro.core.events import MsgBatch
from repro.core.state import local_index
from repro.core.tick import deliver_chunk, deliver_rmis

needs2 = needs_devices(2)

# one device of a 4-device mesh over 8 parts: it owns parts 2 and 3
P_LOC, N, N_PARTS, PART0, D_AGG = 2, 512, 8, 2, 4
C = 8192
G = deliver_chunk(C)
N_LIVE = sorted({0, 1, C - 1, C}
                | {k * G + o for k in (1, 2) for o in (-1, 0, 1)})
BACKENDS = {"xla": XlaDelivery(), "pallas": PallasDelivery(interpret=True)}


def _lane(n_live: int, seed: int = 0):
    """A hand-built delivered lane of C rows with exactly n_live rows for
    the local parts, at random positions. Live rows hit 40 hub slots from
    every source part (duplicate destinations, so the canonical order
    decides the f32 sums); the rest are invalid rows aimed at local slots
    and valid rows for parts another device owns."""
    rng = np.random.default_rng(seed)
    live = np.zeros(C, bool)
    live[rng.choice(C, n_live, replace=False)] = True
    foreign = ~live & (rng.random(C) < 0.5)
    part = np.where(live | ~foreign,
                    PART0 + rng.integers(0, P_LOC, C),
                    rng.choice([0, 1, 4, 5, 6, 7], C))
    hub = rng.integers(0, 40, C) * 7
    slot = np.where(rng.random(C) < 0.7, hub, rng.integers(0, N, C))
    vec = (rng.normal(size=(C, D_AGG))
           * 10.0 ** rng.uniform(-3, 3, (C, 1))).astype(np.float32)
    return MsgBatch(part=jnp.asarray(part, jnp.int32),
                    slot=jnp.asarray(slot, jnp.int32),
                    vec=jnp.asarray(vec),
                    cnt=jnp.asarray(rng.choice([-1.0, 0.0, 1.0], C),
                                    jnp.float32),
                    src_part=jnp.asarray(rng.integers(0, N_PARTS, C),
                                         jnp.int32),
                    valid=jnp.asarray(live | foreign))


def _old_delivery(agg, cnt, busy, b, delivery):
    """The delivery before compaction: stable argsort of the WHOLE lane
    by (local destination, source part), then deliver_add over every
    row (sentinel rows dropped by the backend)."""
    idx, _ = local_index(b.part, b.slot, PART0, P_LOC, N, b.valid)
    key = idx * N_PARTS + jnp.clip(b.src_part, 0, N_PARTS - 1)
    order = jnp.argsort(key, stable=True)
    idx_s, lp_s = local_index(b.part[order], b.slot[order], PART0, P_LOC,
                              N, b.valid[order])
    agg, cnt, dirty = delivery.deliver_add(agg, cnt, idx_s, b.vec[order],
                                           b.cnt[order])
    return agg, cnt, dirty, busy.at[lp_s].add(1, mode="drop")


class _Layer:
    """The two LayerState fields deliver_rmis reads."""

    def __init__(self, agg, cnt):
        self.agg = agg.reshape(P_LOC, N, D_AGG)
        self.agg_cnt = cnt.reshape(P_LOC, N)


@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("n_live", N_LIVE)
def test_compacted_delivery_bit_equal(backend, n_live):
    delivery = BACKENDS[backend]
    rng = np.random.default_rng(1)
    agg = jnp.asarray(rng.normal(size=(P_LOC * N, D_AGG)), jnp.float32)
    cnt = jnp.asarray(rng.integers(0, 5, P_LOC * N), jnp.float32)
    busy = jnp.asarray(rng.integers(0, 9, P_LOC), jnp.int32)
    lane = _lane(n_live)

    want = jax.jit(_old_delivery, static_argnums=4)(agg, cnt, busy, lane,
                                                    delivery)
    got = jax.jit(
        lambda a, c, bz, b: deliver_rmis(_Layer(a, c), b, jnp.int32(PART0),
                                         bz, delivery, N_PARTS)
    )(agg, cnt, busy, lane)
    exact = backend == "xla" or n_live <= G
    for name, w, g in zip(("agg", "cnt", "dirty", "busy"), want, got):
        if name == "agg" and not exact:
            # "pallas" sums each step's records before adding them: the
            # sums round differently, within f32 of the records' magnitude
            scale = np.asarray(jnp.zeros_like(agg).at[_local_idx(lane)].add(
                jnp.abs(lane.vec), mode="drop")) + np.abs(np.asarray(agg))
            np.testing.assert_array_less(np.abs(np.asarray(w - g)),
                                         1e-6 * scale + 1e-30)
            continue
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g),
                                      err_msg=name)
    assert int(got[4]) == min(C, -(-n_live // G) * G)
    assert int(jnp.sum(got[3] - busy)) == n_live


def _local_idx(b):
    return local_index(b.part, b.slot, PART0, P_LOC, N, b.valid)[0]


@pytest.mark.parametrize("capacity,chunk", [
    (544, 544),                       # a small lane: one step of all of it
    (1088, 1024),
    (C, 1024),
    (103_424, 2048),                  # reddit-hub-ingest
    (270_336, 5120),                  # products-mesh4-ingest, one chip's
])
def test_deliver_chunk(capacity, chunk):
    assert deliver_chunk(capacity) == chunk


# ------------------------------------------ the counter, end to end

def _streamed(stream_case, driver, mesh=None):
    from repro.core import windowing as win
    from repro.core.pipeline import D3Pipeline, PipelineConfig
    from repro.graph.sage import GraphSAGE
    model = GraphSAGE((stream_case.d_in, 16, 16))
    cfg = PipelineConfig(n_parts=4, node_cap=64, edge_cap=256, repl_cap=256,
                         feat_cap=512, edge_tick_cap=64,
                         max_nodes=stream_case.n_nodes,
                         window=win.WindowConfig(kind=win.SESSION,
                                                 interval=3))
    pipe = D3Pipeline(model, model.init(jax.random.key(0)), cfg, mesh=mesh)
    if driver == "tick":
        pipe.run_stream(stream_case.edges, stream_case.feats, tick_edges=32)
        pipe.flush(max_ticks=128)
    else:
        pipe.run_stream_super(stream_case.edges, stream_case.feats,
                              tick_edges=32, super_ticks=4)
        pipe.flush_super(max_ticks=128, T=4)
    return pipe


def _check_counter(m, lane_rows_per_tick):
    assert m.route_dropped == 0 and m.reduce_msgs > 0
    assert m.deliver_lane_rows == m.ticks * lane_rows_per_tick
    # every live RMI was delivered; compaction ran below the lane's size
    assert m.reduce_msgs <= m.deliver_rows < m.deliver_lane_rows


@pytest.mark.parametrize("driver", ["tick", "super"])
def test_deliver_rows_local(stream_case, driver):
    pipe = _streamed(stream_case, driver)
    # 2 layers x one lane of edge_tick_cap + n_parts * edge_cap rows
    _check_counter(pipe.metrics, 2 * (64 + 4 * 256))


@needs2
def test_deliver_rows_mesh2_matches_local(stream_case):
    from repro.launch.mesh import make_stream_mesh
    local = _streamed(stream_case, "super")
    mesh = _streamed(stream_case, "super", mesh=make_stream_mesh(2))
    # 2 layers x 2 devices x 2 buckets of edge_tick_cap + 2 * edge_cap
    _check_counter(mesh.metrics, 2 * 2 * 2 * (64 + 2 * 256))
    np.testing.assert_array_equal(np.asarray(jax.device_get(local.sink)),
                                  np.asarray(jax.device_get(mesh.sink)))
    assert mesh.metrics.reduce_msgs == local.metrics.reduce_msgs


def test_deliver_rows_forced2_subprocess():
    r = run_forced_devices(2, Path(__file__), ["-k", "mesh2"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    assert "1 passed" in r.stdout, r.stdout[-3000:]
