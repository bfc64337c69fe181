"""Golden equivalence of the delivery plane (ISSUE 3 tentpole).

`delivery_backend="pallas"` (sorted segment-reduce kernels, interpret
mode off-TPU) must be indistinguishable from `delivery_backend="xla"`
(the reference scatters): same embeddings, same exact integer TickStats,
same busy vector — across all four window policies, T=1 and T=k, and
both routers. The xla pipelines are themselves pinned to the static
oracle by tests/test_mesh_router.py, so pallas ≡ xla ≡ oracle.

Float tolerance note: integer-natured quantities (stats, counts, busy)
are compared EXACTLY; embeddings use the same tight allclose as the
router golden matrix, because duplicate RMI records summed by a one-hot
matmul and by a sequential scatter can differ in f32 summation order.

The whole module carries the `pallas` marker (pyproject registers it) —
CI's pallas-interpret lane selects it with `-m pallas`; the mesh tests
skip below 4 devices and run there under a forced 4-device CPU backend.
"""
import numpy as np
import jax
import pytest

from repro.core import windowing as win
from repro.core.delivery import (BACKENDS, PallasDelivery, XlaDelivery,
                                 make_delivery)
from repro.core.pipeline import D3Pipeline, PipelineConfig
from repro.graph.sage import GraphSAGE
from repro.launch.mesh import make_stream_mesh

pytestmark = pytest.mark.pallas

N_NODES, D_IN = 32, 8

needs4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >=4 devices (CI pallas lane forces a 4-device backend)")

ALL_POLICIES = [win.WindowConfig(kind=win.STREAMING),
                win.WindowConfig(kind=win.TUMBLING, interval=3),
                win.WindowConfig(kind=win.SESSION, interval=3),
                win.WindowConfig(kind=win.ADAPTIVE)]


def make_stream(seed=0, n_edges=100):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, N_NODES, n_edges),
                      rng.integers(0, N_NODES, n_edges)], 1)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = {v: rng.normal(size=D_IN).astype(np.float32)
             for v in range(N_NODES)}
    return edges, feats


def build_pipe(window, backend, mesh=None):
    model = GraphSAGE((D_IN, 12, 12))
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=4, node_cap=32, edge_cap=128, repl_cap=128,
                         feat_cap=128, edge_tick_cap=32, max_nodes=N_NODES,
                         window=window, delivery_backend=backend)
    return D3Pipeline(model, params, cfg, mesh=mesh)


def run_per_tick(pipe, edges, feats):
    pipe.run_stream(edges, feats, tick_edges=24)
    pipe.flush(max_ticks=96)
    return pipe


def run_super(pipe, edges, feats):
    pipe.run_stream_super(edges, feats, tick_edges=24, super_ticks=4)
    pipe.flush_super(max_ticks=96, T=4)
    return pipe


def assert_golden_equal(ref, other):
    """Exact integer telemetry + tight embedding equivalence."""
    assert other.metrics.reduce_msgs == ref.metrics.reduce_msgs
    assert other.metrics.broadcast_msgs == ref.metrics.broadcast_msgs
    assert other.metrics.cross_part_msgs == ref.metrics.cross_part_msgs
    assert other.metrics.emitted_total == ref.metrics.emitted_total
    assert other.metrics.dropped == ref.metrics.dropped
    np.testing.assert_array_equal(other.metrics.busy_logical,
                                  ref.metrics.busy_logical)
    # aggregator counts are integer-valued floats: exact on both backends
    np.testing.assert_array_equal(np.asarray(other.states[0].agg_cnt),
                                  np.asarray(ref.states[0].agg_cnt))
    a, b = ref.embeddings(), other.embeddings()
    assert set(a) == set(b)
    for vid in a:
        np.testing.assert_allclose(b[vid], a[vid], rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ registry units

def test_registry_and_validation():
    assert set(BACKENDS) == {"xla", "pallas"}
    assert isinstance(make_delivery("xla"), XlaDelivery)
    assert isinstance(make_delivery("pallas"), PallasDelivery)
    with pytest.raises(ValueError, match="unknown delivery_backend"):
        make_delivery("cuda")
    with pytest.raises(ValueError, match="not registered"):
        PipelineConfig(delivery_backend="nope").validate()
    # backends must be hashable static-arg citizens (jit cache keys)
    assert hash(make_delivery("pallas")) == hash(make_delivery("pallas"))


def test_pipeline_resolves_backend():
    pipe = build_pipe(win.WindowConfig(kind=win.STREAMING), "pallas")
    assert isinstance(pipe.delivery, PallasDelivery)
    pipe = build_pipe(win.WindowConfig(kind=win.STREAMING), "xla")
    assert isinstance(pipe.delivery, XlaDelivery)


# ------------------------------------- golden matrix (LocalRouter, 1 device)

@pytest.mark.parametrize("window", ALL_POLICIES,
                         ids=[w.kind for w in ALL_POLICIES])
def test_pallas_golden_matrix_local(window):
    """pallas ≡ xla at T = 1 and T = k under the LocalRouter, per policy."""
    edges, feats = make_stream()
    ref = run_per_tick(build_pipe(window, "xla"), edges, feats)
    per = run_per_tick(build_pipe(window, "pallas"), edges, feats)
    assert_golden_equal(ref, per)
    sup = run_super(build_pipe(window, "pallas"), edges, feats)
    assert_golden_equal(ref, sup)


def test_pallas_super_tick_stays_donated():
    """The pallas program must not break the donated-carry contract."""
    edges, feats = make_stream()
    pipe = build_pipe(win.WindowConfig(kind=win.STREAMING), "pallas")
    old_feat = pipe.states[0].feat
    pipe.run_stream_super(edges, feats, tick_edges=24, super_ticks=4)
    assert old_feat.is_deleted(), "PipelineCarry must stay donated"


# --------------------------------------- golden matrix (MeshRouter, >=4 dev)

@needs4
@pytest.mark.parametrize("window", ALL_POLICIES,
                         ids=[w.kind for w in ALL_POLICIES])
def test_pallas_golden_matrix_mesh(window):
    """pallas ≡ xla on a real 4-device mesh: the delivery kernels run
    INSIDE the shard_map, after the all_to_all routing round."""
    edges, feats = make_stream()
    mesh = make_stream_mesh(4)
    ref = run_per_tick(build_pipe(window, "xla", mesh=mesh), edges, feats)
    per = run_per_tick(build_pipe(window, "pallas", mesh=mesh), edges, feats)
    assert_golden_equal(ref, per)
    sup = run_super(build_pipe(window, "pallas", mesh=mesh), edges, feats)
    assert_golden_equal(ref, sup)
