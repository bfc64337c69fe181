"""Golden equivalence of the routing plane (ISSUE 2 tentpole).

`MeshRouter` (part axis block-sharded over a ("data",) mesh, fixed-capacity
all_to_all delivery) must be indistinguishable from `LocalRouter` (flat
scatter, one device): same embeddings, same integer TickStats, same busy
vector — at T = 1 and T = k, across all four window policies, and both must
match the static oracle.

Three execution tiers:
  * in-process on the suite's single CPU device: router/config/termination
    units + a degenerate 1-device mesh (full shard_map machinery, D=1);
  * in-process `@needs4` tests: the full policy matrix — they skip unless
    jax sees >= 4 devices, i.e. they run in the CI mesh lane
    (XLA_FLAGS=--xla_force_host_platform_device_count=4);
  * a subprocess smoke (fast lane, any environment) that forces a 4-device
    CPU backend and checks the streaming golden triplet, backpressure and
    the sharded table allocation;
    the slow lane re-runs the full @needs4 matrix the same way.
"""
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import needs_devices, run_forced_devices
from repro.core import windowing as win
from repro.core.oracle import build_snapshot, oracle_embeddings
from repro.core.pipeline import D3Pipeline, PipelineConfig
from repro.graph.sage import GraphSAGE
from repro.launch.mesh import make_stream_mesh

N_NODES, D_IN = 32, 8

needs4 = needs_devices(4)

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


def build_pipe(window, mesh=None, outbox_cap=None):
    model = GraphSAGE((D_IN, 12, 12))
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=4, node_cap=32, edge_cap=128, repl_cap=128,
                         feat_cap=128, outbox_cap=outbox_cap,
                         edge_tick_cap=32, max_nodes=N_NODES, window=window)
    return model, params, D3Pipeline(model, params, cfg, mesh=mesh)


def assert_embeddings_close(a, b, rtol=1e-5, atol=1e-5):
    assert set(a) == set(b)
    for vid in a:
        np.testing.assert_allclose(b[vid], a[vid], rtol=rtol, atol=atol)


# ----------------------------------------------------------- units (1 dev)

def test_local_router_delivery_is_identity():
    from repro.core.events import MsgBatch
    from repro.dist.router import LocalRouter
    msg = MsgBatch(part=jnp.arange(4, dtype=jnp.int32),
                   slot=jnp.zeros(4, jnp.int32),
                   vec=jnp.ones((4, 3)), cnt=jnp.zeros(4),
                   src_part=jnp.zeros(4, jnp.int32),
                   valid=jnp.ones(4, bool))
    r = LocalRouter(n_parts=4)
    assert r.route(msg) is msg
    assert int(r.part0()) == 0
    assert r.psum(5) == 5


def test_config_validation_rejects_indivisible_parts():
    cfg = PipelineConfig(n_parts=6, feat_cap=6)
    cfg.validate()                       # fine on one device
    with pytest.raises(ValueError, match="not divisible by the mesh"):
        cfg.validate(n_devices=4)
    with pytest.raises(ValueError, match="outbox_cap or feat_cap"):
        PipelineConfig(n_parts=8, feat_cap=100).validate()
    with pytest.raises(ValueError, match="must be > 0"):
        PipelineConfig(node_cap=0).validate()


def test_termination_public_quiet_api():
    from repro.core.termination import TerminationCoordinator
    term = TerminationCoordinator(quiet_sweeps=2)
    assert term.quiet == 0 and term.seed_quiet() == 0
    # device-computed counter replaces the host count (observe_flag)
    assert not term.observe_flag(1)
    assert term.quiet == 1 and term.seed_quiet() == 1
    assert term.observe_flag(2)          # reached quiet_sweeps
    term.reset()
    assert term.quiet == 0


def test_mesh_single_device_golden_and_donated():
    """The full shard_map/MeshRouter machinery on a degenerate 1-device
    mesh must match the LocalRouter reference, keep the sharded carry
    donated, and sync once per super-tick."""
    edges, feats = make_stream()
    _, _, ref = build_pipe(win.WindowConfig(kind=win.STREAMING))
    ref.run_stream(edges, feats, tick_edges=24)
    ref.flush(max_ticks=64)

    mesh = make_stream_mesh(1)
    _, _, sup = build_pipe(win.WindowConfig(kind=win.STREAMING), mesh=mesh)
    old_feat = sup.states[0].feat
    sup.run_stream_super(edges, feats, tick_edges=24, super_ticks=4)
    assert old_feat.is_deleted(), "sharded PipelineCarry must stay donated"
    sup.flush_super(max_ticks=64, T=4)
    assert_embeddings_close(ref.embeddings(), sup.embeddings())
    assert sup.metrics.reduce_msgs == ref.metrics.reduce_msgs
    assert sup.metrics.broadcast_msgs == ref.metrics.broadcast_msgs
    np.testing.assert_array_equal(sup.metrics.busy_logical,
                                  ref.metrics.busy_logical)


def test_stream_mesh_rejects_oversubscription():
    with pytest.raises(ValueError, match="only"):
        make_stream_mesh(len(jax.devices()) + 1)


# ------------------------------------------- full matrix (>= 4 devices)

@needs4
@pytest.mark.parametrize("window", ALL_POLICIES,
                         ids=[w.kind for w in ALL_POLICIES])
def test_mesh_golden_matrix_multidevice(window):
    """LocalRouter vs MeshRouter vs static oracle, per-tick AND super-tick
    drivers, on a real 4-device ("data",) mesh."""
    edges, feats = make_stream()
    model, params, ref = build_pipe(window)
    ref.run_stream(edges, feats, tick_edges=24)
    ref.flush(max_ticks=96)
    e_ref = ref.embeddings()

    mesh = make_stream_mesh(4)
    _, _, per = build_pipe(window, mesh=mesh)
    per.run_stream(edges, feats, tick_edges=24)
    per.flush(max_ticks=96)
    assert_embeddings_close(e_ref, per.embeddings())
    # identical tick boundaries -> identical integer counters
    assert per.metrics.reduce_msgs == ref.metrics.reduce_msgs
    assert per.metrics.broadcast_msgs == ref.metrics.broadcast_msgs
    assert per.metrics.cross_part_msgs == ref.metrics.cross_part_msgs
    assert per.metrics.emitted_total == ref.metrics.emitted_total
    np.testing.assert_array_equal(per.metrics.busy_logical,
                                  ref.metrics.busy_logical)
    # agg counts converge to the oracle's in-degrees on every shard layout
    np.testing.assert_allclose(np.asarray(per.states[0].agg_cnt),
                               np.asarray(ref.states[0].agg_cnt))

    _, _, sup = build_pipe(window, mesh=mesh)
    old_feat = sup.states[0].feat
    sup.run_stream_super(edges, feats, tick_edges=24, super_ticks=4)
    assert old_feat.is_deleted(), "sharded PipelineCarry must stay donated"
    sup.flush_super(max_ticks=96, T=4)
    assert_embeddings_close(e_ref, sup.embeddings())

    g, _ = build_snapshot(edges, feats, D_IN, N_NODES)
    oracle = np.asarray(oracle_embeddings(model, params, g))
    for vid, vec in sup.embeddings().items():
        np.testing.assert_allclose(vec, oracle[vid], rtol=1e-4, atol=1e-4)


@needs4
def test_mesh_tables_born_sharded():
    """Each device zeroes its own shards of a mesh pipeline's tables: none
    is built whole on one device and copied out, which at deployment caps
    would hold the entire carry on device 0."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_stream_mesh(4)
    with jax.transfer_guard_device_to_device("disallow_explicit"):
        _, _, pipe = build_pipe(win.WindowConfig(kind=win.STREAMING),
                                mesh=mesh)
    assert pipe.states[0].feat.sharding == NamedSharding(mesh, P("data"))
    assert pipe.sink.sharding == NamedSharding(mesh, P("data"))


@needs4
def test_mesh_outbox_backpressure_dropped():
    """Regression: a starved outbox (one emission slot per part per tick)
    must defer — not lose — emissions under the sharded path."""
    edges, feats = make_stream(seed=3, n_edges=80)
    mesh = make_stream_mesh(4)
    model, params, pipe = build_pipe(win.WindowConfig(kind=win.STREAMING),
                                     mesh=mesh, outbox_cap=4)  # 1 slot/part
    pipe.run_stream_super(edges, feats, tick_edges=32, super_ticks=3)
    assert pipe.metrics.dropped > 0, "starved outbox must report deferrals"
    pipe.flush_super(max_ticks=256, T=8)
    g, _ = build_snapshot(edges, feats, D_IN, N_NODES)
    oracle = np.asarray(oracle_embeddings(model, params, g))
    emb = pipe.embeddings()
    assert len(emb) == N_NODES
    for vid, vec in emb.items():
        np.testing.assert_allclose(vec, oracle[vid], rtol=1e-4, atol=1e-4)


def test_last_slot_emission_not_lost_by_topk_padding():
    """Regression: when a part's ONLY evicted vertex sits in its last
    node_cap slot and the per-part quota has spare entries, the top_k
    padding used to clamp onto the same slot and the duplicate-index
    scatter-set could erase the emission — fwd_pending then never cleared
    and flush() span to max_ticks."""
    from repro.core.events import (edge_batch_from_numpy, empty_feat_batch,
                                   feat_batch_from_numpy, repl_batch_from_numpy)
    from repro.core.state import apply_edge_batch, apply_repl_batch, init_topo
    from repro.core.tick import layer_tick_body
    from repro.core import state as st_mod
    import jax.numpy as jnp

    N = 4                                    # tiny per-part slot space
    model = GraphSAGE((D_IN, 8))
    params = model.init(jax.random.key(0))
    layer = model.layers[0]
    topo = init_topo(1, 8, 8, N)
    # one master vertex in slot N-1 of part 0, no edges
    from repro.core.events import VertexBatch
    vb = VertexBatch(part=jnp.zeros(1, jnp.int32),
                     slot=jnp.full(1, N - 1, jnp.int32),
                     is_master=jnp.ones(1, bool), valid=jnp.ones(1, bool))
    topo = st_mod.apply_vertex_batch(topo, vb)
    ls = st_mod.init_layer(1, N, D_IN, D_IN)
    fb = feat_batch_from_numpy(np.zeros(1), np.full(1, N - 1),
                               np.ones((1, D_IN), np.float32), 4, D_IN)
    eb = edge_batch_from_numpy({k: np.zeros(0, np.int64) for k in
                                ("part", "edge_slot", "src_slot", "dst_slot",
                                 "dst_master_part", "dst_master_slot")}, 4)
    rb = repl_batch_from_numpy({k: np.zeros(0, np.int64) for k in
                                ("part", "repl_slot", "master_slot",
                                 "rep_part", "rep_slot")}, 4)
    new_ls, outbox, stats, _ = layer_tick_body(
        layer, params["l0"], topo, ls, fb, eb, rb,
        jnp.int32(0), win.WindowConfig(kind=win.STREAMING), outbox_cap=2)
    assert int(stats.emitted) == 1
    assert int(outbox.valid.sum()) == 1
    assert not bool(new_ls.fwd_pending.any()), \
        "emitted vertex must leave the pending set"


# ------------------------------------------------- subprocess (forced 4)

def _run_forced4(pytest_args, timeout=540):
    return run_forced_devices(4, Path(__file__), pytest_args, timeout)


def test_mesh_golden_streaming_forced4_subprocess():
    """Fast-lane smoke on any machine: force a 4-device CPU backend in a
    subprocess and run the STREAMING golden, backpressure and sharded
    allocation tests there."""
    r = _run_forced4(["-k", "test_mesh_golden_matrix_multidevice and "
                            "streaming or backpressure or born_sharded"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]


@pytest.mark.slow
def test_mesh_golden_full_matrix_forced4_subprocess():
    """Slow lane: the complete 4-policy x 2-driver matrix under forced
    4-device CPU (the CI mesh lane runs the same tests in-process)."""
    r = _run_forced4(["-k", "test_mesh_golden_matrix_multidevice or "
                            "backpressure"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
