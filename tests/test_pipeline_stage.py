"""Hybrid-parallel 2-D mesh (ISSUE 7 tentpole).

Contracts pinned here:

  * stage=1 is the UNTOUCHED 1-D program: a `PipelineConfig(n_stages=1)`
    pipeline on a 1-device mesh is bit-for-bit (`assert_array_equal`,
    exact integer stats) the LocalRouter reference across all four
    window policies and T=1 and T=k — the refactor (psum_vote /
    extra_work plumbing shared with the pipelined path) must be
    HLO-invisible at stage=1.

  * stage>1 is a SCHEDULE-SKEWED but convergent program: per-tick
    behaviour differs from the 1-D program (layer l sees the stream l
    hops late), but after flush the quiescent state is the same fixed
    point — embeddings match the LocalRouter reference to f32 round-off
    and the static oracle, and the integer aggregator counts match
    EXACTLY (each edge contributes once, arrival-order independent).

  * the inter-stage ring is real pending work: it is non-empty mid-
    stream, `flush`/`flush_super` refuse to terminate over it, and it is
    EMPTY at quiescence (T=1 and T=k).

  * fail-loud config plane: every invalid (mesh, n_stages, layer-stack)
    combination raises a clear ValueError instead of misrouting.

  * the serve and checkpoint planes survive stage parallelism: point
    queries answer correctly from the stage-replicated sink, and a
    mid-stream snapshot (including in-flight ring rows) restores into a
    run that converges identically.

Execution tiers mirror test_mesh_router: units + the stage=1 matrix on
the suite's single CPU device; @needs2/@needs4/@needs8 in-process cells
(CI pipeline lane forces an 8-device CPU backend = stage 2 x data 4); a
forced-2 subprocess smoke in the fast lane; the forced-8 matrix in the
slow lane.
"""
from pathlib import Path

import numpy as np
import jax
import pytest

from conftest import needs_devices, run_forced_devices
from repro.core import windowing as win
from repro.core.oracle import build_snapshot, oracle_embeddings
from repro.core.pipeline import D3Pipeline, PipelineConfig
from repro.graph.sage import GraphSAGE
from repro.launch.mesh import make_stream_mesh

N_NODES, D = 32, 8

needs2 = needs_devices(2)
needs4 = needs_devices(4)
needs8 = needs_devices(8)

ALL_POLICIES = [win.WindowConfig(kind=win.STREAMING),
                win.WindowConfig(kind=win.TUMBLING, interval=3),
                win.WindowConfig(kind=win.SESSION, interval=3),
                win.WindowConfig(kind=win.ADAPTIVE)]


def make_stream(seed=0, n_edges=100):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, N_NODES, n_edges),
                      rng.integers(0, N_NODES, n_edges)], 1)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = {v: rng.normal(size=D).astype(np.float32)
             for v in range(N_NODES)}
    return edges, feats


def build_pipe(window, mesh=None, n_stages=1, n_layers=2, route_cap=None,
               query_cap=0):
    # uniform dims (in == out == D on every layer): the stage-parallel
    # SPMD-uniformity contract
    model = GraphSAGE((D,) * (n_layers + 1))
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=4, node_cap=32, edge_cap=128, repl_cap=128,
                         feat_cap=128, edge_tick_cap=32, max_nodes=N_NODES,
                         window=window, n_stages=n_stages,
                         route_cap=route_cap, query_cap=query_cap)
    return model, params, D3Pipeline(model, params, cfg, mesh=mesh)


def run_ref(window, n_layers=2, tick_edges=24, seed=0):
    edges, feats = make_stream(seed=seed)
    model, params, ref = build_pipe(window, n_layers=n_layers)
    ref.run_stream(edges, feats, tick_edges=tick_edges)
    ref.flush(max_ticks=128)
    return edges, feats, model, params, ref


def assert_embeddings_close(a, b, rtol=1e-5, atol=1e-5):
    assert set(a) == set(b)
    for vid in a:
        np.testing.assert_allclose(b[vid], a[vid], rtol=rtol, atol=atol)


# --------------------------------------------------- fail-loud config plane

def test_validate_rejects_bad_stage_configs():
    with pytest.raises(ValueError, match="must be >= 1"):
        PipelineConfig(n_stages=0).validate()
    # a stage-parallel config on the LocalRouter would silently run
    # layer-sequentially — reject
    with pytest.raises(ValueError, match="LocalRouter"):
        PipelineConfig(n_stages=2).validate(n_devices=2, n_layers=2,
                                            local=True)
    with pytest.raises(ValueError, match="multiple of the stage count"):
        PipelineConfig(n_stages=2).validate(n_devices=3, n_layers=2)
    with pytest.raises(ValueError, match="round-robin"):
        PipelineConfig(n_stages=2).validate(n_devices=4, n_layers=3)
    # stage=1 keeps the 1-D semantics of every existing check
    PipelineConfig(n_parts=4, feat_cap=4).validate(n_devices=1)


def test_make_stream_mesh_stage_shapes():
    # stage must divide the device budget, whatever the machine has
    with pytest.raises(ValueError, match="multiple of the stage count"):
        make_stream_mesh(1, stage=2)
    m1 = make_stream_mesh(1, stage=1)
    assert m1.axis_names == ("data",), "stage=1 stays a 1-D mesh"


@needs2
def test_mesh_config_stage_mismatch_rejected():
    mesh = make_stream_mesh(2, stage=2)
    assert dict(mesh.shape) == {"stage": 2, "data": 1}
    with pytest.raises(ValueError, match="must agree"):
        build_pipe(win.WindowConfig(kind=win.STREAMING), mesh=mesh,
                   n_stages=1)


@needs2
def test_nonuniform_layer_stack_rejected():
    mesh = make_stream_mesh(2, stage=2)
    model = GraphSAGE((D, 16, D))         # in != out on both layers
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=4, node_cap=32, edge_cap=128,
                         repl_cap=128, feat_cap=128, edge_tick_cap=32,
                         max_nodes=N_NODES, n_stages=2)
    with pytest.raises(ValueError, match="SPMD-uniform"):
        D3Pipeline(model, params, cfg, mesh=mesh)


# ------------------------------------------- stage=1 bit-identity (1 dev)

@pytest.mark.parametrize("window", ALL_POLICIES,
                         ids=[w.kind for w in ALL_POLICIES])
def test_stage1_golden_bit_identity(window):
    """n_stages=1 on a mesh must stay BIT-identical to the LocalRouter
    1-D program — embeddings via assert_array_equal and exact integer
    stats, T=1 and T=k. Pins that the hybrid-parallel refactor is
    unreachable (not just numerically harmless) at stage=1."""
    edges, feats, _, _, ref = run_ref(window)
    e_ref = ref.embeddings()

    mesh = make_stream_mesh(1, stage=1)
    for driver in ("tick", "super"):
        _, _, pipe = build_pipe(window, mesh=mesh, n_stages=1)
        assert pipe.n_stages == 1 and pipe.stage_ring is None
        if driver == "tick":
            pipe.run_stream(edges, feats, tick_edges=24)
            pipe.flush(max_ticks=128)
        else:
            pipe.run_stream_super(edges, feats, tick_edges=24,
                                  super_ticks=4)
            pipe.flush_super(max_ticks=128, T=4)
        emb = pipe.embeddings()
        assert set(emb) == set(e_ref)
        for vid in emb:
            np.testing.assert_array_equal(emb[vid], e_ref[vid])
        m, r = pipe.metrics, ref.metrics
        assert (m.reduce_msgs, m.broadcast_msgs, m.cross_part_msgs,
                m.emitted_total, m.dropped) == \
               (r.reduce_msgs, r.broadcast_msgs, r.cross_part_msgs,
                r.emitted_total, r.dropped)
        np.testing.assert_array_equal(m.busy_logical, r.busy_logical)
        assert m.stage_idle == 0 and pipe.bubble_fraction() == 0.0


# --------------------------------------------- stage=2 golden (>= 2 devs)

@needs2
@pytest.mark.parametrize("window", ALL_POLICIES,
                         ids=[w.kind for w in ALL_POLICIES])
def test_stage2_golden_matrix(window):
    """stage=2 x data=1: schedule-skewed, but the quiescent state equals
    the LocalRouter reference and the static oracle — T=1 and T=k,
    exact integer aggregator counts."""
    edges, feats, model, params, ref = run_ref(window)
    e_ref = ref.embeddings()

    mesh = make_stream_mesh(2, stage=2)
    for driver in ("tick", "super"):
        _, _, pipe = build_pipe(window, mesh=mesh, n_stages=2)
        if driver == "tick":
            pipe.run_stream(edges, feats, tick_edges=24)
            pipe.flush(max_ticks=160)
        else:
            pipe.run_stream_super(edges, feats, tick_edges=24,
                                  super_ticks=4)
            pipe.flush_super(max_ticks=160, T=4)
        assert pipe._ring_occupancy_host() == 0, \
            "quiescence must drain the inter-stage ring"
        assert_embeddings_close(e_ref, pipe.embeddings())
        # each edge reaches every layer's aggregator exactly once,
        # whatever the inter-stage schedule
        for r, ls in enumerate(pipe.states):
            got = np.asarray(ls.agg_cnt)       # [S, P, N] stacked rounds
            for s in range(2):
                li = r * 2 + s
                np.testing.assert_array_equal(
                    got[s], np.asarray(ref.states[li].agg_cnt))
        assert pipe.metrics.dropped == ref.metrics.dropped
        assert pipe.metrics.route_dropped == 0

    g, _ = build_snapshot(edges, feats, D, N_NODES)
    oracle = np.asarray(oracle_embeddings(model, params, g))
    for vid, vec in pipe.embeddings().items():
        np.testing.assert_allclose(vec, oracle[vid], rtol=1e-4, atol=1e-4)


@needs2
def test_stage2_four_layers_two_rounds():
    """R = L // S = 2 rounds per stage: exercises the deeper ring (slot
    r > 0 reads, the stage-0 wrap hop) against a 4-layer reference."""
    window = win.WindowConfig(kind=win.STREAMING)
    edges, feats, model, params, ref = run_ref(window, n_layers=4)
    mesh = make_stream_mesh(2, stage=2)
    _, _, pipe = build_pipe(window, mesh=mesh, n_stages=2, n_layers=4)
    assert pipe._n_rounds == 2
    pipe.run_stream_super(edges, feats, tick_edges=24, super_ticks=4)
    pipe.flush_super(max_ticks=160, T=4)
    assert_embeddings_close(ref.embeddings(), pipe.embeddings())
    g, _ = build_snapshot(edges, feats, D, N_NODES)
    oracle = np.asarray(oracle_embeddings(model, params, g))
    for vid, vec in pipe.embeddings().items():
        np.testing.assert_allclose(vec, oracle[vid], rtol=1e-4, atol=1e-4)


@needs2
@pytest.mark.parametrize("driver", ["tick", "super"])
def test_flush_drains_inflight_stage_ring(driver):
    """Mid-stream the ring holds live rows; quiescence must wait for the
    skewed tail to telescope through every stage (regression: a flush
    that ignored ring occupancy would terminate early and lose the last
    L-1 hops of every in-flight update)."""
    window = win.WindowConfig(kind=win.STREAMING)
    edges, feats = make_stream()
    mesh = make_stream_mesh(2, stage=2)
    _, _, pipe = build_pipe(window, mesh=mesh, n_stages=2)
    if driver == "tick":
        pipe.run_stream(edges, feats, tick_edges=24)
    else:
        pipe.run_stream_super(edges, feats, tick_edges=24, super_ticks=4)
    assert pipe._ring_occupancy_host() > 0, \
        "a just-streamed pipeline must have rows in flight between stages"
    if driver == "tick":
        pipe.flush(max_ticks=160)
    else:
        pipe.flush_super(max_ticks=160, T=4)
    assert pipe._ring_occupancy_host() == 0
    # the drained rows materialized: every vertex has an embedding
    assert len(pipe.embeddings()) == N_NODES


@needs2
def test_stage2_bubble_telemetry():
    window = win.WindowConfig(kind=win.STREAMING)
    edges, feats = make_stream()
    mesh = make_stream_mesh(2, stage=2)
    _, _, pipe = build_pipe(window, mesh=mesh, n_stages=2)
    pipe.run_stream(edges, feats, tick_edges=24)
    pipe.flush(max_ticks=160)
    # warm-up and drain ticks necessarily bubble (stage 1 idles on tick
    # 0; stage 0 idles while the tail drains)
    assert pipe.metrics.stage_idle > 0
    assert 0.0 < pipe.bubble_fraction() <= 1.0


@needs2
def test_stage2_query_plane():
    """Point queries served from the stage-replicated sink: stale_ok
    embedding reads bit-match read_nodes, link queries answer, nothing
    strands."""
    from repro.serve.query import KIND_EMBED, KIND_LINK
    window = win.WindowConfig(kind=win.STREAMING)
    edges, feats = make_stream()
    mesh = make_stream_mesh(2, stage=2)
    _, _, pipe = build_pipe(window, mesh=mesh, n_stages=2, query_cap=8)
    pipe.run_stream(edges, feats, tick_edges=24)
    pipe.flush(max_ticks=160)
    vids = sorted(pipe.embeddings())[:4]
    qs = [(i, KIND_EMBED, v, False) for i, v in enumerate(vids)]
    qs.append((len(qs), KIND_LINK, vids[0], vids[1], False))
    pipe.tick(queries=qs)
    pipe.flush(max_ticks=160)
    ans = pipe.drain_answers()
    assert sorted(ans["qid"].tolist()) == list(range(len(qs)))
    assert ans["ok"].all()
    snap = pipe.read_nodes(vids)
    for qid, v in enumerate(vids):
        row = np.flatnonzero(ans["qid"] == qid)[0]
        np.testing.assert_array_equal(ans["vec"][row], snap[v])


@needs2
def test_stage2_checkpoint_roundtrip(tmp_path):
    """A mid-stream snapshot carries the in-flight ring rows: restoring
    it and replaying the tail converges to the uninterrupted run."""
    from repro.ft.checkpoint import CheckpointManager
    window = win.WindowConfig(kind=win.STREAMING)
    edges, feats = make_stream()
    mesh = make_stream_mesh(2, stage=2)
    _, _, pipe = build_pipe(window, mesh=mesh, n_stages=2)
    half = len(edges) // 2
    pipe.run_stream(edges[:half], feats, tick_edges=24)
    assert pipe._ring_occupancy_host() > 0
    mgr = CheckpointManager(tmp_path)
    mgr.save_pipeline(0, pipe)
    seen = set(int(v) for v in edges[:half].reshape(-1))

    def finish(p):
        e_chunks, f_chunks = p.chunk_stream(edges[half:], feats, 24,
                                            seen=set(seen))
        for chunk, f_events in zip(e_chunks, f_chunks):
            p.tick(chunk, f_events)
        p.flush(max_ticks=160)
        return p.embeddings()

    e_straight = finish(pipe)

    _, _, fresh = build_pipe(window, mesh=mesh, n_stages=2)
    mgr.restore_pipeline(fresh)
    assert fresh._ring_occupancy_host() == pipe._ring_occupancy_host() or \
        fresh._ring_occupancy_host() > 0
    e_restored = finish(fresh)
    assert set(e_restored) == set(e_straight)
    for vid in e_straight:
        np.testing.assert_allclose(e_restored[vid], e_straight[vid],
                                   rtol=1e-6, atol=1e-6)


# ------------------------------------- stage=2 x data>1 (>= 4 / 8 devs)

@needs4
def test_stage2_data2_capped_route_backpressure():
    """The full hybrid plane: 2 stages x 2 data shards with a tiny
    route_cap on hub-heavy traffic — capped lanes defer (never drop),
    re-emit, and still converge to the 1-D reference and oracle."""
    window = win.WindowConfig(kind=win.STREAMING)
    rng = np.random.default_rng(1)
    src = rng.integers(1, N_NODES, 120)
    dst = np.where(rng.random(120) < 0.75, rng.integers(0, 3, 120),
                   rng.integers(0, N_NODES, 120))
    edges = np.stack([src, dst], 1)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = {v: rng.normal(size=D).astype(np.float32)
             for v in range(N_NODES)}

    model, params, ref = build_pipe(window)
    ref.run_stream(edges, feats, tick_edges=24)
    ref.flush(max_ticks=160)

    mesh = make_stream_mesh(4, stage=2)
    assert dict(mesh.shape) == {"stage": 2, "data": 2}
    _, _, pipe = build_pipe(window, mesh=mesh, n_stages=2, route_cap=8)
    pipe.run_stream_super(edges, feats, tick_edges=24, super_ticks=4)
    pipe.flush_super(max_ticks=256, T=8)
    assert pipe.metrics.route_dropped == 0
    assert_embeddings_close(ref.embeddings(), pipe.embeddings(),
                            rtol=1e-4, atol=1e-4)
    g, _ = build_snapshot(edges, feats, D, N_NODES)
    oracle = np.asarray(oracle_embeddings(model, params, g))
    for vid, vec in pipe.embeddings().items():
        np.testing.assert_allclose(vec, oracle[vid], rtol=1e-4, atol=1e-4)


@needs8
@pytest.mark.parametrize("window", ALL_POLICIES,
                         ids=[w.kind for w in ALL_POLICIES])
def test_stage2_data4_golden_matrix(window):
    """The ISSUE target shape — stage=2 x data=4 — over every window
    policy (T=k launches; the CI pipeline lane runs this
    in-process on a forced 8-device CPU backend)."""
    edges, feats, model, params, ref = run_ref(window)
    mesh = make_stream_mesh(8, stage=2)
    assert dict(mesh.shape) == {"stage": 2, "data": 4}
    _, _, pipe = build_pipe(window, mesh=mesh, n_stages=2)
    pipe.run_stream_super(edges, feats, tick_edges=24, super_ticks=4)
    pipe.flush_super(max_ticks=160, T=4)
    assert pipe._ring_occupancy_host() == 0
    assert_embeddings_close(ref.embeddings(), pipe.embeddings())
    g, _ = build_snapshot(edges, feats, D, N_NODES)
    oracle = np.asarray(oracle_embeddings(model, params, g))
    for vid, vec in pipe.embeddings().items():
        np.testing.assert_allclose(vec, oracle[vid], rtol=1e-4, atol=1e-4)


# ------------------------------------------------- subprocess (forced N)

def test_stage_smoke_forced2_subprocess():
    """Fast-lane smoke on any machine: a forced 2-device CPU backend runs
    the STREAMING stage=2 golden + the ring-drain regression."""
    r = run_forced_devices(
        2, Path(__file__),
        ["-k", "(test_stage2_golden_matrix and streaming) or "
               "test_flush_drains_inflight_stage_ring"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]


@pytest.mark.slow
def test_stage_full_matrix_forced8_subprocess():
    """Slow lane: the complete stage matrix — including the 2x4 target
    shape — under a forced 8-device CPU backend (the CI pipeline lane
    runs the same cells in-process)."""
    r = run_forced_devices(
        8, Path(__file__),
        ["-k", "test_stage2_data4_golden_matrix or "
               "test_stage2_data2_capped_route_backpressure or "
               "test_stage2_golden_matrix"],
        timeout=1200)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
