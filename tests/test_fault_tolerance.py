"""Checkpoint/restore (incl. mid-window in-flight state), elastic rescale,
straggler planning."""
import numpy as np
import jax
import pytest

from repro.core import windowing as win
from repro.core.oracle import build_snapshot, oracle_embeddings
from repro.core.pipeline import D3Pipeline, PipelineConfig
from repro.ft.checkpoint import CheckpointManager
from repro.ft.elastic import rescale_parts, shard_views
from repro.ft.stragglers import StragglerMitigator, speculative_chunks
from repro.graph.sage import GraphSAGE


def make_pipe(window=None, seed=0, n_nodes=40):
    model = GraphSAGE((6, 12, 12))
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=4, node_cap=64, edge_cap=256, repl_cap=256,
                         feat_cap=256, edge_tick_cap=64, max_nodes=n_nodes,
                         window=window or win.WindowConfig(kind=win.SESSION,
                                                           interval=4),
                         seed=seed)
    return model, params, D3Pipeline(model, params, cfg)


def make_stream(seed=0, n_nodes=40, n_edges=120, d=6):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, n_nodes, n_edges),
                      rng.integers(0, n_nodes, n_edges)], 1)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = {v: rng.normal(size=d).astype(np.float32) for v in range(n_nodes)}
    return edges, feats


def test_checkpoint_restart_mid_stream(tmp_path):
    """Kill the pipeline mid-stream (with windows pending = in-flight
    events) and restore into a FRESH pipeline; the continued run must equal
    the uninterrupted run AND the static oracle."""
    edges, feats = make_stream()
    half = len(edges) // 2

    model, params, pipe = make_pipe()
    pipe.run_stream(edges[:half], feats, tick_edges=16)
    mgr = CheckpointManager(tmp_path / "ckpt")
    mgr.save_pipeline(step=1, pipe=pipe)     # windows still pending here

    # "crash": build a brand-new pipeline and restore
    _, _, pipe2 = make_pipe()
    got = mgr.restore_pipeline(pipe2)
    assert got == 1
    pipe2.run_stream(edges[half:], feats, tick_edges=16)
    pipe2.flush(max_ticks=128)

    g, _ = build_snapshot(edges, feats, 6, 40)
    ref = np.asarray(oracle_embeddings(model, params, g))
    emb = pipe2.embeddings()
    touched = set(np.unique(edges).tolist())   # isolated vertices never emit
    assert len(emb) == len(touched)
    for vid, vec in emb.items():
        np.testing.assert_allclose(vec, ref[vid], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("where", ["local", "mesh"])
def test_checkpoint_restores_pending_consistent_queries(tmp_path, where):
    """A carry checkpointed with HELD `consistent` point queries (the
    query plane's in-flight state) must restore into a fresh pipeline and
    answer them identically — same qids, same answer ticks, bit-equal
    payloads — on the LocalRouter and on a mesh."""
    from repro.launch.mesh import make_stream_mesh
    from repro.serve.query import KIND_EMBED, KIND_LINK

    edges, feats = make_stream()
    mesh = make_stream_mesh(1) if where == "mesh" else None

    def make_qpipe():
        model = GraphSAGE((6, 12, 12))
        params = model.init(jax.random.key(0))
        cfg = PipelineConfig(
            n_parts=4, node_cap=64, edge_cap=256, repl_cap=256,
            feat_cap=256, edge_tick_cap=64, max_nodes=40, query_cap=8,
            window=win.WindowConfig(kind=win.TUMBLING, interval=4))
        return D3Pipeline(model, params, cfg, mesh=mesh)

    u, v = int(edges[0, 0]), int(edges[0, 1])
    pipe = make_qpipe()
    pipe.run_stream(edges[:80], feats, tick_edges=16)
    pipe.tick(edges[80:], queries=[(1, KIND_EMBED, u, True),
                                   (2, KIND_LINK, u, v, True),
                                   (3, KIND_EMBED, v, False)])
    pipe.drain_answers()                   # anything already answered
    held = int(np.asarray(jax.device_get(pipe.queries.pending)).sum())
    assert held > 0, "test needs queries still pending at the cut"

    mgr = CheckpointManager(tmp_path / "ckpt")
    mgr.save_pipeline(step=1, pipe=pipe)
    pipe2 = make_qpipe()
    assert mgr.restore_pipeline(pipe2) == 1
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(pipe2.queries.pending)),
        np.asarray(jax.device_get(pipe.queries.pending)))

    def finish(p):
        p.flush(max_ticks=128)
        ans = p.drain_answers()
        order = np.argsort(ans["qid"])
        return {k: val[order] for k, val in ans.items()}

    a, b = finish(pipe), finish(pipe2)
    assert a["qid"].size == held
    np.testing.assert_array_equal(b["qid"], a["qid"])
    np.testing.assert_array_equal(b["tick"], a["tick"])
    np.testing.assert_array_equal(b["ok"], a["ok"])
    np.testing.assert_array_equal(b["vec"], a["vec"])
    np.testing.assert_array_equal(b["score"], a["score"])


def test_checkpoint_gc_and_latest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"a": np.arange(4)})
    assert mgr.latest().step == 4
    assert len(list(tmp_path.glob("*.ckpt"))) == 2
    tree, step = mgr.restore({"a": np.zeros(4, np.int64)})
    assert step == 4
    np.testing.assert_array_equal(np.asarray(tree["a"]), np.arange(4))


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(tmp_path, async_write=True)
    mgr.save(7, {"x": np.ones((8, 8))})
    mgr.wait()
    tree, step = mgr.restore({"x": np.zeros((8, 8))})
    assert step == 7


def test_checkpoint_crc_detects_corruption(tmp_path):
    """ISSUE 10: every checkpoint payload carries a CRC32 of the
    compressed blob. Flip ONE byte of a real checkpoint: an explicit-step
    restore fails loudly (step + path in the message), and a latest-step
    restore warns and falls back to the previous kept generation."""
    from repro.ft.checkpoint import CheckpointCorruptError
    mgr = CheckpointManager(tmp_path, keep=3)
    x1 = np.arange(64, dtype=np.float32).reshape(8, 8)
    mgr.save(1, {"x": x1})
    mgr.save(2, {"x": x1 + 1.0})
    info = mgr.latest()
    blob = bytearray(info.path.read_bytes())
    blob[len(blob) // 2] ^= 0xFF                      # one flipped byte
    info.path.write_bytes(bytes(blob))

    with pytest.raises(CheckpointCorruptError, match=r"step 2"):
        mgr.restore({"x": np.zeros((8, 8), np.float32)}, step=2)
    with pytest.warns(UserWarning, match="falling back"):
        tree, step = mgr.restore({"x": np.zeros((8, 8), np.float32)})
    assert step == 1
    np.testing.assert_array_equal(np.asarray(tree["x"]), x1)


def test_rescale_plan_properties():
    plan = rescale_parts(8, 16, 64)
    # every logical part lands on a valid new shard; moves are minimal-ish
    for lp, old, new in plan.moves:
        assert 0 <= new < 16
    # scale-up never leaves a new shard empty
    views = shard_views(64, 16, 64)
    assert all(len(v) > 0 for v in views)
    # scale-down to 5 (non-divisor) still covers all shards
    views5 = shard_views(64, 5, 64)
    assert all(len(v) > 0 for v in views5)
    assert sum(len(v) for v in views5) == 64


def test_failure_recovery_rescale(tmp_path):
    """Checkpoint, 'lose a machine' (parallelism 2 -> 1), restore, verify
    exactness — the Alg. 5 remap moves keyed state without repartitioning."""
    edges, feats = make_stream(seed=2)
    model, params, pipe = make_pipe(seed=2)
    pipe.cfg.base_parallelism = 2
    pipe.run_stream(edges[:60], feats, tick_edges=16)
    mgr = CheckpointManager(tmp_path)
    mgr.save_pipeline(step=5, pipe=pipe)

    _, _, pipe2 = make_pipe(seed=2)
    from repro.ft.elastic import simulate_failure_and_recover
    cfg_before = pipe2.cfg
    step, plan, new_cfg = simulate_failure_and_recover(pipe2, mgr, 5,
                                                       new_parallelism=1)
    assert step == 5 and pipe2.cfg.base_parallelism == 1
    # the recovery must NOT mutate the old config in place: it returns a
    # fresh validated PipelineConfig and installs it on the pipeline
    assert new_cfg is pipe2.cfg and new_cfg is not cfg_before
    assert cfg_before.base_parallelism == 2
    pipe2.run_stream(edges[60:], feats, tick_edges=16)
    pipe2.flush(max_ticks=128)
    g, _ = build_snapshot(edges, feats, 6, 40)
    ref = np.asarray(oracle_embeddings(model, params, g))
    for vid, vec in pipe2.embeddings().items():
        np.testing.assert_allclose(vec, ref[vid], rtol=1e-4, atol=1e-4)


def test_straggler_detection_and_steal():
    m = StragglerMitigator(n_shards=4, patience=2)
    busy = np.array([10, 10, 10, 100])
    m.observe_tick(1.0, busy)          # establishes EWMA
    for _ in range(3):
        m.observe_tick(5.0, busy)      # shard 3 consistently slow
    assert 3 in m.persistent_stragglers()
    parts = [np.arange(i * 16, (i + 1) * 16) for i in range(4)]
    overrides = m.plan_work_steal(parts, busy)
    assert overrides and all(v != 3 for v in overrides.values())


def test_drivers_feed_straggler_mitigator():
    """ISSUE 9: with the telemetry plane on, BOTH pipeline drivers feed
    `observe_tick` (per-tick wall + per-shard busy proxies) and a
    synthetically slowed shard is flagged and re-mapped off itself via
    the pipeline's own part map."""
    from dataclasses import replace
    edges, feats = make_stream()
    model = GraphSAGE((6, 12, 12))
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=4, node_cap=64, edge_cap=256,
                         repl_cap=256, feat_cap=256, edge_tick_cap=64,
                         max_nodes=40, telemetry=True)
    pipe = D3Pipeline(model, params, cfg)
    assert pipe.straggler is not None and pipe.straggler.ticks_observed == 0
    pipe.run_stream(edges[:48], feats, tick_edges=16)     # one tick a launch
    n1 = pipe.straggler.ticks_observed
    assert n1 == 3 and pipe.straggler._ewma > 0.0
    pipe.run_super_tick(T=4)                              # scan driver
    assert pipe.straggler.ticks_observed == n1 + 1
    # telemetry off: the mitigator is not even constructed
    off = D3Pipeline(model, params, replace(cfg, telemetry=False))
    assert off.straggler is None

    # synthetically slow shard 2: inflate the wall clock past threshold x
    # EWMA with shard 2 carrying the busy mass, past the patience window
    m = StragglerMitigator(n_shards=4, patience=2)
    parts = [np.arange(d, 16, 4) for d in range(4)]       # pipeline-style map
    busy = np.array([5, 5, 80, 5])
    m.observe_tick(0.01, np.array([20, 20, 20, 20]))      # healthy baseline
    for _ in range(3):
        flagged = m.observe_tick(0.05, busy)
        assert flagged == [2]
    assert m.persistent_stragglers() == [2]
    overrides = m.plan_work_steal(parts, busy)
    moved = {lp for lp in overrides}
    assert moved and moved.issubset(set(parts[2].tolist()))
    assert all(tgt != 2 for tgt in overrides.values())


def test_speculative_chunks():
    started = {0: 0.0, 1: 5.0, 2: 9.0}
    assert speculative_chunks([0, 1, 2], started, now_s=10.0,
                              timeout_s=4.0) == [0, 1]
