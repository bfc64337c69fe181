"""Golden serving matrix for the query plane (ISSUE 4 tentpole).

The query plane must be indistinguishable across every execution
configuration: {LocalRouter, MeshRouter} x {per-tick, super-tick} x
{xla, pallas delivery} — same answered qids, EXACT integer answer
ticks/ok flags, embeddings to f32 round-off. Within one configuration:

  * stale_ok answers BIT-match the `read_nodes` host oracle of the same
    tick (they read the same sink buffer);
  * consistent answers issued before a drain flush match the STATIC
    oracle (they hold until a locally-clean, globally-silent tick);
  * `embeddings()` is a thin wrapper over `read_nodes` (same dict);
  * the donated-carry and one-host-sync-per-super-tick contracts hold
    with queries aboard, and query_cap=0 compiles the plane away.

The in-process mesh tests use the degenerate 1-device mesh (full
shard_map/MeshRouter machinery); the @needs4 variant re-runs the matrix
on a real 4-device backend (CI mesh lane).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import windowing as win
from repro.core.oracle import build_snapshot, oracle_embeddings
from repro.core.pipeline import D3Pipeline, PipelineConfig
from repro.graph.sage import GraphSAGE
from repro.launch.mesh import make_stream_mesh
from repro.serve.query import (KIND_EMBED, KIND_LINK, admit,
                               init_query_state, query_batch_from_numpy)
from repro.serve.session import ServeSession

N_NODES, D_IN = 32, 8

needs4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >=4 devices (CI mesh lane forces a 4-device CPU backend)")


def make_stream(seed=0, n_edges=100):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, N_NODES, n_edges),
                      rng.integers(0, N_NODES, n_edges)], 1)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = {v: rng.normal(size=D_IN).astype(np.float32)
             for v in range(N_NODES)}
    return edges, feats


def build_pipe(window=None, mesh=None, backend="xla", query_cap=8,
               query_tick_cap=None):
    model = GraphSAGE((D_IN, 12, 12))
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=4, node_cap=32, edge_cap=128, repl_cap=128,
                         feat_cap=128, edge_tick_cap=32, max_nodes=N_NODES,
                         query_cap=query_cap, query_tick_cap=query_tick_cap,
                         delivery_backend=backend,
                         window=window or win.WindowConfig(kind=win.STREAMING))
    return model, params, D3Pipeline(model, params, cfg, mesh=mesh)


def chunked(edges, feats, tick_edges=24):
    e_chunks = [edges[lo: lo + tick_edges]
                for lo in range(0, len(edges), tick_edges)]
    seen, f_chunks = set(), []
    for ch in e_chunks:
        fe = []
        for u in ch.reshape(-1):
            u = int(u)
            if u not in seen and u in feats:
                seen.add(u)
                fe.append((u, feats[u]))
        f_chunks.append(fe)
    return e_chunks, f_chunks


def query_mix(edges):
    """Fixed query set: stale_ok + consistent embeds, a consistent link."""
    u, v = int(edges[0, 0]), int(edges[0, 1])
    return [(1, KIND_EMBED, 0, False),          # stale_ok read
            (2, KIND_LINK, u, v, True),          # consistent link score
            (3, KIND_EMBED, 5, True),            # consistent read
            (4, KIND_LINK, u, 5, False)]         # stale_ok link score


def run_config(edges, feats, mesh, driver, backend):
    """Stream 3 update ticks, admit the query mix on tick 4, flush —
    driver "tick" one tick a launch (`tick`, `flush`), "super" all four
    ticks in one launch and flushing four a launch."""
    _, _, pipe = build_pipe(mesh=mesh, backend=backend)
    e_chunks, f_chunks = chunked(edges, feats)
    q = query_mix(edges)
    if driver == "tick":
        for ch, fe in zip(e_chunks[:-1], f_chunks[:-1]):
            pipe.tick(ch, fe)
        pipe.tick(e_chunks[-1], f_chunks[-1], queries=q)
        pipe.flush(max_ticks=96)
    else:
        q_chunks = [None] * (len(e_chunks) - 1) + [q]
        pipe.run_super_tick(e_chunks, f_chunks, T=len(e_chunks),
                            query_chunks=q_chunks)
        pipe.flush_super(max_ticks=96, T=4)
    return pipe, canon(pipe.drain_answers())


def canon(ans):
    order = np.argsort(ans["qid"])
    return {k: v[order] for k, v in ans.items()}


# -------------------------------------------------------------- unit tests

def test_config_validation():
    PipelineConfig(query_cap=0).validate()             # disabled is fine
    with pytest.raises(ValueError, match="must be >= 0"):
        PipelineConfig(query_cap=-1).validate()
    with pytest.raises(ValueError, match="query plane is disabled"):
        PipelineConfig(query_cap=0, query_tick_cap=8).validate()
    cfg = PipelineConfig(query_cap=8)
    assert cfg.capacities().query_admissions == 8 * cfg.n_parts
    assert PipelineConfig(
        query_cap=8, query_tick_cap=16).capacities().query_admissions == 16


def test_admission_fills_free_slots_and_drops_overflow():
    from repro.dist.router import LocalRouter
    qs = init_query_state(2, 2, 4)                     # 2 parts x 2 slots
    rows = {"qid": np.arange(3), "kind": np.zeros(3),
            "part": np.zeros(3), "slot": np.arange(3),
            "part2": np.zeros(3), "slot2": np.zeros(3),
            "consistent": np.zeros(3, bool), "issue": np.zeros(3)}
    qb = query_batch_from_numpy(rows, 4, 4)
    qs, n_adm, dropped = admit(qs, qb, jnp.int32(0))
    # part 0 has 2 slots; the third record for part 0 must drop — and the
    # drop MASK identifies exactly which record, so it can answer ok=False
    assert int(n_adm) == 2 and int(dropped.sum()) == 1
    assert bool(dropped[2]) and not bool(dropped[0]) and not bool(dropped[1])
    assert qs.pending[0].tolist() == [True, True]
    assert qs.pending[1].tolist() == [False, False]
    assert sorted(np.asarray(qs.qid[0]).tolist()) == [0, 1]


def test_queries_require_enabled_plane():
    _, _, pipe = build_pipe(query_cap=0)
    with pytest.raises(AssertionError, match="query_cap=0"):
        pipe.tick(queries=[(1, KIND_EMBED, 0, False)])
    with pytest.raises(ValueError, match="query_cap > 0"):
        ServeSession(pipe)


def test_serve_session_rejects_bad_launch_size():
    """A session launches `super_ticks` >= 1 ticks at a time; the old
    per-tick driver name points at super_ticks=1."""
    _, _, pipe = build_pipe()
    with pytest.raises(ValueError, match="super_ticks"):
        ServeSession(pipe, super_ticks=0)
    with pytest.raises(ValueError, match="super_ticks=1"):
        ServeSession(pipe, driver="tick")


def test_read_nodes_partial_gather_and_embeddings_wrapper():
    edges, feats = make_stream()
    _, _, pipe = build_pipe(query_cap=0)
    pipe.run_stream(edges, feats, tick_edges=24)
    pipe.flush(max_ticks=96)
    full = pipe.embeddings()
    some = pipe.read_nodes([0, 1, 5, 99999])           # unknown vid ignored
    assert set(some) <= set(full)
    for v in some:
        np.testing.assert_array_equal(some[v], full[v])
    assert pipe.read_nodes([]) == {}


def test_pending_table_overflow_answers_ok_false():
    """Device-side admission overflow must NOT silently lose queries:
    the dropped qids come back as ok=False answers in the same tick, so
    the client knows exactly what to re-submit."""
    edges, feats = make_stream()
    # 1 pending slot per part, but room to ADMIT 8 requests per tick
    _, _, pipe = build_pipe(query_cap=1, query_tick_cap=8)
    pipe.run_stream(edges[:48], feats, tick_edges=24)
    vid = int(edges[0, 0])
    # 5 consistent reads of ONE vertex admitted alongside an update chunk:
    # the tick moves messages, so they all want to hold — but the master
    # part has a single slot; 4 must drop and answer ok=False now
    qs = [(i, KIND_EMBED, vid, True) for i in range(5)]
    pipe.tick(edges[48:72], queries=qs)
    ans = canon(pipe.drain_answers())
    assert len(ans["qid"]) == 4 and not ans["ok"].any()
    assert ans["tick"].tolist() == [pipe.now - 1] * 4
    assert pipe.metrics.queries_dropped == 4
    # the surviving query still resolves on flush
    pipe.flush(max_ticks=96)
    survivor = pipe.drain_answers()
    assert len(survivor["qid"]) == 1 and survivor["ok"].all()
    assert set(survivor["qid"]) | set(ans["qid"]) == set(range(5))


def test_session_budgets_submission_bursts():
    """A submission burst larger than one launch's admission budget must
    stay queued (not crash the fixed-capacity staging) and drain over
    subsequent advances."""
    edges, feats = make_stream()
    model = GraphSAGE((D_IN, 12, 12))
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=4, node_cap=32, edge_cap=128, repl_cap=128,
                         feat_cap=128, edge_tick_cap=32, max_nodes=N_NODES,
                         query_cap=8, query_tick_cap=4,
                         window=win.WindowConfig(kind=win.STREAMING))
    pipe = D3Pipeline(model, params, cfg)
    s = ServeSession(pipe, super_ticks=2)
    e_chunks, f_chunks = chunked(edges, feats)
    s.advance_super(e_chunks, f_chunks)            # ingest everything first
    vids = [int(edges[i % len(edges), 0]) for i in range(13)]
    s.submit_embed(vids)                           # 13 > 4/tick * 2 ticks
    s.advance_super(T=2)
    assert len(s._queue) == 5                      # budget = 8 admitted
    s.advance_super(T=2)
    s.flush()
    assert s.outstanding == 0
    assert len(s.answers) == 13


# ------------------------------------------------- per-config golden checks

def test_stale_ok_bit_matches_read_nodes_same_tick():
    """A stale_ok answer at tick t IS the sink row read_nodes sees after
    tick t — bitwise, not approximately."""
    edges, feats = make_stream()
    _, _, pipe = build_pipe()
    pipe.run_stream(edges[:72], feats, tick_edges=24)
    pipe.tick(edges[72:], queries=[(1, KIND_EMBED, 0, False),
                                   (2, KIND_EMBED, 5, False)])
    oracle = pipe.read_nodes([0, 5])
    ans = canon(pipe.drain_answers())
    assert ans["qid"].tolist() == [1, 2]
    assert ans["tick"].tolist() == [pipe.now - 1] * 2
    for i, vid in enumerate((0, 5)):
        if vid in oracle:
            assert bool(ans["ok"][i])
            np.testing.assert_array_equal(ans["vec"][i], oracle[vid])
        else:
            assert not bool(ans["ok"][i])


def test_consistent_answers_match_static_oracle_after_flush():
    edges, feats = make_stream()
    model, params, pipe = build_pipe()
    pipe, ans = run_config(edges, feats, None, "tick", "xla")
    g, _ = build_snapshot(edges, feats, D_IN, N_NODES)
    oracle = np.asarray(oracle_embeddings(model, params, g))
    u, v = int(edges[0, 0]), int(edges[0, 1])
    by = {int(q): i for i, q in enumerate(ans["qid"])}
    assert ans["ok"].all()
    np.testing.assert_allclose(ans["vec"][by[3]], oracle[5],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ans["score"][by[2]],
                               float(oracle[u] @ oracle[v]), rtol=1e-4)


def test_unknown_vertex_host_rejected():
    """Queries naming a vertex the partitioner has never seen (or an id
    outside the configured id space) answer ok=False on the host, without
    burning device pending slots."""
    _, _, pipe = build_pipe()
    pipe.tick(queries=[(7, KIND_EMBED, 0, False),          # unseen vid
                       (8, KIND_LINK, 0, 10 ** 6, False)])  # out of range
    ans = canon(pipe.drain_answers())
    assert ans["qid"].tolist() == [7, 8]
    assert not ans["ok"].any()
    assert pipe.metrics.queries_admitted == 0


def test_super_tick_donation_and_single_sync_with_queries():
    edges, feats = make_stream()
    _, _, pipe = build_pipe()
    e_chunks, f_chunks = chunked(edges, feats)
    old_feat = pipe.states[0].feat
    old_q = pipe.queries.pending
    pipe.run_super_tick(e_chunks, f_chunks, T=len(e_chunks),
                        query_chunks=[query_mix(edges)])
    assert old_feat.is_deleted(), "PipelineCarry must stay donated"
    assert old_q.is_deleted(), "QueryState rides the donated carry"


def test_query_metrics_accumulate():
    edges, feats = make_stream()
    _, _, pipe = build_pipe()
    pipe.run_stream(edges[:48], feats, tick_edges=24)
    # admit together with an update chunk: that tick MOVES messages, so
    # the consistent queries must hold at least one tick
    pipe.tick(edges[48:72], queries=query_mix(edges))
    pipe.flush(max_ticks=96)
    m = pipe.metrics
    assert m.queries_admitted == 4
    assert m.queries_answered == 4
    assert m.queries_dropped == 0
    assert m.query_hold_ticks > 0          # the consistent ones held


# --------------------------------------------------- the full golden matrix

def assert_answers_match(ref, other, name):
    np.testing.assert_array_equal(other["qid"], ref["qid"], err_msg=name)
    np.testing.assert_array_equal(other["tick"], ref["tick"],
                                  err_msg=f"{name}: answer ticks must be "
                                          "EXACTLY equal across configs")
    np.testing.assert_array_equal(other["ok"], ref["ok"], err_msg=name)
    np.testing.assert_array_equal(other["issue"], ref["issue"], err_msg=name)
    np.testing.assert_array_equal(other["kind"], ref["kind"], err_msg=name)
    np.testing.assert_allclose(other["vec"], ref["vec"], rtol=1e-5,
                               atol=1e-5, err_msg=name)
    np.testing.assert_allclose(other["score"], ref["score"], rtol=1e-4,
                               atol=1e-5, err_msg=name)


@pytest.fixture(scope="module")
def golden_ref():
    """The reference config's answers: LocalRouter, one-tick launches, xla.
    Built once; every matrix cell compares against it."""
    edges, feats = make_stream()
    _, ref = run_config(edges, feats, None, "tick", "xla")
    assert len(ref["qid"]) == 4 and ref["ok"].all()
    return edges, feats, ref


MATRIX = [("tick", "xla", "mesh1"), ("super", "xla", "local"),
          ("super", "xla", "mesh1"),
          pytest.param("tick", "pallas", "local", marks=pytest.mark.pallas),
          pytest.param("super", "pallas", "local", marks=pytest.mark.pallas),
          pytest.param("super", "pallas", "mesh1",
                       marks=pytest.mark.pallas)]


@pytest.mark.parametrize("driver,backend,where", MATRIX)
def test_golden_serving_matrix(golden_ref, driver, backend, where):
    """{LocalRouter, MeshRouter} x {T=1, T=k launches} x {xla, pallas}:
    identical answered qids, EXACT answer ticks, equivalent payloads.
    The in-process mesh is the degenerate 1-device one (full shard_map +
    MeshRouter machinery); @needs4 below re-runs on real 4 devices."""
    edges, feats, ref = golden_ref
    mesh = make_stream_mesh(1) if where == "mesh1" else None
    _, got = run_config(edges, feats, mesh, driver, backend)
    assert_answers_match(ref, got, f"{driver}-{backend}-{where}")


@needs4
@pytest.mark.parametrize("driver", ["tick", "super"])
def test_golden_serving_matrix_4dev_mesh(golden_ref, driver):
    """The matrix's mesh column on a real 4-device ("data",) mesh — query
    wire records actually cross devices on the extra all_to_all lane."""
    edges, feats, ref = golden_ref
    _, got = run_config(edges, feats, make_stream_mesh(4), driver, "xla")
    assert_answers_match(ref, got, f"4dev-{driver}")


# ------------------------------------------------------------- ServeSession

def test_serve_session_both_drivers():
    edges, feats = make_stream()
    e_chunks, f_chunks = chunked(edges, feats)
    results = {}
    for driver in ("tick", "super"):      # T = 1 against T = 4 launches
        _, _, pipe = build_pipe()
        s = ServeSession(pipe, super_ticks=1 if driver == "tick" else 4)
        s.submit_embed([0], consistent=False)
        s.submit_embed([5], consistent=True)
        s.submit_link([(int(edges[0, 0]), int(edges[0, 1]))],
                      consistent=True)
        if driver == "tick":
            for ch, fe in zip(e_chunks, f_chunks):
                s.advance(ch, fe)
        else:
            s.advance_super(e_chunks, f_chunks, T=len(e_chunks))
        s.flush()
        assert s.outstanding == 0
        stats = s.latency_stats()
        assert stats["answered"] == 3
        assert stats["p99_ms"] >= stats["p50_ms"] >= 0
        results[driver] = s.answers
    # both launch sizes resolve the same queries with the same payloads
    assert set(results["tick"]) == set(results["super"])
    for qid, a in results["tick"].items():
        b = results["super"][qid]
        assert (a.kind, a.ok) == (b.kind, b.ok)
        np.testing.assert_allclose(a.vec, b.vec, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(a.score, b.score, rtol=1e-4, atol=1e-5)


def test_serve_session_latency_stats_single_population():
    """latency_stats bugfix (ISSUE 6): staleness percentiles used to run
    over ALL answers while latency percentiles skipped adopted ones
    (latency_s=None) — two silently different populations. Both must
    filter identically, with adopted answers counted separately."""
    from repro.serve.session import Answer
    _, _, pipe = build_pipe()
    s = ServeSession(pipe, super_ticks=1)
    # two timed answers (staleness 1, 3) + one ADOPTED answer with a huge
    # staleness that must NOT leak into the percentile population
    s.answers[0] = Answer(qid=0, kind=KIND_EMBED, ok=True,
                          vec=np.zeros(12, np.float32), score=0.0,
                          issue_tick=0, answer_tick=1, latency_s=0.010)
    s.answers[1] = Answer(qid=1, kind=KIND_EMBED, ok=True,
                          vec=np.zeros(12, np.float32), score=0.0,
                          issue_tick=0, answer_tick=3, latency_s=0.030)
    s.answers[2] = Answer(qid=2, kind=KIND_EMBED, ok=True,
                          vec=np.zeros(12, np.float32), score=0.0,
                          issue_tick=0, answer_tick=500, latency_s=None)
    stats = s.latency_stats()
    assert stats["answered"] == 3 and stats["adopted"] == 1
    assert stats["staleness_ticks_max"] == 3          # not the adopted 500
    assert stats["p50_ms"] == pytest.approx(20.0)
    # all-adopted sessions report counts only (no percentile keys)
    s2 = ServeSession(pipe, super_ticks=1)
    s2.answers[9] = Answer(qid=9, kind=KIND_EMBED, ok=True,
                           vec=np.zeros(12, np.float32), score=0.0,
                           issue_tick=0, answer_tick=2, latency_s=None)
    st2 = s2.latency_stats()
    assert st2["answered"] == st2["adopted"] == 1 and "p50_ms" not in st2


def test_serve_session_answer_retention_bound():
    """`answers` is bounded by max_retained: the OLDEST harvested answers
    evict first, and the bound never blocks new answers from landing."""
    edges, feats = make_stream()
    e_chunks, f_chunks = chunked(edges, feats)
    _, _, pipe = build_pipe()
    s = ServeSession(pipe, super_ticks=1, max_retained=4)
    early = s.submit_embed([0, 1, 2])
    for ch, fe in zip(e_chunks, f_chunks):
        s.advance(ch, fe)
    s.flush()
    assert s.outstanding == 0 and set(s.answers) == set(early)
    late = s.submit_embed([3, 4, 5])
    s.advance()                            # admit the queued wave
    s.flush()
    assert s.outstanding == 0
    # 6 answers harvested, bound 4: the two OLDEST-harvested rows (both
    # from the first wave) evicted; the fresh wave is fully retained
    assert len(s.answers) == 4
    assert set(late) <= set(s.answers)
    assert len(set(early) & set(s.answers)) == 1
    with pytest.raises(ValueError, match="max_retained"):
        ServeSession(pipe, super_ticks=1, max_retained=0)
