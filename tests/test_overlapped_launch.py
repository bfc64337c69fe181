"""The pending super-tick launch (`D3Pipeline.run_super_tick`): a launch
that the host need not answer before the next one returns before its
sync, and the next launch stages while the device runs it.

  * deferring the sync changes no result: state, sink and every counter
    match launches whose values are read at once;
  * `launches_overlapped` counts the deferred launches, and stays 0 for
    launches that admit queries or labels, with the telemetry plane on,
    and for flush launches;
  * serving that interleaves query-free launches with launches that
    admit queries answers as a fully synchronous run does, retries
    included;
  * a staging error in launch k+1 still leaves launch k folded.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import windowing as win
from repro.core.pipeline import D3Pipeline, PipelineConfig, StreamMetrics
from repro.core.train_plane import TrainConfig
from repro.graph.sage import GraphSAGE
from repro.optim import sgd
from repro.serve.session import ServeSession

N_NODES, D_IN, TICK, T, N_CLS = 48, 8, 16, 4, 3
SESSION = win.WindowConfig(kind=win.SESSION, interval=2)


def make_stream(seed=0, n_edges=6 * T * TICK, n_nodes=N_NODES):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, n_nodes, n_edges),
                      rng.integers(0, n_nodes, n_edges)], 1)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = {v: rng.normal(size=D_IN).astype(np.float32)
             for v in range(n_nodes)}
    return edges, feats


def build(telemetry=False, node_cap=64, train=False):
    model = GraphSAGE((D_IN, 12, 12), n_classes=N_CLS if train else 0)
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=4, node_cap=node_cap, edge_cap=256,
                         repl_cap=256, feat_cap=64, edge_tick_cap=TICK,
                         max_nodes=N_NODES, query_cap=8, query_tick_cap=4,
                         telemetry=telemetry, window=SESSION,
                         train_cap=16 if train else 0)
    tcfg = TrainConfig(optimizer=sgd(), batch_threshold=1) if train \
        else None
    return D3Pipeline(model, params, cfg, train=tcfg)


def launches(pipe, edges, feats):
    """The stream cut into super-tick launches of T micro-ticks."""
    e_chunks, f_chunks = pipe.chunk_stream(edges, feats, TICK)
    return [(e_chunks[lo: lo + T], f_chunks[lo: lo + T])
            for lo in range(0, len(e_chunks), T)]


def counters(m: StreamMetrics) -> dict:
    """Every counter but the overlap count; span timings are wall time."""
    skip = {"spans", "launches_overlapped"}
    return {f.name: getattr(m, f.name) for f in dataclasses.fields(m)
            if f.name not in skip}


def assert_same_state(a, b):
    np.testing.assert_array_equal(np.asarray(a.sink), np.asarray(b.sink))
    np.testing.assert_array_equal(np.asarray(a.sink_seen),
                                  np.asarray(b.sink_seen))
    for sa, sb in zip(a.states, b.states):
        for x, y in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    ca, cb = counters(a.metrics), counters(b.metrics)
    assert ca.keys() == cb.keys()
    for k in ca:
        np.testing.assert_array_equal(ca[k], cb[k], err_msg=k)


def test_deferred_launches_reach_the_same_state():
    """Two rounds of three launches and a flush: a serving session
    (every launch deferred) against the same launches read at once."""
    edges, feats = make_stream(seed=1)
    deferred = ServeSession(build(), driver="super", super_ticks=T)
    sync = build()
    plan = launches(sync, edges, feats)
    assert len(plan) == 6
    kept, read = [], []
    for rnd in (plan[:3], plan[3:]):
        for e, f in rnd:
            kept.append(deferred.advance_super(e, f, T=T))
            stats, quiet = sync.run_super_tick(e, f, T=T)
            read.append((stats, quiet))
        deferred.flush()
        sync.flush_super(T=T)
        assert_same_state(deferred.pipe, sync)
    assert deferred.pipe.metrics.launches_overlapped == 6
    assert sync.metrics.launches_overlapped == 0
    # a deferred launch's value, read late, is the one read at once
    for got, want in zip(kept, read):
        assert len(got) == 2 and got[1] == want[1]
        for x, y in zip(jax.tree.leaves(got[0]), jax.tree.leaves(want[0])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _ingest(pipe, edges, feats, queries=False, labels=False):
    """Every launch of the stream, then a flush; optionally a query or
    a label admitted in every launch."""
    vids = np.unique(edges)
    for i, (e, f) in enumerate(launches(pipe, edges, feats)):
        q = [[(i, 0, int(vids[i % len(vids)]), False)]] if queries else None
        lab = [[(int(vids[i % len(vids)]), i % N_CLS)]] if labels else None
        pipe.run_super_tick(e, f, T=T, query_chunks=q, label_chunks=lab)
    pipe.flush_super(T=T)
    return pipe.metrics


@pytest.mark.parametrize("case, want", [
    ("query_free", 6), ("every_launch_a_query", 0),
    ("every_launch_a_label", 0), ("telemetry", 0)])
def test_launches_overlapped(case, want):
    edges, feats = make_stream(seed=2)
    pipe = build(telemetry=case == "telemetry",
                 train=case == "every_launch_a_label")
    m = _ingest(pipe, edges, feats,
                queries=case == "every_launch_a_query",
                labels=case == "every_launch_a_label")
    assert m.launches - m.drain_launches == 6
    assert m.launches_overlapped == want
    # one sync a launch, wherever it fell
    assert m.spans["d3.sync"].count == m.launches


def test_flush_launches_are_not_overlapped():
    edges, feats = make_stream(seed=3)
    pipe = build()
    for e, f in launches(pipe, edges, feats)[:3]:
        pipe.run_super_tick(e, f, T=T)
    before = pipe.metrics.launches_overlapped     # settles the third
    assert before == 2
    pipe.flush_super(T=T)
    m = pipe.metrics
    assert m.drain_launches > 0
    assert m.launches_overlapped == before


def _serve(read_at_once: bool):
    """Query-free launches interleaved with launches that admit stale_ok
    and consistent queries, some for vertices not streamed yet (ok=False,
    retried), then trickle launches of one edge in which the consistent
    query is answered. Returns the session and, after every launch, the
    answered qids and the session's counters."""
    edges, feats = make_stream(seed=4)
    s = ServeSession(build(), driver="super", super_ticks=T, max_retries=2,
                     retry_backoff_ticks=2)
    half = len(edges) // 2
    late = sorted(set(np.unique(edges[half:]).tolist())
                  - set(np.unique(edges[:half]).tolist()))
    steps = []

    def advance(e, f, T):
        out = s.advance_super(e, f, T=T)
        if read_at_once:           # the sync and harvest of the launch
            tuple(out)
            s._harvest()
        steps.append((sorted(s.answers), dict(s.counters)))

    plan = launches(s.pipe, edges, feats)
    for i, (e, f) in enumerate(plan[:4]):
        if i == 1:
            s.submit_embed([int(edges[0, 0]), int(edges[1, 1])])
            s.submit_embed(late[:2] or [N_NODES - 1])
        advance(e, f, T)
    s.submit_embed([int(edges[2, 0])], consistent=True)
    s.submit_link([(int(edges[0, 0]), int(edges[3, 1]))])
    advance(*plan[4], T)
    for k in range(3):
        advance([edges[k: k + 1]], None, 2 * T)
    s.flush()
    return s, steps


def test_interleaved_queries_answer_as_a_synchronous_run():
    (got, got_steps), (want, want_steps) = _serve(False), _serve(True)
    assert got.pipe.metrics.launches_overlapped > 0
    assert want.pipe.metrics.launches_overlapped == 0
    # the same answers harvested by the same launch, retries included
    assert got_steps == want_steps
    assert got.counters == want.counters
    assert got.counters["retried"] > 0
    assert len(got_steps[4][0]) < len(got_steps[-1][0])  # in the trickle
    assert sorted(got.answers) == sorted(want.answers)
    for qid, a in want.answers.items():
        b = got.answers[qid]
        assert (b.ok, b.kind, b.issue_tick, b.answer_tick, b.score) == \
            (a.ok, a.kind, a.issue_tick, a.answer_tick, a.score), qid
        np.testing.assert_array_equal(b.vec, a.vec)
    assert got.outstanding == want.outstanding == 0


def test_staging_error_leaves_the_launch_before_folded():
    """Launch k fits the vertex tables, launch k+1 overflows them while
    it is staged: launch k's stats fold all the same."""
    edges, feats = make_stream(seed=5, n_edges=T * TICK, n_nodes=8)
    more, more_feats = make_stream(seed=6, n_edges=T * TICK)
    ref = build(node_cap=8)
    first = launches(ref, edges, feats)[0]
    ref.run_super_tick(*first, T=T)[0]
    pipe = build(node_cap=8)
    pipe.run_super_tick(*first, T=T)
    with pytest.raises(RuntimeError, match="node_cap"):
        pipe.run_super_tick(*launches(pipe, more, more_feats)[0], T=T)
    m, r = pipe.metrics, ref.metrics
    assert m.ticks == r.ticks == T
    for k in ("emitted_total", "reduce_msgs", "broadcast_msgs",
              "cross_part_msgs", "dropped", "edges_staged"):
        assert getattr(m, k) == getattr(r, k), k
    np.testing.assert_array_equal(m.busy_logical, r.busy_logical)
    assert m.spans["d3.sync"].count == 1
