"""Golden equivalence of T-tick and one-tick launches (ISSUE 1 tentpole).

A launch of T micro-ticks (`run_super_tick`: one jitted `lax.scan` over T
micro-ticks x L layers) must produce the SAME materialized embeddings as
T one-tick launches (`tick()`, `run_stream`, `flush`), and both must match
the static oracle on the final snapshot — across all four window policies.
"""
import numpy as np
import jax
import pytest

from repro.core import windowing as win
from repro.core.oracle import build_snapshot, oracle_embeddings
from repro.core.pipeline import D3Pipeline, PipelineConfig
from repro.graph.sage import GraphSAGE

N_NODES, D_IN = 48, 8


def make_stream(seed=0, n_edges=160):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, N_NODES, n_edges),
                      rng.integers(0, N_NODES, n_edges)], 1)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = {v: rng.normal(size=D_IN).astype(np.float32)
             for v in range(N_NODES)}
    return edges, feats


def build_pipe(window):
    model = GraphSAGE((D_IN, 12, 12))
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=4, node_cap=48, edge_cap=192, repl_cap=192,
                         feat_cap=256, edge_tick_cap=48, max_nodes=N_NODES,
                         window=window)
    return model, params, D3Pipeline(model, params, cfg)


def test_super_tick_matches_per_tick_and_oracle_streaming(
        streamed_pipeline, super_streamed_pipeline):
    """STREAMING golden triplet on the shared session pipelines: the two
    drivers ran the SAME stream with the SAME tick boundaries, so their
    sinks must agree bit-for-bit at fp tolerance, and both match the static
    oracle."""
    ref, sup = streamed_pipeline, super_streamed_pipeline
    e_ref, e_sup = ref.pipe.embeddings(), sup.pipe.embeddings()
    assert set(e_ref) == set(e_sup)
    for vid in e_ref:
        np.testing.assert_allclose(e_sup[vid], e_ref[vid],
                                   rtol=1e-5, atol=1e-5)
    g, _ = build_snapshot(ref.case.edges, ref.case.feats,
                          ref.case.d_in, ref.case.n_nodes)
    oracle = np.asarray(oracle_embeddings(ref.model, ref.params, g))
    for vid in e_sup:
        np.testing.assert_allclose(e_sup[vid], oracle[vid],
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kind", [win.TUMBLING, win.SESSION, win.ADAPTIVE])
def test_super_tick_matches_per_tick_and_oracle(kind):
    edges, feats = make_stream()
    w = win.WindowConfig(kind=kind, interval=3)

    model, params, ref = build_pipe(w)
    ref.run_stream(edges, feats, tick_edges=32)
    ref.flush(max_ticks=96)

    _, _, sup = build_pipe(w)
    sup.run_stream_super(edges, feats, tick_edges=32, super_ticks=4)
    sup.flush_super(max_ticks=96, T=4)

    e_ref, e_sup = ref.embeddings(), sup.embeddings()
    assert set(e_ref) == set(e_sup)
    for vid in e_ref:
        np.testing.assert_allclose(e_sup[vid], e_ref[vid],
                                   rtol=1e-5, atol=1e-5)

    g, _ = build_snapshot(edges, feats, D_IN, N_NODES)
    oracle = np.asarray(oracle_embeddings(model, params, g))
    for vid in e_sup:
        np.testing.assert_allclose(e_sup[vid], oracle[vid],
                                   rtol=1e-4, atol=1e-4)


def test_super_tick_single_sync_stats_match_per_tick():
    """The summed TickStats carried through the scan equal the per-tick
    driver's accumulation when tick boundaries line up exactly."""
    edges, feats = make_stream(seed=3, n_edges=128)
    w = win.WindowConfig(kind=win.STREAMING)

    _, _, ref = build_pipe(w)
    ref.run_stream(edges, feats, tick_edges=32)

    _, _, sup = build_pipe(w)
    n_chunks = -(-len(edges) // 32)
    sup.run_stream_super(edges, feats, tick_edges=32, super_ticks=n_chunks)

    # identical tick boundaries -> identical counters (no fp involved)
    assert sup.metrics.ticks == ref.metrics.ticks
    assert sup.metrics.reduce_msgs == ref.metrics.reduce_msgs
    assert sup.metrics.broadcast_msgs == ref.metrics.broadcast_msgs
    assert sup.metrics.cross_part_msgs == ref.metrics.cross_part_msgs
    assert sup.metrics.emitted_total == ref.metrics.emitted_total
    np.testing.assert_array_equal(sup.metrics.busy_logical,
                                  ref.metrics.busy_logical)


def test_flush_super_reports_quiescence():
    edges, feats = make_stream(seed=5, n_edges=96)
    _, _, pipe = build_pipe(win.WindowConfig(kind=win.SESSION, interval=4))
    pipe.run_stream_super(edges, feats, tick_edges=48, super_ticks=2)
    n = pipe.flush_super(max_ticks=64, T=4)
    assert n >= 2
    from repro.core.tick import has_work
    assert not any(bool(has_work(ls)) for ls in pipe.states)
    # a fresh empty super-tick on a quiescent pipeline stays quiescent
    _, quiet = pipe.run_super_tick(T=4)
    assert quiet >= 4


def test_stacked_batches_pad_short_super_tick():
    """Fewer staged ticks than T: the tail is padded with empty ticks and
    the embeddings still match T=1 launches."""
    edges, feats = make_stream(seed=7, n_edges=64)
    w = win.WindowConfig(kind=win.STREAMING)
    _, _, ref = build_pipe(w)
    ref.run_stream(edges, feats, tick_edges=32)
    ref.flush(max_ticks=64)

    _, _, sup = build_pipe(w)
    sup.run_stream_super(edges, feats, tick_edges=32, super_ticks=8)
    sup.flush_super(max_ticks=64, T=4)
    e_ref, e_sup = ref.embeddings(), sup.embeddings()
    assert set(e_ref) == set(e_sup)
    for vid in e_ref:
        np.testing.assert_allclose(e_sup[vid], e_ref[vid],
                                   rtol=1e-5, atol=1e-5)
