"""Telemetry plane (ISSUE 9): occupancy-exactness on golden streams
(every gauge equals the integer count derivable from the plain
LocalRouter run), trace recorder roundtrip + schema gating, advisor
recommendations validated by zero-drop replay, and mesh parity at forced-4 (defer-ring
gauges vs the `defer_occupancy` oracle, telemetry on == off golden).
"""
import json
from pathlib import Path

import numpy as np
import jax
import pytest

from conftest import needs_devices, run_forced_devices
from repro.core import windowing as win
from repro.core.pipeline import D3Pipeline, PipelineConfig
from repro.core.state import defer_occupancy
from repro.graph.sage import GraphSAGE
from repro.telemetry.advisor import (apply_recommendation, recommend,
                                     replay_ok)
from repro.telemetry.trace import (TRACE_DEVICE_COLS, TRACE_HOST_COLS,
                                   TraceRecorder, load_trace)

N_NODES, D_IN = 32, 8

needs4 = needs_devices(4)

ALL_POLICIES = [win.WindowConfig(kind=win.STREAMING),
                win.WindowConfig(kind=win.TUMBLING, interval=3),
                win.WindowConfig(kind=win.SESSION, interval=3),
                win.WindowConfig(kind=win.ADAPTIVE)]

FLUSH_TICKS = 8


def make_stream(seed=0, n_edges=100):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, N_NODES, n_edges),
                      rng.integers(0, N_NODES, n_edges)], 1)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = {v: rng.normal(size=D_IN).astype(np.float32)
             for v in range(N_NODES)}
    return edges, feats


def build_pipe(window=None, telemetry=False, mesh=None, **cfg_kw):
    model = GraphSAGE((D_IN, 12, 12))
    params = model.init(jax.random.key(0))
    kw = dict(n_parts=4, node_cap=32, edge_cap=128, repl_cap=128,
              feat_cap=128, edge_tick_cap=32, max_nodes=N_NODES,
              window=window or win.WindowConfig(kind=win.STREAMING),
              telemetry=telemetry)
    kw.update(cfg_kw)
    return model, params, D3Pipeline(model, params, PipelineConfig(**kw),
                                     mesh=mesh)


def drive(pipe, e_chunks, f_chunks, driver):
    """Fixed tick sequence (chunks + FLUSH_TICKS empty ticks) so every
    pipeline in a test sees identical tick boundaries."""
    if driver == "tick":
        for e, f in zip(e_chunks, f_chunks):
            pipe.tick(e, f)
        for _ in range(FLUSH_TICKS):
            pipe.tick()
    else:
        pipe.run_super_tick(e_chunks, f_chunks, T=len(e_chunks))
        pipe.run_super_tick(T=FLUSH_TICKS)
    return pipe


# ------------------------------------- occupancy exactness (golden, local)

@pytest.mark.parametrize("driver", ["tick", "super"])
@pytest.mark.parametrize("window", ALL_POLICIES,
                         ids=[w.kind for w in ALL_POLICIES])
def test_occupancy_exactness_local(window, driver, tmp_path):
    """Every per-plane occupancy column equals the exact integer count
    from the plain (telemetry=False) per-tick LocalRouter run, on both
    drivers, and the traced pipeline's numerics are bit-identical."""
    edges, feats = make_stream()
    _, _, ref = build_pipe(window)
    e_chunks, f_chunks = ref.chunk_stream(edges, feats, 24)
    ref_rows = []
    for e, f in zip(e_chunks, f_chunks):
        ref_rows.append(ref.tick(e, f))
    for _ in range(FLUSH_TICKS):
        ref_rows.append(ref.tick())

    _, _, tel = build_pipe(window, telemetry=True)
    drive(tel, e_chunks, f_chunks, driver)
    cols = tel.trace.columns()
    T = len(ref_rows)
    assert len(tel.trace) == T

    exact = {
        "emitted_final": [int(r[-1].emitted) for r in ref_rows],
        "emitted_sum": [sum(int(s.emitted) for s in r) for r in ref_rows],
        "reduce_msgs": [sum(int(s.reduce_msgs) for s in r)
                        for r in ref_rows],
        "broadcast_msgs": [sum(int(s.broadcast_msgs) for s in r)
                           for r in ref_rows],
        "dropped": [sum(int(s.dropped) for s in r) for r in ref_rows],
        "suppressed": [sum(int(s.n_suppressed) for s in r)
                       for r in ref_rows],
        "outbox_demand": [max(int(s.emitted) + int(s.dropped) for s in r)
                          for r in ref_rows],
    }
    for col, want in exact.items():
        np.testing.assert_array_equal(cols[col], want, err_msg=col)
    # per-part demand peak: not derivable from the psum'd scalars, but
    # tightly bracketed by them — per layer the hottest part carries at
    # least the global demand / n_parts and at most all of it
    demand = np.asarray(exact["outbox_demand"])
    pp = cols["outbox_part_peak"]
    assert (pp >= -(-demand // 4)).all() and (pp <= demand).all()
    # LocalRouter: no wire, no route buckets, no defer rings — exactly 0
    for col in ("wire_rows", "route_deferred", "route_dropped",
                "occ_bc_defer", "occ_rmi_defer", "route_peak"):
        assert cols[col].sum() == 0, col
    # query/training planes compiled away -> their gauges are exactly 0
    for col in ("query_pending", "query_backlog", "train_labeled",
                "train_dirty", "q_admitted"):
        assert cols[col].sum() == 0, col
    # the untraced TickStats gauges are static zeros (compile-away knob)
    assert all(int(s.occ_bc_defer) == 0 and int(s.route_peak) == 0
               and int(s.outbox_part_peak) == 0
               for r in ref_rows for s in r)
    # telemetry on is numerically bit-identical to off
    np.testing.assert_array_equal(np.asarray(tel.sink),
                                  np.asarray(ref.sink))
    assert tel.metrics.emitted_total == ref.metrics.emitted_total
    # host columns: monotone tick clock, ingest counts, wall timings
    np.testing.assert_array_equal(cols["tick"], np.arange(T))
    np.testing.assert_array_equal(
        cols["edges_in"][:len(e_chunks)], [len(e) for e in e_chunks])
    assert (cols["wall_s"] > 0).all()
    assert cols["amortized"].all() if driver == "super" \
        else not cols["amortized"].any()
    # trace survives a disk roundtrip
    tel.save_trace(tmp_path / "t.npz")
    back = load_trace(tmp_path / "t.npz")
    for c in TRACE_DEVICE_COLS:
        np.testing.assert_array_equal(back.col(c), cols[c])


def test_query_plane_occupancy_gauges():
    """query_pending equals the device's held-slot population after each
    tick; q_admitted/q_answered match the flow counters."""
    from repro.serve.query import KIND_EMBED
    edges, feats = make_stream()
    _, _, pipe = build_pipe(telemetry=True, query_cap=8)
    pipe.run_stream(edges[:48], feats, tick_edges=24)
    base = len(pipe.trace)
    u = int(edges[0, 0])
    pipe.tick(edges[48:72], queries=[(1, KIND_EMBED, u, True),
                                     (2, KIND_EMBED, u, False)])
    held = int(np.asarray(jax.device_get(pipe.queries.pending)).sum())
    cols = pipe.trace.columns()
    assert cols["query_pending"][base] == held
    assert cols["q_admitted"][base] == 2
    assert cols["queries_in"][base] == 2
    pipe.flush(max_ticks=64)
    cols = pipe.trace.columns()
    assert cols["q_answered"].sum() == 2
    assert cols["query_pending"][-1] == 0


# --------------------------------------------- trace recorder & loader

def test_trace_roundtrip_schema_and_validation(tmp_path):
    rec = TraceRecorder(meta={"n_parts": 4})
    assert rec.meta["schema"] == 1
    row = np.arange(len(TRACE_DEVICE_COLS))
    rec.append({"tick": 0, "wall_s": 0.25, "edges_in": 7}, row)
    rec.append({"tick": 1, "wall_s": 0.5}, row * 2)
    rec.annotate(serving_p99_ms=3.5)
    with pytest.raises(ValueError, match="columns"):
        rec.append({"tick": 2}, np.zeros(3))
    p = tmp_path / "trace.npz"
    rec.save(p)
    tr = load_trace(p)
    assert len(tr) == 2
    assert tr.meta["n_parts"] == 4 and tr.meta["serving_p99_ms"] == 3.5
    np.testing.assert_array_equal(tr.col("route_peak"),
                                  [row[11], 2 * row[11]])
    np.testing.assert_allclose(tr.col("wall_s"), [0.25, 0.5])
    assert tr.col("edges_in")[0] == 7 and tr.col("edges_in")[1] == 0
    assert set(tr.columns) == set(TRACE_HOST_COLS + TRACE_DEVICE_COLS)
    # wrong schema version is rejected
    rec.meta["schema"] = 99
    rec.save(p)
    with pytest.raises(ValueError, match="schema"):
        load_trace(p)
    # a random npz is not a trace
    np.savez(tmp_path / "junk.npz", a=np.zeros(3))
    with pytest.raises(ValueError, match="meta"):
        load_trace(tmp_path / "junk.npz")


def test_defer_occupancy_oracle_helper():
    from dataclasses import replace as rep
    from repro.core.state import init_layer
    ls = init_layer(4, 8, D_IN, D_IN, bc_defer_rows=6, rmi_defer_rows=4)
    b, r = defer_occupancy(ls)
    assert (int(b), int(r)) == (0, 0)
    import jax.numpy as jnp
    ls = rep(ls, bc_defer_ok=jnp.array([1, 0, 1, 1, 0, 0], bool),
             rmi_defer_ok=jnp.array([0, 1, 0, 0], bool))
    b, r = defer_occupancy(ls)
    assert (int(b), int(r)) == (3, 1)


# --------------------------------------------------------------- advisor

def test_advisor_zero_drop_recommendation_replays_clean(tmp_path):
    """The full loop the CI bench lane runs, locally: record -> recommend
    -> validate bounds -> replay through the real pipeline with zero
    drops and identical numerics."""
    edges, feats = make_stream(n_edges=160)
    model, params, pipe = build_pipe(telemetry=True)
    pipe.run_stream_super(edges, feats, tick_edges=24, super_ticks=4)
    pipe.flush_super(max_ticks=64, T=4)
    pipe.save_trace(tmp_path / "TRACE.npz")
    trace = load_trace(tmp_path / "TRACE.npz")
    recs = recommend(trace)
    caps = recs["caps"]
    assert caps["outbox_cap"] % 4 == 0
    assert caps["outbox_cap"] >= trace.col("outbox_demand").max()
    assert caps["outbox_cap"] >= 4 * trace.col("outbox_part_peak").max()
    assert caps["edge_tick_cap"] >= trace.col("edges_in").max()
    assert caps["route_cap"] is None          # LocalRouter: no buckets
    assert caps["query_cap"] == 0 and caps["train_cap"] == 0
    assert recs["basis"]["ticks"] == len(trace)

    cfg2 = apply_recommendation(
        PipelineConfig(n_parts=4, node_cap=32, edge_cap=128, repl_cap=128,
                       max_nodes=N_NODES), recs)
    cfg2.validate()
    pipe2 = D3Pipeline(model, params, cfg2)
    pipe2.run_stream_super(edges, feats, tick_edges=24, super_ticks=4)
    pipe2.flush_super(max_ticks=64, T=4)
    out = replay_ok(pipe2)
    assert out["dropped"] == 0 and out["route_dropped"] == 0
    np.testing.assert_array_equal(np.asarray(pipe2.sink),
                                  np.asarray(pipe.sink))


def test_advisor_cli(tmp_path):
    from repro.telemetry.advisor import main
    edges, feats = make_stream(n_edges=80)
    _, _, pipe = build_pipe(telemetry=True)
    pipe.run_stream_super(edges, feats, tick_edges=24, super_ticks=4)
    pipe.save_trace(tmp_path / "TRACE.npz")
    out = tmp_path / "RECS.json"
    assert main([str(tmp_path / "TRACE.npz"), "--out", str(out),
                 "--slack", "1.5"]) == 0
    recs = json.loads(out.read_text())
    assert recs["schema"] == 1 and recs["slack"] == 1.5
    assert recs["caps"]["outbox_cap"] >= 4


# ----------------------------------------- mesh parity (>= 4 devices)

@needs4
def test_mesh_telemetry_exactness_and_parity(tmp_path):
    """Forced-4 mesh with a capped exchange: the defer-ring gauges equal
    the `defer_occupancy` oracle on the end-of-tick carry, route_peak is
    live, telemetry on == off bit-for-bit, the super-tick driver's
    device rows equal the per-tick driver's, and the advisor's
    recommended caps replay with zero drops and less wire than dense."""
    from repro.launch.mesh import make_stream_mesh
    edges, feats = make_stream(n_edges=140)
    mesh = make_stream_mesh(4)
    capped = dict(route_cap=8, route_defer_cap=64)

    _, _, tel = build_pipe(telemetry=True, mesh=mesh, **capped)
    e_chunks, f_chunks = tel.chunk_stream(edges, feats, 24)
    oracle_bc, oracle_rmi = [], []
    for e, f in zip(e_chunks, f_chunks):
        tel.tick(e, f)
        occ = [defer_occupancy(ls) for ls in tel.states]
        oracle_bc.append(sum(int(b) for b, _ in occ))
        oracle_rmi.append(sum(int(r) for _, r in occ))
    for _ in range(FLUSH_TICKS):
        tel.tick()
        occ = [defer_occupancy(ls) for ls in tel.states]
        oracle_bc.append(sum(int(b) for b, _ in occ))
        oracle_rmi.append(sum(int(r) for _, r in occ))
    cols = tel.trace.columns()
    np.testing.assert_array_equal(cols["occ_bc_defer"], oracle_bc)
    np.testing.assert_array_equal(cols["occ_rmi_defer"], oracle_rmi)
    assert cols["route_peak"].max() > 0
    # every pre-cap demand row ships, defers, or drops in its tick
    assert (cols["route_peak"] <= cols["wire_rows"]
            + cols["route_deferred"] + cols["route_dropped"]).all()
    assert tel.metrics.route_peak == cols["route_peak"].max()
    assert tel.metrics.outbox_peak == cols["outbox_demand"].max()
    assert cols["outbox_part_peak"].max() > 0
    assert tel.metrics.outbox_part_peak == cols["outbox_part_peak"].max()

    # telemetry off: identical numerics (bit-for-bit golden)
    _, _, off = build_pipe(mesh=mesh, **capped)
    for e, f in zip(e_chunks, f_chunks):
        off.tick(e, f)
    for _ in range(FLUSH_TICKS):
        off.tick()
    np.testing.assert_array_equal(np.asarray(tel.sink),
                                  np.asarray(off.sink))
    assert tel.metrics.emitted_total == off.metrics.emitted_total
    assert tel.metrics.wire_rows == off.metrics.wire_rows

    # super-tick driver: same tick boundaries -> identical device rows
    _, _, sup = build_pipe(telemetry=True, mesh=mesh, **capped)
    drive(sup, e_chunks, f_chunks, "super")
    sup_cols = sup.trace.columns()
    for c in TRACE_DEVICE_COLS:
        np.testing.assert_array_equal(sup_cols[c], cols[c], err_msg=c)

    # advisor: record the observability trace DENSE (peaks recorded
    # under a capped config are only valid for that config's deferral
    # dynamics), then the zero-defer sizing route_cap = max route_peak
    # replays bit-identically to dense with strictly less wire
    model, params, dense = build_pipe(telemetry=True, mesh=mesh)
    drive(dense, e_chunks, f_chunks, "super")
    dense.save_trace(tmp_path / "MESH.npz")
    trace = load_trace(tmp_path / "MESH.npz")
    recs = recommend(trace)
    assert recs["caps"]["route_cap"] == \
        int(dense.trace.columns()["route_peak"].max())
    cfg2 = apply_recommendation(
        PipelineConfig(n_parts=4, node_cap=32, edge_cap=128, repl_cap=128,
                       max_nodes=N_NODES), recs)
    rep = D3Pipeline(model, params, cfg2, mesh=mesh)
    drive(rep, e_chunks, f_chunks, "super")
    replay_ok(rep)
    assert rep._wire_bytes_per_tick <= dense._wire_bytes_per_tick
    assert rep.metrics.route_deferred == 0   # zero-defer sizing held
    np.testing.assert_array_equal(np.asarray(rep.sink),
                                  np.asarray(dense.sink))


def test_telemetry_forced4_subprocess():
    r = run_forced_devices(4, Path(__file__),
                           ["-k", "mesh_telemetry"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
