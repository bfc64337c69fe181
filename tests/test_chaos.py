"""Chaos plane (ISSUE 10): live elastic resharding goldens, fault
injection scenarios, and degraded-mode serving.

The reshard goldens pin the headline invariant: an UNCAPPED 1-D run is
bit-equal across ANY device count (canonical delivery order), so a
mid-stream `D3Pipeline.reshard` — in either direction, one tick a launch
or several, with in-flight windows, defer rings, and held consistent
queries — must leave the flushed sink bit-equal to the local
single-device run, with identical logical integer stats and zero drops.

The chaos scenarios (`repro.ft.chaos`) then make something go WRONG on
purpose — fail-stop shard loss, a torn checkpoint write, a fail-slow
shard, an admission storm — and assert the declared recovery behavior,
deterministically (seeded streams, tick-indexed fault schedules, no
wall clock).

Multi-device tests carry `needs_devices`; the subprocess smokes at the
bottom re-run them on a forced 4-device CPU so single-device machines
still cover the matrix (fast lane: one golden; slow lane: everything).
"""
from dataclasses import asdict
from pathlib import Path

import numpy as np
import jax
import pytest

from conftest import needs_devices, run_forced_devices

needs4 = needs_devices(4)

# logical (device-count-invariant) integer stats: equal across local /
# meshed / resharded runs of the same stream
STAT_KEYS = ("ticks", "emitted_total", "reduce_msgs", "broadcast_msgs",
             "cross_part_msgs", "dropped", "route_dropped",
             "queries_admitted", "queries_answered", "suppressed")


def _stats(pipe):
    m = asdict(pipe.metrics)
    return {k: m[k] for k in STAT_KEYS}


def _stream(n=32, d_in=8, n_events=150, seed=0):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, n, n_events),
                      rng.integers(0, n, n_events)], 1)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = {v: rng.normal(size=d_in).astype(np.float32) for v in range(n)}
    return edges, feats


def _build(D, S=1, n=32, d_in=8, **cfg_kw):
    from repro.core import windowing as win
    from repro.core.pipeline import D3Pipeline, PipelineConfig
    from repro.graph.sage import GraphSAGE
    from repro.launch.mesh import make_stream_mesh
    model = GraphSAGE((d_in, d_in, d_in))
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=4, node_cap=32, edge_cap=128, repl_cap=128,
                         feat_cap=128, edge_tick_cap=32, max_nodes=n,
                         n_stages=S,
                         window=win.WindowConfig(kind=win.SESSION,
                                                 interval=3), **cfg_kw)
    mesh = make_stream_mesh(D * S, stage=S) if D else None
    return D3Pipeline(model, params, cfg, mesh=mesh)


def _feed(pipe, edges, feats, driver, tick_edges=16):
    """driver "tick": one micro-tick a launch; "super": one launch."""
    chunks = [edges[i:i + tick_edges]
              for i in range(0, len(edges), tick_edges)]
    rows = [[(int(v), feats[int(v)]) for e in c for v in set(map(int, e))]
            for c in chunks]
    if driver == "tick":
        for c, r in zip(chunks, rows):
            pipe.tick(c, r)
    else:
        pipe.run_super_tick(chunks, rows)


def _run(D, edges, feats, driver="tick", reshard_mesh=None, S=1, **cfg_kw):
    pipe = _build(D, S=S, **cfg_kw)
    half = (len(edges) // 32) * 16          # chunk-aligned midpoint
    _feed(pipe, edges[:half], feats, driver)
    if reshard_mesh is not None:
        pipe.reshard(reshard_mesh() if callable(reshard_mesh)
                     else reshard_mesh)
    _feed(pipe, edges[half:], feats, driver)
    pipe.flush(max_ticks=128)
    return np.asarray(jax.device_get(pipe.sink)), _stats(pipe), pipe


# ----------------------------------------------------- reshard goldens
@pytest.fixture(scope="module")
def golden_case():
    edges, feats = _stream()
    sink, stats, _ = _run(None, edges, feats)
    return edges, feats, sink, stats


@needs4
@pytest.mark.parametrize("driver", ["tick", "super"])
@pytest.mark.parametrize("d_old,d_new", [(4, 2), (2, 4)],
                         ids=["down", "up"])
def test_reshard_mid_stream_golden(golden_case, driver, d_old, d_new):
    """Mid-stream reshard (scale-down AND scale-up, T=1 and T=k) with
    in-flight windows: the flushed sink is BIT-equal to the local run and
    every logical integer stat matches exactly. Nothing dropped."""
    from repro.launch.mesh import make_stream_mesh
    edges, feats, base_sink, base_stats = golden_case
    sink, stats, _ = _run(d_old, edges, feats, driver,
                          reshard_mesh=lambda: make_stream_mesh(d_new))
    np.testing.assert_array_equal(base_sink, sink)
    assert stats == base_stats
    assert stats["dropped"] == 0 and stats["route_dropped"] == 0


@needs4
def test_reshard_to_local_and_survivors(golden_case):
    """Degenerate directions: mesh -> LocalRouter, and a survivor mesh
    built from the live mesh minus 'lost' shards."""
    from repro.launch.mesh import make_stream_mesh, survivor_mesh
    edges, feats, base_sink, _ = golden_case
    sink, _, pipe = _run(4, edges, feats, reshard_mesh=lambda: None)
    np.testing.assert_array_equal(base_sink, sink)
    assert pipe.mesh is None
    sink2, stats2, pipe2 = _run(
        4, edges, feats,
        reshard_mesh=lambda: survivor_mesh(make_stream_mesh(4), [1, 3]))
    np.testing.assert_array_equal(base_sink, sink2)
    assert pipe2._n_data == 2 and stats2["route_dropped"] == 0


@needs4
def test_reshard_capped_defer_rings_survive(golden_case):
    """Capped wire (route_cap set, unbounded defer): the defer rings hold
    in-flight rows across the reshard — ZERO route drops. Deferral shifts
    rows across tick boundaries, so vs the uncapped local run the sink is
    fixed-point (allclose), not bit, equal."""
    from repro.launch.mesh import make_stream_mesh
    edges, feats, base_sink, _ = golden_case
    sink, stats, _ = _run(4, edges, feats,
                          reshard_mesh=lambda: make_stream_mesh(2),
                          route_cap=8, route_defer_cap=None)
    np.testing.assert_allclose(base_sink, sink, rtol=1e-5, atol=1e-5)
    assert stats["route_dropped"] == 0 and stats["dropped"] == 0


@needs4
@pytest.mark.parametrize("driver", ["tick", "super"])
def test_reshard_stage_grid_data_axis(golden_case, driver):
    """2-D grid, data-axis reshard (S=2, D=2 -> D=1): bit-equal to the
    uninterrupted SAME-stage-count run (S>1 schedules are fixed-point,
    not bit, equal to S=1 — PR7), allclose to the local run."""
    from repro.launch.mesh import make_stream_mesh
    edges, feats, base_sink, _ = golden_case
    ref, ref_stats, _ = _run(2, edges, feats, driver, S=2)
    sink, stats, _ = _run(2, edges, feats, driver, S=2,
                          reshard_mesh=lambda: make_stream_mesh(2, stage=2))
    np.testing.assert_array_equal(ref, sink)
    assert stats == ref_stats
    np.testing.assert_allclose(base_sink, sink, rtol=1e-5, atol=1e-5)


@needs4
def test_reshard_stage_change_needs_quiescence(golden_case):
    """Changing the STAGE count with rows still in the stage ring raises
    (flush to quiescence first); after a flush it succeeds, and the
    result is allclose to the local run (stage-count change re-schedules
    the float reductions — fixed-point, not bit, equality)."""
    from repro.launch.mesh import make_stream_mesh
    edges, feats, base_sink, _ = golden_case
    pipe = _build(2, S=2)
    _feed(pipe, edges[:96], feats, "tick")   # leaves rows in the ring
    with pytest.raises(RuntimeError, match="flush"):
        pipe.reshard(make_stream_mesh(4))
    pipe.flush(max_ticks=128)
    pipe.reshard(make_stream_mesh(4))
    _feed(pipe, edges[96:], feats, "tick")
    pipe.flush(max_ticks=128)
    sink = np.asarray(jax.device_get(pipe.sink))
    np.testing.assert_allclose(base_sink, sink, rtol=1e-5, atol=1e-5)


@needs4
def test_straggler_remap_on_stage_grid():
    """Fail-slow shard under a 2-stage grid: the synthetic wall schedule
    flags the slow data shard, `mitigate_stragglers()` reshards onto the
    survivors, and `parts_per_shard()` re-maps end-to-end."""
    from repro.ft.chaos import ChaosConfig, scenario_slow_shard
    rep = scenario_slow_shard(ChaosConfig(), d_old=2, n_stages=2)
    assert rep["plan"] is not None and rep["n_data_after"] == 1
    assert [p.tolist() for p in rep["parts_after"]] == [[0, 1, 2, 3]]
    assert rep["dropped"] == 0 and rep["route_dropped"] == 0


# ----------------------------------------------------- chaos scenarios
@needs4
def test_chaos_failstop_recovery_bit_equal(tmp_path):
    """The ISSUE 10 acceptance scenario: hub-heavy spike + fail-stop loss
    of 2/4 shards mid-stream -> checkpoint-restore + reshard onto the
    survivor mesh + replay. dropped == 0, route_dropped == 0, the held
    consistent answers are bit-equal to the uninterrupted oracle's, and
    the post-recovery sink is bit-equal to the oracle run."""
    from repro.ft.chaos import ChaosConfig, scenario_failstop
    rep = scenario_failstop(ChaosConfig(), tmp_path)
    assert rep["dropped"] == 0 and rep["route_dropped"] == 0
    np.testing.assert_array_equal(rep["oracle_sink"], rep["chaos_sink"])
    assert rep["oracle_answers"] and (set(rep["oracle_answers"])
                                      == set(rep["chaos_answers"]))
    for qid, oa in rep["oracle_answers"].items():
        ca = rep["chaos_answers"][qid]
        assert ca.ok and oa.ok
        np.testing.assert_array_equal(oa.vec, ca.vec)
    assert rep["restored_step"] == rep["cut"]
    assert rep["stats"]["degraded"] is None         # restored to normal
    assert rep["stats"]["degraded_ticks"] > 0       # but it WAS degraded


def test_chaos_truncated_checkpoint(tmp_path):
    """Torn checkpoint write: explicit-step restore fails loudly with
    step + path; latest-restore warns and falls back a generation."""
    from repro.ft.chaos import ChaosConfig, scenario_truncated_checkpoint
    rep = scenario_truncated_checkpoint(ChaosConfig(), tmp_path)
    assert rep["explicit_error"] is not None
    assert f"step {rep['torn_step']}" in rep["explicit_error"]
    assert ".ckpt" in rep["explicit_error"]
    assert rep["restored_step"] == rep["torn_step"] - 1
    assert rep["fallback_warned"]


@needs4
def test_chaos_slow_shard_mitigated():
    """Fail-slow shard: flagged by the deterministic wall schedule, then
    resharded away — it owns zero parts afterwards, nothing dropped."""
    from repro.ft.chaos import ChaosConfig, scenario_slow_shard
    cfg = ChaosConfig()
    rep = scenario_slow_shard(cfg)
    assert rep["plan"] is not None and rep["mitigated_at_chunk"] is not None
    assert rep["n_data_after"] == 2                 # 4 -> 2 (divisor of 4)
    assert sum(len(p) for p in rep["parts_after"]) == cfg.n_parts
    assert rep["dropped"] == 0 and rep["route_dropped"] == 0


def test_chaos_admission_storm_degrades_observably():
    """A 96-query burst against an 8/tick admission budget: the session
    sheds beyond the threshold, bound-retries the retriable failures,
    late-materializing endpoints answer ok on a retry, and every counter
    lands in latency_stats(). Nothing silent, nothing stuck."""
    from repro.ft.chaos import ChaosConfig, scenario_admission_storm
    rep = scenario_admission_storm(ChaosConfig())
    st = rep["stats"]
    assert st["shed"] > 0 and st["retried"] > 0
    assert rep["storm_resolved"] == rep["n_storm"]
    assert rep["late_ok"] and all(rep["late_ok"].values())
    assert rep["outstanding"] == 0
    assert rep["dropped"] == 0 and rep["route_dropped"] == 0


# ------------------------------------------- ServeSession degraded mode
def _serve(**kw):
    from repro.ft.chaos import ChaosConfig, build_pipeline
    from repro.serve.session import ServeSession
    return ServeSession(build_pipeline(ChaosConfig()), super_ticks=1,
                        **kw)


def _tick_edges(session, edges, feats):
    rows = [(int(v), feats[int(v)]) for e in edges for v in set(map(int, e))]
    session.advance(edges, rows)


def test_session_shed_threshold():
    """Submissions beyond shed_threshold get an immediate ok=False shed
    answer instead of unbounded queue growth."""
    s = _serve(shed_threshold=4)
    qids = s.submit_embed(range(8))
    st = s.latency_stats()
    assert st["shed"] == 6                # 2 queued count double (known)
    shed = [q for q in qids if q in s.answers]
    assert len(shed) == 6 and all(not s.answers[q].ok for q in shed)


def test_session_degraded_holds_consistent():
    """degrade(): stale_ok flows, consistent held until restore_normal();
    the declared reason + degraded tick count surface in stats."""
    from repro.ft.chaos import ChaosConfig, hub_heavy_stream
    cfg = ChaosConfig()
    edges, feats, _ = hub_heavy_stream(cfg)
    s = _serve()
    _tick_edges(s, edges[:32], feats)
    s.flush()                              # materialize some embeddings
    vid = int(edges[0, 0])
    s.degrade("drill")
    q_stale = s.submit_embed([vid])
    q_cons = s.submit_embed([vid], consistent=True)
    for _ in range(3):
        s.advance(None, None)
    assert s.degraded == "drill"
    assert q_stale[0] in s.answers and s.answers[q_stale[0]].ok
    assert q_cons[0] not in s.answers      # held in the host queue
    st = s.latency_stats()
    assert st["degraded"] == "drill" and st["degraded_ticks"] == 3
    s.restore_normal()
    for _ in range(3):
        s.advance(None, None)
    s.flush()
    assert q_cons[0] in s.answers and s.answers[q_cons[0]].ok
    assert s.latency_stats()["degraded"] is None


def test_session_bounded_retry_backoff():
    """A retriable ok=False answer (unknown vertex) is resubmitted under
    the SAME qid with exponential tick backoff, capped at max_retries;
    exhaustion surfaces as a final failed answer + counter."""
    s = _serve(max_retries=2, retry_backoff_ticks=1)
    q = s.submit_embed([47])               # never materializes
    ticks = 0
    while q[0] not in s.answers and ticks < 32:
        s.advance(None, None)
        ticks += 1
    st = s.latency_stats()
    assert q[0] in s.answers and not s.answers[q[0]].ok
    assert st["retried"] == 2 and st["retry_exhausted"] == 1
    assert s.outstanding == 0


def test_session_retry_state_capped_by_max_retained():
    """Retry state rides the max_retained bound: beyond it the OLDEST
    retry gives up with a final failed answer (counted), so a hostile
    failure stream cannot grow host state without bound."""
    s = _serve(max_retries=8, retry_backoff_ticks=4, max_retained=2)
    qids = s.submit_embed([44, 45, 46, 47])   # all unknown -> all retry
    for _ in range(3):
        s.advance(None, None)
    assert len(s._retry_queue) <= 2
    assert s.latency_stats()["retry_exhausted"] >= 2
    assert all(not s.answers[q].ok for q in qids if q in s.answers)


# ------------------------------------------------- subprocess (forced 4)
def _run_forced4(pytest_args, timeout=540):
    return run_forced_devices(4, Path(__file__), pytest_args, timeout)


def test_reshard_golden_forced4_subprocess():
    """Fast-lane smoke on any machine: one scale-down golden + the
    truncation scenario under a forced 4-device CPU."""
    r = _run_forced4(["-k", "test_reshard_mid_stream_golden and tick "
                            "and down"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]


@pytest.mark.slow
def test_chaos_full_matrix_forced4_subprocess():
    """Slow lane (CI `chaos` job runs this in-process): the full reshard
    golden matrix + every chaos scenario on a forced 4-device CPU."""
    r = _run_forced4(["-k", "not subprocess"], timeout=1800)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
