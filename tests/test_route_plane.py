"""Traffic-adaptive routing plane (ISSUE 5 tentpole).

Covers, bottom-up:
  * the packed wire format (dist/wire.py): exact pack/unpack round-trips
    for both lane types;
  * kernels/route_pack: the sort-by-destination plan vs the O(N*D)
    one-hot reference, and the xla-vs-pallas placement equivalence;
  * the misrouting regression: a VALID record addressed to an
    out-of-range part must be masked out of the exchange (the old
    `jnp.clip(part // Pl, 0, D-1)` silently shipped it to the last
    device, where it burned bucket capacity before being dropped);
  * the capped golden matrix under SKEWED hub-heavy traffic:
    route_cap in {dense, C//D, tiny} x {per-tick, super-tick} x
    {xla, pallas} on a real 4-device mesh must converge to the
    LocalRouter reference and the static oracle with EXACT integer
    aggregator counts, defer (never drop) overflow, re-emit every
    deferred row, and terminate its flush;
  * capped-wire query plane: link tails carried by wire backpressure
    must all answer eventually (the wire-backlog quiescence vote).

Stats contract at route_cap < C: the emission-side counters
(broadcast/reduce/cross_part) are counted BEFORE the wire, so deferral
never double-counts them — but delivery DELAYS shift which ticks
coalesce a vertex's updates, so their cumulative values may legally
differ from the dense reference under windows. What must match exactly:
final aggregator counts (each edge contributes once), the converged
embeddings (to f32 round-off of the telescoped delta sums), and
`route_dropped == 0` in any correctly-sized config. At the dense
default the existing test_mesh_router golden matrix already pins EXACT
integer stats.

Execution tiers mirror test_mesh_router: units anywhere, @needs4
in-process (CI mesh/pallas lanes), a forced-4 subprocess smoke in the
fast lane and the full matrix in the slow lane.
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


def hub_stream(seed=0, n_edges=120):
    """Skewed topology: most edges point AT a handful of hub vertices, so
    RMI traffic converges on the hubs' owner device and overflows small
    per-destination buckets (the route_cap stress shape)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(1, N_NODES, n_edges)
    dst = np.where(rng.random(n_edges) < 0.75,
                   rng.integers(0, 3, n_edges),        # hubs 0..2
                   rng.integers(0, N_NODES, n_edges))
    edges = np.stack([src, dst], 1)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = {v: rng.normal(size=D_IN).astype(np.float32)
             for v in range(N_NODES)}
    return edges, feats


def build_pipe(window, mesh=None, route_cap=None, route_defer_cap=None,
               backend="xla", query_cap=0):
    model = GraphSAGE((D_IN, 12, 12))
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=4, node_cap=32, edge_cap=128, repl_cap=128,
                         feat_cap=128, edge_tick_cap=32, max_nodes=N_NODES,
                         window=window, route_cap=route_cap,
                         route_defer_cap=route_defer_cap,
                         delivery_backend=backend, query_cap=query_cap)
    return model, params, D3Pipeline(model, params, cfg, mesh=mesh)


def assert_embeddings_close(a, b, rtol=1e-5, atol=1e-5):
    assert set(a) == set(b)
    for vid in a:
        np.testing.assert_allclose(b[vid], a[vid], rtol=rtol, atol=atol)


# ------------------------------------------------------------- wire format

def _msg_batch(rng, cap=13, d=5):
    from repro.core.events import MsgBatch
    return MsgBatch(
        part=jnp.asarray(rng.integers(0, 7, cap), jnp.int32),
        slot=jnp.asarray(rng.integers(0, 31, cap), jnp.int32),
        vec=jnp.asarray(rng.normal(size=(cap, d)), jnp.float32),
        cnt=jnp.asarray(rng.random(cap), jnp.float32),
        src_part=jnp.asarray(rng.integers(0, 7, cap), jnp.int32),
        valid=jnp.asarray(rng.random(cap) < 0.6))


def test_wire_pack_roundtrip_msg_and_query_batches():
    from repro.dist.wire import field_col, lane_width, pack_lane, unpack_lane
    from repro.serve.query import empty_query_batch
    rng = np.random.default_rng(0)
    msg = _msg_batch(rng)
    buf = pack_lane(msg)
    assert buf.shape == (13, lane_width(msg)) and lane_width(msg) == 5 + 5
    back = unpack_lane(buf, msg)
    for a, b in zip(jax.tree.leaves(msg), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the part column is where the router re-derives destinations from
    np.testing.assert_array_equal(
        np.asarray(buf[:, field_col(msg, "part")], np.int32),
        np.asarray(msg.part))
    qb = empty_query_batch(4, 6)
    assert lane_width(qb) == 6 + 10
    q2 = unpack_lane(pack_lane(qb), qb)
    for a, b in zip(jax.tree.leaves(qb), jax.tree.leaves(q2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------------------- route_pack

@pytest.mark.parametrize("cap", [1, 3, 64])
def test_route_plan_matches_onehot_reference(cap):
    from repro.kernels.route_pack import route_plan, route_plan_ref
    rng = np.random.default_rng(1)
    n, D = 57, 4
    # out-of-range destinations with ok=True must be excluded by the plan
    # itself (route_plan_ref semantics), not just by the caller's mask
    dst = jnp.asarray(rng.integers(-1, D + 2, n), jnp.int32)
    ok = jnp.asarray(rng.random(n) < 0.7)
    order, ship_s, slot_s, left_s = route_plan(dst, ok, D, cap)
    ship_r, slot_r, left_r = route_plan_ref(dst, ok, D, cap)
    inv = np.asarray(order)
    np.testing.assert_array_equal(np.asarray(ship_s), np.asarray(ship_r)[inv])
    np.testing.assert_array_equal(np.asarray(left_s), np.asarray(left_r)[inv])
    np.testing.assert_array_equal(np.asarray(slot_s), np.asarray(slot_r)[inv])
    # FIFO per destination: earlier records never overflow behind later ones
    for dev in range(D):
        ranks = np.flatnonzero(np.asarray(ship_r)
                               & (np.asarray(dst) == dev))
        lefts = np.flatnonzero(np.asarray(left_r)
                               & (np.asarray(dst) == dev))
        if len(ranks) and len(lefts):
            assert ranks.max() < lefts.min()


@pytest.mark.pallas
def test_route_pack_pallas_matches_xla():
    from repro.kernels.route_pack import route_pack, route_pack_ref, route_plan
    rng = np.random.default_rng(2)
    n, D, cap, W = 70, 4, 8, 9
    rows = jnp.asarray(rng.normal(size=(n, W)), jnp.float32)
    dst = jnp.asarray(rng.integers(0, D, n), jnp.int32)
    ok = jnp.asarray(rng.random(n) < 0.8)
    order, _, slot_s, _ = route_plan(dst, ok, D, cap)
    rows_s = rows[order]
    ref = route_pack_ref(rows_s, slot_s, D * cap)
    for backend in ("xla", "pallas"):
        got = route_pack(rows_s, slot_s, D * cap, backend=backend,
                         interpret=True if backend == "pallas" else None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=0, atol=0)


def test_config_rejects_undeferrable_capped_wire():
    """route_defer_cap=0 is allowed for MsgBatch lanes (loud drops), but a
    capped query wire that can drop would strand qids — rejected."""
    cfg = PipelineConfig(n_parts=4, feat_cap=4, route_cap=1,
                         route_defer_cap=0, query_cap=8)
    cfg.validate(n_devices=1)            # no wire capping on one device
    with pytest.raises(ValueError, match="strand its qid"):
        cfg.validate(n_devices=4)
    # deferral available (default ring) -> fine
    PipelineConfig(n_parts=4, feat_cap=4, route_cap=1,
                   query_cap=8).validate(n_devices=4)
    with pytest.raises(ValueError, match="route_cap=0 must be > 0"):
        PipelineConfig(route_cap=0, feat_cap=8).validate()


def test_oversized_qid_host_rejected():
    """qids at or beyond 2**24 would round on the packed f32 wire and
    answer under the WRONG qid — the host must reject them with an
    ok=False answer that still carries the exact qid."""
    from repro.serve.query import KIND_EMBED
    _, _, pipe = build_pipe(win.WindowConfig(kind=win.STREAMING),
                            query_cap=4)
    pipe.tick(queries=[(2 ** 24 + 1, KIND_EMBED, 0, False),
                       (-1, KIND_EMBED, 0, False)])
    ans = pipe.drain_answers()
    assert sorted(ans["qid"].tolist()) == [-1, 2 ** 24 + 1]
    assert not ans["ok"].any()
    assert pipe.metrics.queries_admitted == 0


def test_local_router_route_lanes_identity():
    from repro.dist.router import LocalRouter
    from repro.dist.wire import init_defer
    rng = np.random.default_rng(3)
    msg = _msg_batch(rng)
    lanes, defers, rcpt = LocalRouter(n_parts=4).route_lanes(
        (msg,), (init_defer(0, 10),))
    assert lanes[0] is msg
    assert int(rcpt.rows) == 0
    assert int(rcpt.deferred) == 0 and int(rcpt.dropped) == 0


# ------------------------------------------- misrouting regression (4 dev)

@needs4
def test_invalid_part_masked_out_of_exchange():
    """A VALID record with an out-of-range destination part must vanish
    from the exchange (and not burn a bucket slot). Before ISSUE 5 the
    destination clip shipped it to the LAST device."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from repro.core.events import MsgBatch
    from repro.dist.router import MeshRouter
    from repro.dist.wire import init_defer

    mesh = make_stream_mesh(4)
    router = MeshRouter(n_parts=4, n_devices=4, route_cap=1)

    def prog():
        # every device emits: one rogue record (part=99) FIRST, then one
        # valid record for part 3 — with cap=1 the rogue would eat the
        # bucket slot if it were clip-routed to the last device
        rogue_then_valid = jnp.asarray([99, 3], jnp.int32)
        msg = MsgBatch(part=rogue_then_valid,
                       slot=jnp.zeros(2, jnp.int32),
                       vec=jnp.ones((2, 4), jnp.float32),
                       cnt=jnp.zeros(2, jnp.float32),
                       src_part=jnp.zeros(2, jnp.int32),
                       valid=jnp.ones(2, bool))
        (out,), _, rcpt = router.route_lanes((msg,), (init_defer(0, 6),))
        return (out.part, out.valid, router.psum(rcpt.rows),
                router.psum(rcpt.dropped))

    f = jax.shard_map(prog, mesh=mesh, in_specs=(),
                      out_specs=(P("data"), P("data"), P(), P()),
                      check_vma=False)
    parts, valid, rows, dropped = jax.jit(f)()
    parts, valid = np.asarray(parts), np.asarray(valid)
    # device 3 receives the four valid records; nothing else arrives
    assert valid.sum() == 4
    np.testing.assert_array_equal(parts[valid], [3, 3, 3, 3])
    assert int(rows) == 4
    # rogue rows are masked out, not deferred/dropped (they never existed
    # as far as the wire is concerned — delivery could only drop them)
    assert int(dropped) == 0


# --------------------------------------- capped golden matrix (hub-heavy)

def run_capped(edges, feats, mesh, driver, backend, route_cap,
               route_defer_cap=None, window=None):
    window = window or win.WindowConfig(kind=win.STREAMING)
    model, params, pipe = build_pipe(window, mesh=mesh, route_cap=route_cap,
                                     route_defer_cap=route_defer_cap,
                                     backend=backend)
    if driver == "tick":
        pipe.run_stream(edges, feats, tick_edges=24)
        pipe.flush(max_ticks=256)
    else:
        pipe.run_stream_super(edges, feats, tick_edges=24, super_ticks=4)
        pipe.flush_super(max_ticks=256, T=4)
    return model, params, pipe


CAPPED_MATRIX = [
    ("tick", "xla", 40), ("super", "xla", 40),
    ("tick", "xla", 2), ("super", "xla", 2),
    pytest.param("super", "pallas", 2, marks=pytest.mark.pallas),
]


@needs4
@pytest.mark.parametrize("driver,backend,cap", CAPPED_MATRIX)
def test_capped_golden_hub_heavy(driver, backend, cap):
    """route_cap < C on skewed traffic: converged state must match the
    LocalRouter reference and the static oracle; overflow defers (never
    drops) and every deferred row is re-emitted (exact agg counts)."""
    edges, feats = hub_stream()
    _, _, ref = run_capped(edges, feats, None, "tick", "xla", None)
    model, params, pipe = run_capped(edges, feats, make_stream_mesh(4),
                                     driver, backend, cap)
    assert_embeddings_close(ref.embeddings(), pipe.embeddings())
    # exact: every edge's RMI contributes once, deferred or not
    np.testing.assert_array_equal(np.asarray(pipe.states[0].agg_cnt),
                                  np.asarray(ref.states[0].agg_cnt))
    g, _ = build_snapshot(edges, feats, D_IN, N_NODES)
    oracle = np.asarray(oracle_embeddings(model, params, g))
    for vid, vec in pipe.embeddings().items():
        np.testing.assert_allclose(vec, oracle[vid], rtol=1e-4, atol=1e-4)
    assert pipe.metrics.route_dropped == 0, \
        "correctly-sized defer rings must never drop"
    if cap <= 2:
        assert pipe.metrics.route_deferred > 0, \
            "a tiny bucket under hub traffic must exercise the defer path"
    # capped wire must be measurably smaller than the dense wire
    _, _, dense = run_capped(edges, feats, make_stream_mesh(4), driver,
                             backend, None)
    assert pipe.metrics.wire_bytes < dense.metrics.wire_bytes
    assert dense.metrics.route_deferred == 0


@needs4
@pytest.mark.parametrize("window", ALL_POLICIES,
                         ids=[w.kind for w in ALL_POLICIES])
def test_capped_golden_all_policies(window):
    """The C//D cap across all four window policies (super-tick, xla)."""
    edges, feats = hub_stream(seed=5)
    _, _, ref = run_capped(edges, feats, None, "tick", "xla", None,
                           window=window)
    model, params, pipe = run_capped(edges, feats, make_stream_mesh(4),
                                     "super", "xla", 40, window=window)
    assert_embeddings_close(ref.embeddings(), pipe.embeddings())
    np.testing.assert_array_equal(np.asarray(pipe.states[0].agg_cnt),
                                  np.asarray(ref.states[0].agg_cnt))
    assert pipe.metrics.route_dropped == 0


@needs4
def test_starved_defer_ring_drops_loudly():
    """route_defer_cap=0 disables deferral: bucket overflow must surface
    in route_dropped instead of passing silently."""
    edges, feats = hub_stream(seed=7)
    _, _, pipe = build_pipe(win.WindowConfig(kind=win.STREAMING),
                            mesh=make_stream_mesh(4), route_cap=1,
                            route_defer_cap=0)
    pipe.run_stream(edges[:48], feats, tick_edges=24)
    assert pipe.metrics.route_dropped > 0
    assert pipe.metrics.route_deferred == 0


@needs4
def test_capped_wire_lane_answers_all_queries():
    """Link-tail wire records carried by backpressure must all answer
    eventually — the wire-backlog quiescence vote keeps flush() ticking
    until the ring drains."""
    from repro.serve.query import KIND_LINK
    edges, feats = hub_stream(seed=9)
    _, _, pipe = build_pipe(win.WindowConfig(kind=win.STREAMING),
                            mesh=make_stream_mesh(4), route_cap=2,
                            query_cap=8)
    pipe.run_stream(edges, feats, tick_edges=24)
    pipe.flush(max_ticks=256)
    # a burst of cross-device link queries: heads all fire in one tick,
    # the tail fan-in to the hubs' device exceeds the 2-row bucket
    heads = np.unique(edges[:, 0])[:8]
    qs = [(i, KIND_LINK, int(heads[i]), i % 3, False) for i in range(8)]
    pipe.tick(queries=qs)
    pipe.flush(max_ticks=256)
    ans = pipe.drain_answers()
    assert sorted(ans["qid"].tolist()) == list(range(8))
    assert ans["ok"].all()
    assert pipe.metrics.route_dropped == 0


# ------------------------------------------------- subprocess (forced 4)

def _run_forced4(pytest_args, timeout=540):
    return run_forced_devices(4, Path(__file__), pytest_args, timeout)


def test_capped_golden_forced4_subprocess():
    """Fast-lane smoke on any machine: the tiny-cap overflow-defer
    regression + the misrouting regression on a forced 4-device CPU."""
    r = _run_forced4(["-k", "(test_capped_golden_hub_heavy and tick-xla-2)"
                            " or test_invalid_part_masked_out_of_exchange"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]


@pytest.mark.slow
def test_capped_full_matrix_forced4_subprocess():
    """Slow lane: the whole capped matrix + policies + wire tests under a
    forced 4-device CPU (the CI mesh lane runs them in-process)."""
    r = _run_forced4(["-k", "capped or invalid_part or starved"],
                     timeout=1200)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
