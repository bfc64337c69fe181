"""Delta-gated incremental propagation (ISSUE 6 tentpole).

Three contracts pinned here:

  * EXACT mode — `delta_eps=0` (the default) is bit-for-bit the ungated
    PR 5 program: identical embeddings (assert_array_equal, not
    allclose), identical integer TickStats, suppressed == 0 — across all
    four window policies, T=1 and T=k, and both routers (the golden
    matrix the delivery/router suites use, plus the delta_eps lane).

  * APPROXIMATE mode — at eps > 0 a sub-eps update stream is (a) largely
    suppressed (suppressed > 0, reduce_msgs strictly below the exact
    run's), (b) error-BOUNDED: the sink differs from the static oracle
    on the final snapshot by at most the Lipschitz chain bound
        e1    = ||W1_neigh||_2 * eps          (layer-0 agg residual)
        bound = ||W2_self||_2 * e1 + ||W2_neigh||_2 * (e1 + eps)
    for the 2-layer SAGE stack (phi = identity, relu 1-Lipschitz,
    counts never gated), and (c) still TERMINATING: suppressed-but-
    pending vertices count as quiet, so flush()/flush_super() return.

  * The building blocks — aggregator gates (core/aggregators.GATES) and
    same-destination coalescing (core/events.coalesce_msg_batch) — keep
    their local semantics: monotonic MAX/MIN short-circuit vs the L2
    norm, and sum-preserving per-destination compaction.

Module rides the `pallas` marker like the other golden matrices so the
CI pallas lane (forced 4-device CPU backend) exercises the mesh cells.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import needs_devices
from repro.core import aggregators
from repro.core import windowing as win
from repro.core.events import MsgBatch, coalesce_msg_batch
from repro.core.oracle import build_snapshot, oracle_embeddings
from repro.core.pipeline import D3Pipeline, PipelineConfig
from repro.graph.sage import GCNLayer, GraphSAGE, SAGELayer
from repro.launch.mesh import make_stream_mesh

pytestmark = pytest.mark.pallas

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


def build_pipe(window=None, delta_eps=None, mesh=None):
    model = GraphSAGE((D_IN, 12, 12))
    params = model.init(jax.random.key(0))
    kw = {} if delta_eps is None else {"delta_eps": delta_eps}
    cfg = PipelineConfig(n_parts=4, node_cap=32, edge_cap=128, repl_cap=128,
                         feat_cap=128, edge_tick_cap=32, max_nodes=N_NODES,
                         window=window or win.WindowConfig(kind=win.STREAMING),
                         **kw)
    return model, params, D3Pipeline(model, params, cfg, mesh=mesh)


def run_per_tick(pipe, edges, feats):
    pipe.run_stream(edges, feats, tick_edges=24)
    pipe.flush(max_ticks=96)
    return pipe


def run_super(pipe, edges, feats):
    pipe.run_stream_super(edges, feats, tick_edges=24, super_ticks=4)
    pipe.flush_super(max_ticks=96, T=4)
    return pipe


def assert_bit_identical(ref, other):
    """The eps=0 contract: EXACT embeddings and integer telemetry."""
    assert other.metrics.suppressed == ref.metrics.suppressed == 0
    assert other.metrics.reduce_msgs == ref.metrics.reduce_msgs
    assert other.metrics.broadcast_msgs == ref.metrics.broadcast_msgs
    assert other.metrics.cross_part_msgs == ref.metrics.cross_part_msgs
    assert other.metrics.emitted_total == ref.metrics.emitted_total
    assert other.metrics.dropped == ref.metrics.dropped
    np.testing.assert_array_equal(other.metrics.busy_logical,
                                  ref.metrics.busy_logical)
    a, b = ref.embeddings(), other.embeddings()
    assert set(a) == set(b)
    for vid in a:
        np.testing.assert_array_equal(b[vid], a[vid])


# ------------------------------------------------------------ gate semantics

def test_l2_gate_mean_sum():
    old = jnp.zeros((3, 4))
    new = jnp.asarray([[0.0, 0.0, 0.0, 0.0],        # ||d|| = 0
                       [4e-4, 4e-4, 4e-4, 4e-4],    # ||d|| = 8e-4
                       [2e-3, 0.0, 0.0, 0.0]])      # ||d|| = 2e-3
    for kind in ("mean", "sum"):
        g = np.asarray(aggregators.GATES[kind](new, old, 1e-3))
        np.testing.assert_array_equal(g, [True, True, False])


def test_max_min_gates_are_one_sided():
    """MAX synopsis grows only: a new message can only move the synopsis
    when some coordinate EXCEEDS the old value by more than eps — large
    drops are free (the old max still covers them). MIN mirrors it."""
    old = jnp.asarray([[1.0, 1.0], [1.0, 1.0], [1.0, 1.0]])
    new = jnp.asarray([[0.0, -9.0],        # big DROP: max can't shrink
                       [1.0 + 5e-4, 1.0],  # sub-eps growth
                       [1.0, 1.0 + 2e-3]]) # real growth
    g = np.asarray(aggregators.GATES["max"](new, old, 1e-3))
    np.testing.assert_array_equal(g, [True, True, False])
    g = np.asarray(aggregators.GATES["min"](-new, -old, 1e-3))
    np.testing.assert_array_equal(g, [True, True, False])
    # the L2 gate would NOT suppress the big drop — the short-circuit is
    # strictly more permissive for monotonic synopses
    assert not bool(aggregators.GATES["mean"](new, old, 1e-3)[0])


def test_layers_declare_their_gate_kind():
    assert SAGELayer(4, 4).agg_kind == "mean"
    assert GCNLayer(4, 4).agg_kind == "sum"
    assert set(aggregators.GATES) >= {"mean", "sum", "max", "min"}


def test_negative_or_nan_delta_eps_rejected():
    with pytest.raises(ValueError, match="delta_eps"):
        PipelineConfig(delta_eps=-1e-3).validate()
    with pytest.raises(ValueError, match="delta_eps"):
        PipelineConfig(delta_eps=float("nan")).validate()


# ----------------------------------------------------- coalescing semantics

def _dense_sums(b: MsgBatch, n_parts, n_slots):
    """Per-destination ground truth: dense scatter-add of a MsgBatch."""
    vec = np.zeros((n_parts * n_slots, b.vec.shape[-1]), np.float64)
    cnt = np.zeros((n_parts * n_slots,), np.float64)
    for i in range(b.part.shape[0]):
        if bool(b.valid[i]):
            k = int(b.part[i]) * n_slots + int(b.slot[i])
            vec[k] += np.asarray(b.vec[i], np.float64)
            cnt[k] += float(b.cnt[i])
    return vec, cnt


def test_coalesce_preserves_per_destination_sums():
    rng = np.random.default_rng(3)
    C, n_parts, n_slots, d = 64, 4, 8, 5
    b = MsgBatch(
        part=jnp.asarray(rng.integers(0, n_parts, C), jnp.int32),
        slot=jnp.asarray(rng.integers(0, n_slots, C), jnp.int32),
        vec=jnp.asarray(rng.normal(size=(C, d)).astype(np.float32)),
        cnt=jnp.asarray(rng.integers(0, 2, C).astype(np.float32)),
        src_part=jnp.asarray(rng.integers(0, n_parts, C), jnp.int32),
        valid=jnp.asarray(rng.random(C) < 0.7))
    out = coalesce_msg_batch(b, n_slots)
    assert out.part.shape == b.part.shape          # wire shape is fixed
    ref_vec, ref_cnt = _dense_sums(b, n_parts, n_slots)
    got_vec, got_cnt = _dense_sums(out, n_parts, n_slots)
    np.testing.assert_allclose(got_vec, ref_vec, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_cnt, ref_cnt, rtol=0, atol=0)
    # one live row per DISTINCT live destination, and no duplicates left
    keys = {int(b.part[i]) * n_slots + int(b.slot[i])
            for i in range(C) if bool(b.valid[i])}
    live = np.flatnonzero(np.asarray(out.valid))
    out_keys = [int(out.part[i]) * n_slots + int(out.slot[i]) for i in live]
    assert sorted(out_keys) == sorted(keys)


def test_coalesce_all_invalid_and_all_distinct():
    d = 3
    dead = MsgBatch(part=jnp.zeros(8, jnp.int32), slot=jnp.zeros(8, jnp.int32),
                    vec=jnp.ones((8, d)), cnt=jnp.ones(8),
                    src_part=jnp.zeros(8, jnp.int32),
                    valid=jnp.zeros(8, bool))
    assert not bool(jnp.any(coalesce_msg_batch(dead, 4).valid))
    uniq = MsgBatch(part=jnp.asarray([0, 1, 2, 3], jnp.int32),
                    slot=jnp.asarray([1, 1, 1, 1], jnp.int32),
                    vec=jnp.arange(8.0).reshape(4, 2),
                    cnt=jnp.asarray([1.0, 0.0, 1.0, 0.0]),
                    src_part=jnp.asarray([3, 2, 1, 0], jnp.int32),
                    valid=jnp.ones(4, bool))
    out = coalesce_msg_batch(uniq, 4)
    ref_vec, ref_cnt = _dense_sums(uniq, 4, 4)
    got_vec, got_cnt = _dense_sums(out, 4, 4)
    np.testing.assert_array_equal(got_vec, ref_vec)
    np.testing.assert_array_equal(got_cnt, ref_cnt)
    assert int(jnp.sum(out.valid)) == 4


# --------------------------------- golden matrix: eps=0 is bit-for-bit PR 5

@pytest.mark.parametrize("window", ALL_POLICIES,
                         ids=[w.kind for w in ALL_POLICIES])
def test_eps0_golden_matrix_local(window):
    """Explicit delta_eps=0.0 == default config, bit-identical, both
    drivers, LocalRouter — the gate and the coalescer compile away."""
    edges, feats = make_stream()
    _, _, ref = build_pipe(window)                  # default (eps unset)
    run_per_tick(ref, edges, feats)
    _, _, per = build_pipe(window, delta_eps=0.0)
    run_per_tick(per, edges, feats)
    assert_bit_identical(ref, per)
    _, _, sup = build_pipe(window, delta_eps=0.0)
    run_super(sup, edges, feats)
    assert_bit_identical(ref, sup)


@needs4
@pytest.mark.parametrize("window", ALL_POLICIES,
                         ids=[w.kind for w in ALL_POLICIES])
def test_eps0_golden_matrix_mesh(window):
    """Same lane on a real 4-device mesh: the gate threads through the
    shard_map'd program without disturbing the all_to_all exchange."""
    edges, feats = make_stream()
    mesh = make_stream_mesh(4)
    _, _, ref = build_pipe(window, mesh=mesh)
    run_per_tick(ref, edges, feats)
    _, _, per = build_pipe(window, delta_eps=0.0, mesh=mesh)
    run_per_tick(per, edges, feats)
    assert_bit_identical(ref, per)
    _, _, sup = build_pipe(window, delta_eps=0.0, mesh=mesh)
    run_super(sup, edges, feats)
    assert_bit_identical(ref, sup)


# ----------------------------------------- eps > 0: suppression + the bound

def _tiny_update_waves(rng, feats, n_waves=6, scale=2e-4):
    """Waves of sub-eps feature perturbations (the gate's target traffic).
    Returns (per-wave event lists, the final feature dict)."""
    cur = {v: np.asarray(f, np.float32).copy() for v, f in feats.items()}
    waves = []
    for _ in range(n_waves):
        events = []
        for v in sorted(cur):
            delta = rng.normal(size=D_IN).astype(np.float32)
            delta *= scale / max(float(np.linalg.norm(delta)), 1e-12)
            cur[v] = cur[v] + delta
            events.append((v, cur[v].copy()))
        waves.append(events)
    return waves, cur


def _run_update_stream(pipe, edges, feats, waves):
    """Build the graph, then stream the update waves, then drain."""
    pipe.run_stream(edges, feats, tick_edges=24)
    pipe.flush(max_ticks=96)
    for events in waves:
        pipe.tick(feats=events)
    pipe.flush(max_ticks=96)
    return pipe


def sage_error_bound(params, eps: float) -> float:
    """Lipschitz chain bound for the 2-layer SAGE stack (module doc)."""
    s1n = np.linalg.norm(np.asarray(params["l0"]["neigh"]["w"]), 2)
    s2s = np.linalg.norm(np.asarray(params["l1"]["self"]["w"]), 2)
    s2n = np.linalg.norm(np.asarray(params["l1"]["neigh"]["w"]), 2)
    e1 = s1n * eps
    return float(s2s * e1 + s2n * (e1 + eps))


def test_eps_suppresses_subthreshold_updates_and_bounds_error():
    eps = 1e-3
    rng = np.random.default_rng(7)
    edges, feats = make_stream()
    waves, final_feats = _tiny_update_waves(rng, feats, scale=2e-4)

    model, params, exact = build_pipe()
    _run_update_stream(exact, edges, feats, waves)
    _, _, gated = build_pipe(delta_eps=eps)
    _run_update_stream(gated, edges, feats, waves)

    # (a) the gate fired, and it SAVED messages (volume strictly below the
    # exact run; emission-time invariant: gated + suppressed never exceeds
    # what the exact schedule emitted)
    assert gated.metrics.suppressed > 0
    assert gated.metrics.reduce_msgs < exact.metrics.reduce_msgs
    assert (gated.metrics.reduce_msgs + gated.metrics.suppressed
            <= exact.metrics.reduce_msgs)
    assert exact.metrics.suppressed == 0

    # (b) error vs the static oracle on the FINAL snapshot stays under the
    # Lipschitz chain bound (small f32 slack: the exact pipeline itself
    # sits ~1e-6 off the oracle)
    g, _ = build_snapshot(edges, final_feats, D_IN, N_NODES)
    oracle = np.asarray(oracle_embeddings(model, params, g))
    bound = sage_error_bound(params, eps)
    emb = gated.embeddings()
    assert emb, "gated pipeline materialized no embeddings"
    worst = max(float(np.linalg.norm(emb[v] - oracle[v])) for v in emb)
    assert worst <= bound * 1.01 + 1e-5, \
        f"gated error {worst:.3e} exceeds the eps-derived bound {bound:.3e}"
    # the bound is meaningful: well above f32 noise, well below the
    # embedding scale
    assert 1e-5 < bound < float(np.linalg.norm(oracle))


def test_eps0_run_matches_oracle_after_updates():
    """Control for the bound test: the exact pipeline tracks the oracle to
    f32 tolerance through the same update waves."""
    rng = np.random.default_rng(7)
    edges, feats = make_stream()
    waves, final_feats = _tiny_update_waves(rng, feats, n_waves=2)
    model, params, exact = build_pipe()
    _run_update_stream(exact, edges, feats, waves)
    g, _ = build_snapshot(edges, final_feats, D_IN, N_NODES)
    oracle = np.asarray(oracle_embeddings(model, params, g))
    emb = exact.embeddings()
    for v in emb:
        np.testing.assert_allclose(emb[v], oracle[v], rtol=1e-4, atol=1e-4)


def test_flush_terminates_with_suppressed_residuals():
    """Termination contract: a suppressed-but-pending vertex is QUIET.
    A stream that ends on sub-eps updates must still quiesce under both
    drivers — the residual stays un-sent forever, by design."""
    eps = 1e-3
    rng = np.random.default_rng(11)
    edges, feats = make_stream()
    waves, _ = _tiny_update_waves(rng, feats, n_waves=2, scale=1e-4)

    _, _, per = build_pipe(delta_eps=eps)
    per.run_stream(edges, feats, tick_edges=24)
    per.flush(max_ticks=96)
    for events in waves:
        per.tick(feats=events)
    ran = per.flush(max_ticks=16)        # tight budget: must quiesce fast
    assert ran <= 16
    assert per.metrics.suppressed > 0

    _, _, sup = build_pipe(delta_eps=eps)
    sup.run_stream_super(edges, feats, tick_edges=24, super_ticks=4)
    sup.flush_super(max_ticks=96, T=4)
    for events in waves:
        sup.run_super_tick(feat_chunks=[events], T=1)
    ran = sup.flush_super(max_ticks=16, T=4)
    assert ran <= 16
    assert sup.metrics.suppressed > 0


@needs4
def test_eps_gating_on_mesh_suppresses_and_terminates():
    """Approximate mode through the MeshRouter: suppression counts psum
    across devices, coalescing feeds the capped all_to_all, flush ends."""
    eps = 1e-3
    rng = np.random.default_rng(13)
    edges, feats = make_stream()
    waves, _ = _tiny_update_waves(rng, feats, n_waves=2, scale=1e-4)
    mesh = make_stream_mesh(4)
    _, _, pipe = build_pipe(delta_eps=eps, mesh=mesh)
    pipe.run_stream(edges, feats, tick_edges=24)
    pipe.flush(max_ticks=96)
    for events in waves:
        pipe.tick(feats=events)
    assert pipe.flush(max_ticks=16) <= 16
    assert pipe.metrics.suppressed > 0
