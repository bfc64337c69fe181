"""The streaming training plane (ISSUE 8).

Contracts pinned here:

  * config plane fails loud: TrainConfig validates its knobs, the
    pipeline rejects inconsistent (train_cap, TrainConfig) pairs, the
    unified `capacities()` view agrees with the deprecated accessors
    (which warn), and TrainingCoordinator insists on a TrainConfig.

  * a QUIET training plane is invisible: enabling train_cap + a
    TrainConfig whose threshold never fires leaves the stream bit-for-bit
    (`assert_array_equal` embeddings + exact integer metrics) the
    train_cap=0 program, across all four window policies and both
    drivers.

  * quiescent online gradients ARE the halt-flush oracle's: after a
    flush, a single firing label tick latches `last_grad`/`loss` exactly
    equal (single device) to `TrainingCoordinator._full_batch_grads` —
    which test_training_core pins against `jax.grad` on the static
    snapshot, so the online plane is transitively pinned to autodiff.

  * online learning learns: loss decreases over repeated label passes,
    T=1 and T=k, optimizer state advances.

  * the training state rides the consistent checkpoint cut: a mid-stream
    snapshot restores optimizer state (adam moments + step count) and the
    restored run's continuation is bit-identical to the uninterrupted
    one.

  * the mesh plane agrees with the local plane: data=4 (1-D) and
    stage=2 (2-D) quiescent gradients match the single-device run to
    1e-5 (cross-device scatter-add order differs; see
    backward_layer_routed). Subprocess smokes force the device counts on
    single-device machines.
"""
from dataclasses import replace
from pathlib import Path

import numpy as np
import jax
import pytest

from conftest import needs_devices, run_forced_devices
from repro.core import windowing as win
from repro.core.pipeline import Capacities, D3Pipeline, PipelineConfig
from repro.core.train_plane import TrainConfig
from repro.core.training import TrainingCoordinator
from repro.graph.sage import GraphSAGE
from repro.launch.mesh import make_stream_mesh
from repro.optim import adam, sgd
from repro.serve import TrainSession

N_NODES, D, N_CLS = 32, 8, 4

needs2 = needs_devices(2)
needs4 = needs_devices(4)

ALL_POLICIES = [win.WindowConfig(kind=win.STREAMING),
                win.WindowConfig(kind=win.TUMBLING, interval=3),
                win.WindowConfig(kind=win.SESSION, interval=3),
                win.WindowConfig(kind=win.ADAPTIVE)]
STREAMING = win.WindowConfig(kind=win.STREAMING)


def make_stream(seed=0, n_edges=100):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, N_NODES, n_edges),
                      rng.integers(0, N_NODES, n_edges)], 1)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = {v: rng.normal(size=D).astype(np.float32)
             for v in range(N_NODES)}
    labels = {v: (v * 7 + 3) % N_CLS for v in range(N_NODES)}
    return edges, feats, labels


def build_pipe(window, train=None, train_cap=0, mesh=None, n_stages=1,
               d_hid=16, uniform=False):
    # stage-parallel runs need SPMD-uniform dims (in == out)
    dims = (D, D, D) if (n_stages > 1 or uniform) else (D, d_hid, d_hid)
    model = GraphSAGE(dims, n_classes=N_CLS)
    params = model.init(jax.random.key(0))
    if train is None:
        params = {k: v for k, v in params.items() if k != "head"}
    cfg = PipelineConfig(n_parts=4, node_cap=32, edge_cap=128, repl_cap=128,
                         feat_cap=128, edge_tick_cap=32, max_nodes=N_NODES,
                         window=window, n_stages=n_stages,
                         train_cap=train_cap)
    return model, params, D3Pipeline(model, params, cfg, mesh=mesh,
                                     train=train)


# ------------------------------------------------------------ config plane

def test_train_config_validation():
    with pytest.raises(ValueError, match="optimizer"):
        TrainConfig(optimizer="sgd")
    with pytest.raises(ValueError, match="batch_threshold"):
        TrainConfig(optimizer=sgd(), batch_threshold=0)
    with pytest.raises(ValueError, match="epochs"):
        TrainConfig(optimizer=sgd(), epochs=0)
    with pytest.raises(ValueError, match="window"):
        TrainConfig(optimizer=sgd(), window=-1)
    with pytest.raises(ValueError, match="lr"):
        TrainConfig(optimizer=sgd(), lr=-0.1)
    with pytest.raises(ValueError, match="topk_frac"):
        TrainConfig(optimizer=sgd(), topk_frac=0.0)
    # frozen + hashable: rides jit boundaries as a static argument
    hash(TrainConfig(optimizer=sgd()))


def test_pipeline_rejects_inconsistent_train_config():
    tcfg = TrainConfig(optimizer=sgd(), batch_threshold=1)
    with pytest.raises(ValueError, match="train_cap"):
        build_pipe(STREAMING, train=tcfg, train_cap=0)
    with pytest.raises(ValueError, match="train_cap"):
        build_pipe(STREAMING, train=None, train_cap=8)
    with pytest.raises(ValueError, match="train_cap"):
        PipelineConfig(train_cap=-1).validate()
    # a training pipeline needs an output operator
    model = GraphSAGE((D, 16, 16))          # n_classes=0: no head
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=4, node_cap=32, edge_cap=128, repl_cap=128,
                         feat_cap=128, edge_tick_cap=32, max_nodes=N_NODES,
                         train_cap=8)
    with pytest.raises(ValueError, match="head"):
        D3Pipeline(model, params, cfg, train=tcfg)


def test_capacities_view_matches_deprecated_accessors():
    cfg = PipelineConfig(n_parts=4, node_cap=32, edge_cap=128, repl_cap=128,
                         feat_cap=128, edge_tick_cap=32, train_cap=8)
    caps = cfg.capacities()
    assert isinstance(caps, Capacities)
    assert caps.train_cap == 8
    # the emission budget defaults to feat_cap, split over the parts
    assert caps.outbox == cfg.feat_cap and caps.outbox_per_part == 32
    assert caps.query_admissions == 0          # query plane off
    # one device: no capped exchange, so no defer ring
    assert caps.bc_defer_rows == caps.rmi_defer_rows == 0
    capped = replace(cfg, route_cap=8).capacities(n_devices=2)
    assert capped.bc_defer_rows == 2 * (cfg.n_parts // 2) * cfg.repl_cap


def test_train_session_rejects_untrained_pipeline():
    _, _, pipe = build_pipe(STREAMING)
    with pytest.raises(ValueError, match="train_cap"):
        TrainSession(pipe)
    tcfg = TrainConfig(optimizer=sgd(), batch_threshold=1)
    _, _, tp = build_pipe(STREAMING, train=tcfg, train_cap=8)
    with pytest.raises(ValueError, match="super_ticks"):
        TrainSession(tp, super_ticks=0)


def test_training_coordinator_requires_train_config():
    _, _, pipe = build_pipe(STREAMING)
    with pytest.raises(TypeError, match="TrainConfig"):
        TrainingCoordinator(pipe, None, None, sgd())


# ------------------------------------- quiet plane is bit-invisible

@pytest.mark.parametrize("window", ALL_POLICIES,
                         ids=[w.kind for w in ALL_POLICIES])
def test_quiet_train_plane_bit_identity(window):
    """train_cap > 0 with a never-firing threshold must leave the stream
    bit-for-bit the train_cap=0 program: the training plane reads the
    tick, it never writes it (and at train_cap=0 it is compiled away
    entirely — that side is the reference here)."""
    edges, feats, labels = make_stream()
    tcfg = TrainConfig(optimizer=sgd(), lr=0.1, batch_threshold=10_000)
    for driver in ("tick", "super"):
        _, _, ref = build_pipe(window)
        _, _, pipe = build_pipe(window, train=tcfg, train_cap=64)
        if driver == "tick":
            for p, lab in ((ref, None), (pipe, labels)):
                p.run_stream(edges, feats, tick_edges=24)
                p.tick(labels=(list(lab.items()) if lab else None))
                p.flush(max_ticks=128)
        else:
            for p, lab in ((ref, None), (pipe, labels)):
                p.run_stream_super(edges, feats, tick_edges=24,
                                   super_ticks=4)
                p.run_super_tick(
                    T=1, label_chunks=([list(lab.items())] if lab else None))
                p.flush_super(max_ticks=128, T=4)
        e_ref, e_got = ref.embeddings(), pipe.embeddings()
        assert set(e_got) == set(e_ref)
        for vid in e_got:
            np.testing.assert_array_equal(e_got[vid], e_ref[vid])
        m, r = pipe.metrics, ref.metrics
        assert (m.reduce_msgs, m.broadcast_msgs, m.cross_part_msgs,
                m.emitted_total, m.dropped) == \
               (r.reduce_msgs, r.broadcast_msgs, r.cross_part_msgs,
                r.emitted_total, r.dropped)
        st = pipe.train_stats()
        assert st["steps"] == 0 and st["loss"] == 0.0


# ------------------------------- quiescent grads == halt-flush oracle

def test_quiescent_online_grads_match_oracle_exactly():
    """lr=0 so fires never move parameters: after the stream flushes, one
    label tick fires on the quiescent fixed point and its latched
    last_grad/loss must equal the halt-flush coordinator's full-batch
    grads over the same labels to f32 round-off (single device: the
    routed backward takes the oracle's gather path — same math, but the
    two jitted programs fuse/reassociate their reductions differently,
    so agreement is ~1 ulp, not bitwise)."""
    edges, feats, labels = make_stream()
    tcfg = TrainConfig(optimizer=sgd(), lr=0.0, batch_threshold=1)
    model, params, pipe = build_pipe(STREAMING, train=tcfg, train_cap=64)
    pipe.run_stream(edges, feats, tick_edges=24)
    pipe.flush(max_ticks=128)
    pipe.tick(labels=list(labels.items()))
    ts = pipe.train_state
    st = pipe.train_stats()
    assert st["steps"] == 1, "the label tick must fire exactly once"

    _, _, ref = build_pipe(STREAMING)
    ref.run_stream(edges, feats, tick_edges=24)
    ref.flush(max_ticks=128)
    coord = TrainingCoordinator(ref, model.head, params["head"],
                                TrainConfig(optimizer=sgd(), lr=0.0,
                                            batch_threshold=1))
    coord.observe_labels(labels)
    la, lm = coord._device_labels()
    loss, hg, pg = coord._full_batch_grads(la, lm)

    np.testing.assert_allclose(np.float32(st["loss"]),
                               np.asarray(loss, np.float32),
                               rtol=1e-6, atol=0)
    for name in ("l0", "l1"):
        want = jax.tree.map(lambda x: np.asarray(x).sum(0), pg[name])
        got = ts.last_grad[name]
        for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=5e-6, atol=1e-7)
    for w, g in zip(jax.tree.leaves(hg),
                    jax.tree.leaves(ts.last_grad["head"])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-6, atol=1e-7)
    # lr=0 fires must not perturb the live parameters
    for k in ("l0", "l1"):
        for a, b in zip(jax.tree.leaves(ts.params[k]),
                        jax.tree.leaves(params[k])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------- online learning

@pytest.mark.parametrize("driver", ["tick", "super"])
def test_online_training_decreases_loss(driver):
    edges, feats, labels = make_stream()
    tcfg = TrainConfig(optimizer=sgd(), lr=0.1, batch_threshold=4)
    _, _, pipe = build_pipe(STREAMING, train=tcfg, train_cap=64)
    sess = TrainSession(pipe, super_ticks=1 if driver == "tick" else 4)
    e_chunks, f_chunks = pipe.chunk_stream(edges, feats, 24)
    sess.observe_labels(labels)
    if driver == "tick":
        for e, f in zip(e_chunks, f_chunks):
            sess.advance(e, f)
    else:
        sess.advance_super(e_chunks, f_chunks)
    sess.flush()
    first = sess.train_stats()
    assert first["steps"] > 0 and first["backlog"] == 0
    for _ in range(5):
        sess.observe_labels(labels)
        sess.flush()
    last = sess.train_stats()
    assert last["steps"] > first["steps"]
    assert last["loss"] < first["loss"]
    assert np.isfinite(last["grad_norm"])


def test_online_compression_path_learns():
    """Error-feedback compressed gradients still learn (residual carried
    in TrainState, int8 round-trip on device)."""
    edges, feats, labels = make_stream()
    tcfg = TrainConfig(optimizer=sgd(), lr=0.1, batch_threshold=4,
                       compression=True, topk_frac=0.5)
    _, _, pipe = build_pipe(STREAMING, train=tcfg, train_cap=64)
    assert pipe.train_state.residual, "compression must allocate residuals"
    sess = TrainSession(pipe, super_ticks=1)
    pipe.run_stream(edges, feats, tick_edges=24)
    sess.observe_labels(labels)
    sess.flush()
    first = sess.train_stats()
    for _ in range(5):
        sess.observe_labels(labels)
        sess.flush()
    last = sess.train_stats()
    assert last["steps"] > first["steps"]
    assert last["loss"] < first["loss"]


# ------------------------------------------------- checkpoint cut

def test_optimizer_state_survives_checkpoint(tmp_path):
    """Mid-flight snapshot: adam moments + step count restore bit-equal,
    and the restored run's continuation is bit-identical to the
    uninterrupted one."""
    from repro.ft.checkpoint import CheckpointManager
    edges, feats, labels = make_stream()
    tcfg = TrainConfig(optimizer=adam(), lr=1e-2, batch_threshold=1)
    half = len(edges) // 2

    def build():
        return build_pipe(STREAMING, train=tcfg, train_cap=64)[2]

    pipe = build()
    pipe.run_stream(edges[:half], feats, tick_edges=24)
    pipe.tick(labels=list(labels.items()))
    assert pipe.train_stats()["steps"] >= 1
    mgr = CheckpointManager(tmp_path)
    mgr.save_pipeline(0, pipe)
    opt_at_save = jax.tree.map(np.asarray, pipe.train_state.opt)
    seen = set(int(v) for v in edges[:half].reshape(-1))

    def finish(p):
        e_chunks, f_chunks = p.chunk_stream(edges[half:], feats, 24,
                                            seen=set(seen))
        for e, f in zip(e_chunks, f_chunks):
            p.tick(e, f)
        p.flush(max_ticks=128)
        p.tick(labels=list(labels.items()))
        return (jax.tree.map(np.asarray, p.train_state.params),
                p.train_stats())

    params_a, stats_a = finish(pipe)

    fresh = build()
    mgr.restore_pipeline(fresh)
    for a, b in zip(jax.tree.leaves(fresh.train_state.opt),
                    jax.tree.leaves(opt_at_save)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    params_b, stats_b = finish(fresh)
    assert stats_a == stats_b
    for a, b in zip(jax.tree.leaves(params_a), jax.tree.leaves(params_b)):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------ mesh plane

def _quiescent_grad_run(mesh=None, n_stages=1, uniform=False):
    edges, feats, labels = make_stream()
    tcfg = TrainConfig(optimizer=sgd(), lr=0.0, batch_threshold=1)
    _, _, pipe = build_pipe(STREAMING, train=tcfg, train_cap=64,
                            mesh=mesh, n_stages=n_stages, uniform=uniform)
    pipe.run_stream_super(edges, feats, tick_edges=24, super_ticks=4)
    pipe.flush_super(max_ticks=160, T=4)
    pipe.run_super_tick(T=1, label_chunks=[list(labels.items())])
    ts = pipe.train_state
    return pipe.train_stats(), jax.tree.map(np.asarray, ts.last_grad)


def _assert_grads_close(ref, got, rtol=1e-5, atol=1e-6):
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=rtol, atol=atol)


@needs4
def test_train_mesh_data4_matches_local():
    """1-D data=4: per-part gradient hops ride the packed wire; the
    quiescent fired gradients match the single-device run to 1e-5."""
    st_ref, g_ref = _quiescent_grad_run()
    mesh = make_stream_mesh(4)
    st, g = _quiescent_grad_run(mesh=mesh, n_stages=1)
    assert st["steps"] == st_ref["steps"] == 1
    np.testing.assert_allclose(st["loss"], st_ref["loss"],
                               rtol=1e-5, atol=1e-6)
    _assert_grads_close(g_ref, g)


@needs2
def test_train_stage2_matches_local():
    """2-D stage=2: the stage-replicated training state (stage-gathered
    caches, every stage runs the full-depth backward) agrees with the
    single-device run to 1e-5."""
    st_ref, g_ref = _quiescent_grad_run(uniform=True)
    mesh = make_stream_mesh(2, stage=2)
    st, g = _quiescent_grad_run(mesh=mesh, n_stages=2)
    assert st["steps"] == st_ref["steps"] == 1
    np.testing.assert_allclose(st["loss"], st_ref["loss"],
                               rtol=1e-5, atol=1e-6)
    _assert_grads_close(g_ref, g)


def test_train_mesh_forced4_subprocess():
    r = run_forced_devices(4, Path(__file__),
                           ["-k", "test_train_mesh_data4_matches_local"],
                           timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]


def test_train_stage2_forced2_subprocess():
    r = run_forced_devices(2, Path(__file__),
                           ["-k", "test_train_stage2_matches_local"],
                           timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
