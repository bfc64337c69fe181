"""Training-plane benchmark: the concept-drift scenario under three
training postures over the SAME drifting labeled stream (paper §4.3 +
the ISSUE 8 online plane):

  training[inference_only] — stream + flush, no labels: the events/s
      ceiling the training planes are measured against;
  training[online]         — TrainSession over the super-tick driver:
      labels ride the update launches, the windowed fire-masked step
      runs on device, the stream never halts;
  training[halt_flush]     — TrainingCoordinator: the paper's §4.3.1
      halt/flush/train/rebuild cycle between phases.

derived: events_per_s (edge events over TOTAL wall incl. training),
loss_init/loss_final (first vs last fired/epoch loss across the drift
phases), grad_norm, steps, wire_mb (modeled exchange volume).
"""
from __future__ import annotations

import time

import numpy as np
import jax

from repro.core import windowing as win
from repro.core.pipeline import D3Pipeline, PipelineConfig
from repro.core.train_plane import TrainConfig
from repro.core.training import TrainingCoordinator
from repro.graph.graphs import powerlaw_edges
from repro.graph.sage import GraphSAGE
from repro.optim import sgd

from benchmarks.common import D_IN, D_HID, fmt_row

N_CLS = 5


def _drift_case(scale: str):
    n_nodes = {"small": 300, "full": 800}[scale]
    n_phase = {"small": 600, "full": 4000}[scale]
    phases = {"small": 2, "full": 3}[scale]
    rng = np.random.default_rng(0)
    feats = {v: rng.normal(size=D_IN).astype(np.float32)
             for v in range(n_nodes)}
    w_true = rng.normal(size=(D_IN, N_CLS))
    edges, labels = [], []
    for ph in range(phases):
        edges.append(powerlaw_edges(rng, n_nodes, n_phase))
        drift = rng.normal(size=(D_IN, N_CLS)) * 0.3 * ph
        logits = np.stack([feats[v] for v in range(n_nodes)]) \
            @ (w_true + drift)
        labels.append({v: int(np.argmax(logits[v])) for v in range(n_nodes)})
    return n_nodes, feats, edges, labels


def _build(n_nodes, n_edges, train=None, train_cap=0):
    model = GraphSAGE((D_IN, D_HID, D_HID),
                      n_classes=(N_CLS if train is not None else 0))
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(
        n_parts=8, node_cap=max(128, 4 * n_nodes // 8),
        edge_cap=max(256, 4 * n_edges // 8), repl_cap=max(256, 2 * n_nodes),
        feat_cap=2048, edge_tick_cap=1024, max_nodes=n_nodes,
        window=win.WindowConfig(kind=win.STREAMING), train_cap=train_cap)
    return model, params, D3Pipeline(model, params, cfg, train=train)


def _row(name, wall, n_events, loss_init, loss_final, grad_norm, steps,
         wire_mb):
    return fmt_row(
        name, 1e6 * wall,
        f"events_per_s={n_events / wall:.0f};loss_init={loss_init:.4f};"
        f"loss_final={loss_final:.4f};grad_norm={grad_norm:.4f};"
        f"steps={steps};wire_mb={wire_mb:.3f}")


def run(scale: str = "small"):
    n_nodes, feats, edge_phases, label_phases = _drift_case(scale)
    n_events = sum(len(e) for e in edge_phases)
    n_total = sum(len(e) for e in edge_phases)
    rows = []

    # ---- inference-only ceiling (same warm T=8 launch shape as online)
    _, _, pipe = _build(n_nodes, n_total)
    pipe.run_super_tick(T=8)
    t0 = time.perf_counter()
    for edges in edge_phases:
        e_chunks, f_chunks = pipe.chunk_stream(edges, feats, 128)
        for i in range(0, len(e_chunks), 8):
            pipe.run_super_tick(e_chunks[i:i + 8], f_chunks[i:i + 8], T=8)
    pipe.flush_super(max_ticks=512, T=8)
    wall_inf = time.perf_counter() - t0
    rows.append(_row("training[inference_only]", wall_inf, n_events,
                     0.0, 0.0, 0.0, 0, pipe.metrics.wire_bytes / 1e6))

    # ---- online plane: labels ride the stream, no halt
    from repro.serve import TrainSession
    tcfg = TrainConfig(optimizer=sgd(), lr=0.05, batch_threshold=8)
    _, _, pipe = _build(n_nodes, n_total, train=tcfg,
                        train_cap=max(64, n_nodes // 2))
    sess = TrainSession(pipe, super_ticks=8)
    # warm the two scan shapes (T=1 probe + T=8 cruise) outside the
    # timed region: empty launches, nothing fires, nothing admits
    pipe.run_super_tick(T=1)
    pipe.run_super_tick(T=8)
    loss_init, t0 = None, time.perf_counter()
    for edges, labels in zip(edge_phases, label_phases):
        e_chunks, f_chunks = pipe.chunk_stream(edges, feats, 128)
        sess.observe_labels(labels)
        if loss_init is None:
            # one-tick launch, then read the first fired loss: the
            # untrained starting point of the trajectory
            sess.advance_super(e_chunks[:1], f_chunks[:1], T=1)
            loss_init = sess.train_stats()["loss"]
            e_chunks, f_chunks = e_chunks[1:], f_chunks[1:]
        # labels ride the update launches; steps fire mid-stream (the
        # moving stream re-dirties the window every tick) — no halt.
        # Fixed T=8 launches (shorter tails padded) keep one compiled
        # program across phases AND the final flush.
        for i in range(0, len(e_chunks), 8):
            sess.advance_super(e_chunks[i:i + 8], f_chunks[i:i + 8], T=8)
    sess.flush()
    wall = time.perf_counter() - t0
    st = sess.train_stats()
    rows.append(_row("training[online]", wall, n_events, loss_init,
                     st["loss"], st["grad_norm"], st["steps"],
                     pipe.metrics.wire_bytes / 1e6))

    # ---- halt-flush coordinator cycle per phase
    model, params, pipe = _build(n_nodes, n_total)
    head_model = GraphSAGE((D_IN, D_HID, D_HID), n_classes=N_CLS)
    head_params = head_model.init(jax.random.key(1))["head"]
    coord = TrainingCoordinator(
        pipe, head_model.head, head_params,
        TrainConfig(optimizer=sgd(), lr=0.05, batch_threshold=4, epochs=3))
    loss_init, loss_final, steps, t0 = None, 0.0, 0, time.perf_counter()
    for edges, labels in zip(edge_phases, label_phases):
        pipe.run_stream(edges, feats, tick_edges=128)
        coord.labels.clear()
        coord.observe_labels(labels)
        res = coord.train()
        if loss_init is None:
            loss_init = res.losses[0]
        loss_final = res.losses[-1]
        steps += len(res.losses)
    wall = time.perf_counter() - t0
    gn = float(np.sqrt(sum(
        float((np.asarray(l, np.float32) ** 2).sum())
        for l in jax.tree.leaves(
            coord._full_batch_grads(*coord._device_labels())[1:]))))
    rows.append(_row("training[halt_flush]", wall, n_events, loss_init,
                     loss_final, gn, steps,
                     pipe.metrics.wire_bytes / 1e6))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
