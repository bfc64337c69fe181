"""Continual-training driver: streaming inference + training under drift
(the paper's concept-drift scenario, §4.3) over ONE validated TrainConfig
and two interchangeable training paths:

  * --mode online (default): `TrainSession` drives the fifth (training)
    plane — labels admit into the super-tick itself and the windowed
    fire-masked backprop + Algorithm 3 update runs on device WITHOUT
    ever stopping the stream;
  * --mode halt-flush: `TrainingCoordinator` — the paper's §4.3.1
    halt/flush/train/rebuild cycle (also the online plane's exactness
    oracle in the tests).

    PYTHONPATH=src python examples/train_streaming_gnn.py [--phases 3]
"""
import argparse

import numpy as np
import jax

from repro.core import windowing as win
from repro.core.pipeline import D3Pipeline, PipelineConfig
from repro.core.train_plane import TrainConfig
from repro.core.training import TrainingCoordinator
from repro.graph.graphs import powerlaw_edges
from repro.graph.sage import GraphSAGE
from repro.nn.layers import Linear
from repro.optim import adam
from repro.serve import TrainSession


def make_labels(rng, feats, w_true, n_nodes, d_in, n_cls, phase):
    """Drifted ground truth: hidden linear model + per-phase drift."""
    drift = rng.normal(size=(d_in, n_cls)) * 0.3 * phase
    logits = np.stack([feats[v] for v in range(n_nodes)]) @ (w_true + drift)
    return {v: int(np.argmax(logits[v])) for v in range(n_nodes)}


def run_online(args, rng, feats, w_true, n_nodes, d_in, n_cls):
    model = GraphSAGE((d_in, 32, 32), n_classes=n_cls)
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=8, node_cap=192, edge_cap=2048,
                         repl_cap=1024, feat_cap=2048, edge_tick_cap=256,
                         max_nodes=n_nodes, train_cap=64,
                         window=win.WindowConfig(kind=win.SESSION, interval=4))
    tcfg = TrainConfig(optimizer=adam(), lr=5e-3, batch_threshold=4)
    pipe = D3Pipeline(model, params, cfg, train=tcfg)
    sess = TrainSession(pipe, super_ticks=8)

    for phase in range(args.phases):
        edges = powerlaw_edges(rng, n_nodes, args.edges_per_phase)
        e_chunks, f_chunks = pipe.chunk_stream(edges, feats, 128)
        labels = make_labels(rng, feats, w_true, n_nodes, d_in, n_cls, phase)
        # labels ride the SAME launches as the topology/feature stream
        sess.observe_labels(labels)
        sess.advance_super(e_chunks, f_chunks)
        sess.flush()
        first = sess.train_stats()
        # second pass over the same drifted labels: re-admission re-dirties
        # the window, more fires, loss keeps dropping — while serving
        for _ in range(args.epochs):
            sess.observe_labels(labels)
            sess.flush()
        last = sess.train_stats()
        print(f"phase {phase}: steps={last['steps']} "
              f"loss {first['loss']:.3f} -> {last['loss']:.3f} "
              f"|g|={last['grad_norm']:.3f} backlog={last['backlog']}")
        assert last["steps"] > first["steps"], "training never fired"
        assert last["loss"] < first["loss"]
    print("online continual-training driver OK")


def run_halt_flush(args, rng, feats, w_true, n_nodes, d_in, n_cls):
    model = GraphSAGE((d_in, 32, 32))
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=8, node_cap=192, edge_cap=2048,
                         repl_cap=1024, feat_cap=2048, edge_tick_cap=256,
                         max_nodes=n_nodes,
                         window=win.WindowConfig(kind=win.SESSION, interval=4))
    pipe = D3Pipeline(model, params, cfg)
    head = Linear(32, n_cls)
    tcfg = TrainConfig(optimizer=adam(), lr=5e-3, batch_threshold=4,
                       epochs=args.epochs)
    coord = TrainingCoordinator(pipe, head, head.init(jax.random.key(1)),
                                tcfg)

    for phase in range(args.phases):
        edges = powerlaw_edges(rng, n_nodes, args.edges_per_phase)
        pipe.run_stream(edges, feats, tick_edges=128)
        labels = make_labels(rng, feats, w_true, n_nodes, d_in, n_cls, phase)
        coord.labels.clear()
        coord.observe_labels(labels)
        if coord.should_train():
            res = coord.train()
            print(f"phase {phase}: votes={res.votes} "
                  f"flush_ticks={res.flush_ticks} "
                  f"loss {res.losses[0]:.3f} -> {res.losses[-1]:.3f}")
            assert res.losses[-1] < res.losses[0]
        else:
            print(f"phase {phase}: not enough votes ({coord.votes()})")
    print("halt-flush continual-training driver OK")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("online", "halt-flush"),
                    default="online")
    ap.add_argument("--phases", type=int, default=3)
    ap.add_argument("--edges-per-phase", type=int, default=600)
    ap.add_argument("--epochs", type=int, default=10)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    n_nodes, d_in, n_cls = 250, 16, 5
    feats = {v: rng.normal(size=d_in).astype(np.float32)
             for v in range(n_nodes)}
    # ground-truth labels from a hidden random linear model over features
    w_true = rng.normal(size=(d_in, n_cls))
    run = run_online if args.mode == "online" else run_halt_flush
    run(args, rng, feats, w_true, n_nodes, d_in, n_cls)


if __name__ == "__main__":
    main()
