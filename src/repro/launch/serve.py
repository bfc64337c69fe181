"""Serving launcher: the streaming-GNN online pipeline (the paper's kind)
or LM batched decode, selected by --arch.

    PYTHONPATH=src python -m repro.launch.serve --arch d3gnn-sage --edges 2000
    PYTHONPATH=src python -m repro.launch.serve --arch mistral-nemo-12b \
        --reduced --tokens 32
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.launch.compile_cache import enable_compile_cache


def serve_lm(args):
    spec = get_arch(args.arch)
    model = spec.build_reduced()
    params = model.init(jax.random.key(0))
    B = 4
    cache = model.init_cache(B, args.tokens + 8)
    tok = jnp.asarray(np.random.default_rng(0).integers(
        0, model.cfg.vocab, (B, 1)), jnp.int32)
    decode = jax.jit(model.decode_step)
    t0 = time.perf_counter()
    for i in range(args.tokens):
        logits, cache = decode(params, cache, tok)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    print(f"decoded {args.tokens} tokens x {B} seqs in {dt:.2f}s "
          f"({B * args.tokens / dt:.1f} tok/s)")


def serve_stream(args):
    from repro.core import windowing as win
    from repro.core.pipeline import D3Pipeline, PipelineConfig
    from repro.graph.graphs import powerlaw_edges
    from repro.graph.sage import GraphSAGE
    rng = np.random.default_rng(0)
    n_nodes = 400
    edges = powerlaw_edges(rng, n_nodes, args.edges)
    feats = {v: rng.normal(size=16).astype(np.float32)
             for v in range(n_nodes)}
    model = GraphSAGE((16, 64, 64))
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=8, node_cap=256, edge_cap=4096,
                         repl_cap=1024, feat_cap=2048, edge_tick_cap=512,
                         max_nodes=n_nodes,
                         window=win.WindowConfig(kind=win.SESSION, interval=4))
    pipe = D3Pipeline(model, params, cfg)
    t0 = time.perf_counter()
    # T micro-ticks per lax.scan launch, one host sync per launch
    pipe.run_stream_super(edges, feats, tick_edges=args.tick_edges,
                          super_ticks=args.super_ticks)
    pipe.flush_super(max_ticks=64, T=4)
    dt = time.perf_counter() - t0
    print(f"streamed {args.edges} edges in {dt:.2f}s "
          f"[T={args.super_ticks}, {args.edges / dt:.0f} ev/s]; "
          f"materialized {len(pipe.embeddings())} embeddings; "
          f"{pipe.metrics.reduce_msgs} RMIs, "
          f"{pipe.metrics.cross_part_msgs} cross-part msgs")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="d3gnn-sage")
    ap.add_argument("--edges", type=int, default=2000)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--tick-edges", type=int, default=256)
    ap.add_argument("--super-ticks", type=int, default=16,
                    help="micro-ticks per device launch")
    args = ap.parse_args()
    if args.arch == "d3gnn-sage":
        serve_stream(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
