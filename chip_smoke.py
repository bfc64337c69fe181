#!/usr/bin/env python3
"""Bring-up smoke: the streaming engine's main path on a TPU, end to end.

    python chip_smoke.py               # one chip: an xla and a pallas phase
    python chip_smoke.py --chips 4     # the sharded path, on four chips
    python chip_smoke.py --rehearse    # the same code at tiny sizes, on the
                                       # CPU with interpreted kernels

The paper's GraphSAGE 602->64->64 (`configs/d3gnn_sage.py`) serves a
power-law edge stream over the 232,965 node ids of reddit
(`configs/gnn_common.py`) through `D3Pipeline` + `ServeSession` on the
super-tick driver: ingest -> six-plane super-tick -> sink -> online
`stale_ok` / `consistent` embedding reads and link scores, then a drain
flush. Every materialized sink row and every consistent answer is held to
the static oracle (`core/oracle.py`) within a bar derived below from the
matmul precision the engine uses on the chip. Weights, features and edges
come from `--seed`.

One chip: an "xla" phase at deployment-sized state caps, then a "pallas"
phase (`delivery_backend="pallas"`) at the largest caps whose compiled
program fits the chip, which must contain a real Mosaic kernel.
`--chips 4` runs only the sharded path — `MeshRouter` on
`make_stream_mesh(4)` with the dense wire, a live `reshard` onto two of
the devices halfway — and what it is compared with: the oracle and a
`LocalRouter` run on one device.

Everything printed is a bring-up reading, not a benchmark metric. The
last line of stdout is one JSON object naming the device. Without a TPU
(and without --rehearse) the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
from dataclasses import dataclass, replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# the compile cache is placed before anything compiles
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# Worst-case rounding of one operand to bfloat16 (8 significant bits):
# |bf16(x) - x| <= 2**-8 |x|. An f32 matmul at the default precision on a
# TPU rounds both operands to bf16 and accumulates the products in f32.
U_BF16 = 2.0 ** -8
# f32 summation-order slack: the engine adds a node's messages one RMI at
# a time in arrival order, the oracle in one segment sum; each lands
# within a few f32 ulps per add of the exact sum, far below the bf16 term
F32_SLACK = 1e-4
# standard deviations of the typical-case error scale (see Oracle)
LAMBDA = 8.0
# share of the chip's memory a compiled super-tick may claim; the rest is
# headroom for the parameters, staged batches and the runtime's buffers
FIT_SHARE = 0.75
# HDRF replication factor budgeted for 8 parts on a power-law graph: each
# part holds its masters plus about 0.7 replicas per master
RF_BUDGET = 1.7


@dataclass(frozen=True)
class Sizes:
    n_nodes: int           # node-id space of the stream
    n_parts: int
    tick_edges: int        # edges per micro-tick
    T: int                 # micro-ticks per super-tick
    super_ticks: int       # super-ticks of ingest
    node_cap: int
    repl_cap: int
    edge_cap: int
    feat_cap: int
    query_cap: int
    query_tick_cap: int
    queries_per_launch: int

    @property
    def n_edges(self) -> int:
        return self.tick_edges * self.T * self.super_ticks


def _round_up(n: float, m: int) -> int:
    return int(math.ceil(n / m) * m)


def full_sizes(n_nodes: int) -> Sizes:
    """State caps a deployment of `n_nodes` vertices holds on 8 parts:
    node_cap covers every master plus HDRF's replicas (RF_BUDGET),
    repl_cap the replication records of those replicas. Edge tables hold
    the stream (65,536 edges, 1.5x headroom per part), not reddit's
    114,615,892 edges."""
    n_parts, tick_edges, T, n_super = 8, 1024, 8, 8
    node_cap = _round_up(n_nodes * RF_BUDGET / n_parts, 1024)
    repl_cap = _round_up(node_cap - n_nodes / n_parts, 1024)
    edge_cap = _round_up(1.5 * tick_edges * T * n_super / n_parts, 1024)
    return Sizes(n_nodes=n_nodes, n_parts=n_parts, tick_edges=tick_edges,
                 T=T, super_ticks=n_super, node_cap=node_cap,
                 repl_cap=repl_cap, edge_cap=edge_cap,
                 feat_cap=2 * tick_edges, query_cap=128,
                 query_tick_cap=64, queries_per_launch=64)


TINY = Sizes(n_nodes=2000, n_parts=8, tick_edges=64, T=4, super_ticks=3,
             node_cap=256, repl_cap=256, edge_cap=256, feat_cap=128,
             query_cap=32, query_tick_cap=16, queries_per_launch=16)


def pipeline_config(sizes: Sizes, seed: int = 0):
    """The engine configuration every phase starts from (the serving
    CLI's SESSION window)."""
    from repro.core import windowing as win
    from repro.core.pipeline import PipelineConfig

    return PipelineConfig(
        n_parts=sizes.n_parts, node_cap=sizes.node_cap,
        edge_cap=sizes.edge_cap, repl_cap=sizes.repl_cap,
        feat_cap=sizes.feat_cap, edge_tick_cap=sizes.tick_edges,
        query_cap=sizes.query_cap, query_tick_cap=sizes.query_tick_cap,
        max_nodes=sizes.n_nodes, seed=seed,
        window=win.WindowConfig(kind=win.SESSION, interval=4))


def log(msg: str) -> None:
    print(msg, flush=True)


# ----------------------------------------------------------------- stream
def make_stream(sizes: Sizes, d_in: int, seed: int):
    """Power-law edges over the node-id space plus one feature row for
    every vertex the stream touches, all from `seed`."""
    import numpy as np
    from repro.graph.graphs import powerlaw_edges

    rng = np.random.default_rng(seed)
    edges = powerlaw_edges(rng, sizes.n_nodes, sizes.n_edges)
    touched = np.unique(edges)
    rows = rng.standard_normal((len(touched), d_in), dtype=np.float32)
    return edges, dict(zip(touched.tolist(), rows))


# ----------------------------------------------------------------- oracle
@dataclass(frozen=True)
class Oracle:
    index: dict            # vid -> row of ref / bar / typical
    ref: object            # [n, d_out] oracle rows
    bar: object            # [n, d_out] worst-case bar
    typical: object        # [n, d_out] typical-case bar


def oracle_and_bar(model, params, edges, feats, d_in) -> Oracle:
    """Static oracle rows at HIGHEST matmul precision, and the bars the
    engine's rows are held to. The snapshot holds the streamed vertices
    only, relabelled 0..n-1: every other node id is isolated and has no
    feature, so it changes no row.

    The engine runs each SAGE layer h = W_s x + W_n agg at the default
    precision: x, agg and both weights are rounded to bf16 (relative error
    <= u = 2**-8 each) and the products summed in f32, so each product
    carries a fresh error of at most 2u |x w|. With an error bound B on
    the layer's input (0 for the streamed features) the output error is,
    to first order in u, at most
        B' = ((1+2u) B + 2u|x|) @ |W_s| + ((1+2u) B_agg + 2u|agg|) @ |W_n|
    where B_agg = the mean of B over the in-neighbours (a mean is a convex
    combination); relu is 1-Lipschitz, so B' carries to the next layer.
    `bar` is B + F32_SLACK (1 + |ref|), F32_SLACK covering f32 summation
    order. It holds for any rounding pattern, so it is loose: it assumes
    every rounding error has the same sign.

    `typical` is the same bound for independent, mean-zero rounding
    errors (Hoeffding): each sum's scale adds in quadrature,
        S' = sqrt(4u^2 (x^2 @ W_s^2 + agg^2 @ W_n^2)
                  + S^2 @ W_s^2 + S_agg^2 @ W_n^2),
    S_agg^2 = sum of S^2 over the in-neighbours / in-degree^2, and a sum
    leaves LAMBDA S with probability 2 exp(-LAMBDA^2 / 2). It assumes
    independent errors where the worst case assumes aligned ones, so it is
    the bar that catches an engine error not far beyond rounding. Both
    bars are asserted."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.oracle import build_snapshot, oracle_embeddings
    from repro.graph.segment import segment_mean, segment_sum

    ids = np.asarray(sorted(feats))
    g, _ = build_snapshot(np.searchsorted(ids, edges),
                          dict(enumerate(feats[v] for v in ids)), d_in,
                          len(ids))
    u = U_BF16
    mean = lambda v: segment_mean(v[g.senders], g.receivers, g.n_nodes,
                                  g.edge_mask)
    deg = segment_sum(jnp.ones_like(g.senders, jnp.float32), g.receivers,
                      g.n_nodes, g.edge_mask)[:, None]
    with jax.default_matmul_precision("highest"):
        ref = oracle_embeddings(model, params, g)
        x, bound, sq = g.x, jnp.zeros_like(g.x), jnp.zeros_like(g.x)
        for i, layer in enumerate(model.layers):
            p = params[f"l{i}"]
            ws, wn = p["self"]["w"], p["neigh"]["w"]
            agg = mean(x)
            bound = (((1 + 2 * u) * bound + 2 * u * jnp.abs(x)) @ jnp.abs(ws)
                     + ((1 + 2 * u) * mean(bound) + 2 * u * jnp.abs(agg))
                     @ jnp.abs(wn))
            sq_agg = mean(sq) / jnp.maximum(deg, 1.0)
            sq = ((4 * u * u * x * x + sq) @ (ws * ws)
                  + (4 * u * u * agg * agg + sq_agg) @ (wn * wn))
            x = layer.update(p, x, agg)
    ref, bound, sq = jax.device_get((ref, bound, sq))
    slack = F32_SLACK * (1.0 + np.abs(ref))
    return Oracle(index=dict(zip(ids.tolist(), range(len(ids)))), ref=ref,
                  bar=bound + slack, typical=LAMBDA * np.sqrt(sq) + slack)


def hold(name, what, err, limits: dict) -> None:
    """Print err / limit at its worst for each bar and assert it is <= 1.
    `limits` maps a bar's name to limit arrays shaped like err."""
    worst = {k: float((err / lim).max()) for k, lim in limits.items()}
    log(f"[{name}] {what}: " + " ".join(
        f"worst_err/{k}={v!r}" for k, v in worst.items()))
    for k, v in worst.items():
        assert v <= 1.0, f"{name}: {what} outside the {k}"


def check_rows(name, got: dict, oracle: Oracle):
    """Hold materialized rows {vid: vec} to the oracle within both bars."""
    import numpy as np

    vids = sorted(got)
    assert vids == sorted(oracle.index), (
        f"{name}: {len(vids)} rows materialized, expected one for each of "
        f"the {len(oracle.index)} streamed vertices")
    rows = np.asarray([oracle.index[v] for v in vids])
    vec = np.stack([got[v] for v in vids])
    assert np.all(np.isfinite(vec)), f"{name}: non-finite rows"
    ref = oracle.ref[rows]
    err = np.abs(vec - ref)
    log(f"[{name}] rows={len(vids)} max_abs_err={float(err.max())!r} "
        f"max_rel_err="
        f"{float(err.max() / max(float(np.abs(ref).max()), 1e-30))!r} "
        f"bar_max={float(oracle.bar[rows].max())!r} "
        f"typical_bar_max={float(oracle.typical[rows].max())!r}")
    hold(name, "sink rows", err, {"bar": oracle.bar[rows],
                                  "typical_bar": oracle.typical[rows]})


# ------------------------------------------------------------ serving loop
MODES = ("stale_ok_embed", "consistent_embed", "stale_ok_link",
         "consistent_link")


def serve(pipe, sizes: Sizes, edges, feats, seed: int, reshard=None):
    """Drive one pipeline through the stream with the query mix, then
    drain it. Each launch submits stale_ok and consistent embedding reads
    and link scores over vertices already ingested. Returns (session,
    {mode: {qid: (u, v)}}, per-launch seconds, flush seconds). `reshard`:
    a mesh to relay the live carry onto after half the super-ticks."""
    import numpy as np
    from repro.serve.session import ServeSession

    session = ServeSession(pipe, driver="super", super_ticks=sizes.T)
    rng = np.random.default_rng(seed + 1)
    e_chunks, f_chunks = pipe.chunk_stream(edges, feats, sizes.tick_edges)
    asked = {m: {} for m in MODES}
    share = {"stale_ok_embed": 2, "consistent_embed": 8,
             "stale_ok_link": 4, "consistent_link": 8}
    ingested: set = set()
    launch_s = []
    for k, lo in enumerate(range(0, len(e_chunks), sizes.T)):
        if reshard is not None and k == sizes.super_ticks // 2:
            t = time.perf_counter()
            pipe.reshard(reshard)
            log(f"  live reshard onto {dict(reshard.shape)} data shards: "
                f"{time.perf_counter() - t!r} s")
        if ingested:
            pool = np.asarray(sorted(ingested))
            for mode in MODES:
                link = mode.endswith("link")
                cons = mode.startswith("consistent")
                uv = rng.choice(pool, (sizes.queries_per_launch
                                       // share[mode], 2))
                qids = (session.submit_link(uv.tolist(), consistent=cons)
                        if link else
                        session.submit_embed(uv[:, 0], consistent=cons))
                asked[mode].update(zip(qids, map(tuple, uv.tolist())))
        t = time.perf_counter()
        session.advance_super(e_chunks[lo: lo + sizes.T],
                              f_chunks[lo: lo + sizes.T], T=sizes.T)
        launch_s.append(time.perf_counter() - t)
        for ch in e_chunks[lo: lo + sizes.T]:
            ingested.update(ch.reshape(-1).tolist())
    t = time.perf_counter()
    session.flush()
    return session, asked, launch_s, time.perf_counter() - t


def check_serving(name, pipe, session, asked, oracle: Oracle):
    """Every query answered and nothing dropped; every consistent answer
    equals the oracle within both bars — an embedding row by row, a link
    score through |<a,b> - <a',b'>| <= |a| e_b + |b| e_a + e_a e_b."""
    import numpy as np

    m = pipe.metrics
    answers = session.answers
    n_asked = sum(len(q) for q in asked.values())
    log(f"[{name}] events ingested: {m.ticks} micro-ticks, "
        f"emitted={m.emitted_total} rmis={m.reduce_msgs} "
        f"cross_part={m.cross_part_msgs} outbox_deferrals={m.dropped}")
    for mode, qs in asked.items():
        log(f"[{name}] {mode}: asked={len(qs)} "
            f"answered={sum(q in answers for q in qs)} "
            f"ok={sum(answers[q].ok for q in qs if q in answers)}")
    log(f"[{name}] queries dropped={m.queries_dropped} "
        f"route_dropped={m.route_dropped} outstanding={session.outstanding}")
    assert len(answers) == n_asked and session.outstanding == 0, \
        f"{name}: {n_asked - len(answers)} queries unanswered"
    assert m.queries_dropped == 0 and m.route_dropped == 0, \
        f"{name}: queries or routed records dropped"
    index, ref = oracle.index, oracle.ref
    bars = {"bar": oracle.bar, "typical_bar": oracle.typical}
    errs, lims = [], {k: [] for k in bars}
    for q, (u, v) in asked["consistent_embed"].items():
        assert answers[q].ok, f"{name}: consistent read of {u} not ok"
        errs.append(np.abs(answers[q].vec - ref[index[u]]))
        for k, b in bars.items():
            lims[k].append(b[index[u]])
    for q, (u, v) in asked["consistent_link"].items():
        a = answers[q]
        assert a.ok, f"{name}: consistent link ({u}, {v}) not ok"
        ru, rv = ref[index[u]], ref[index[v]]
        errs.append(np.full(ru.shape, abs(a.score - float(ru @ rv))))
        for k, b in bars.items():
            bu, bv = b[index[u]], b[index[v]]
            lims[k].append(np.full(ru.shape, np.sum(
                np.abs(ru) * bv + np.abs(rv) * bu + bu * bv)
                + F32_SLACK * (1.0 + np.sum(np.abs(ru * rv)))))
    hold(name, "consistent answers", np.stack(errs),
         {k: np.stack(v) for k, v in lims.items()})


# ------------------------------------------------------------------ phases
def peak_bytes(devices) -> str:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(f"{d.id}:{stats.get('peak_bytes_in_use', 'not reported')}")
    return " ".join(out)


def compile_programs(model, cfg, T):
    """AOT-compile the phase's two super-tick programs (the configured
    window, and the drain flush's STREAMING one); with the persistent
    cache on, the pipeline's own launches then load them from the cache.
    Returns (compiled main program, its total bytes, seconds)."""
    from repro.core import windowing as win
    from repro.core.pipeline import lower_super_tick

    t = time.perf_counter()
    main = lower_super_tick(model, cfg, T).compile()
    if cfg.window.kind != win.STREAMING:
        lower_super_tick(model, cfg, T,
                         window=win.WindowConfig(kind=win.STREAMING)
                         ).compile()
    return main, program_bytes(main), time.perf_counter() - t


def program_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def run_phase(name, model, params, cfg, sizes, stream, oracle, seed,
              compiled_s=None, mesh=None, reshard=None):
    """One pipeline through the whole stream; held to the oracle.
    Returns its sink rows {vid: vec}."""
    import jax
    from repro.core.pipeline import D3Pipeline

    edges, feats = stream
    t = time.perf_counter()
    pipe = D3Pipeline(model, params, cfg, mesh=mesh)
    jax.block_until_ready(pipe.states)
    log(f"[{name}] tables allocated in {time.perf_counter() - t!r} s")
    session, asked, launch_s, flush_s = serve(pipe, sizes, edges, feats,
                                              seed, reshard=reshard)
    if compiled_s is not None:
        log(f"[{name}] setup: compile_s={compiled_s!r} "
            f"first_launch_s={launch_s[0]!r}")
    else:
        log(f"[{name}] setup: first_launch_s (compile included)="
            f"{launch_s[0]!r}")
    log(f"[{name}] steady: launches 2..{len(launch_s)} "
        f"s={sum(launch_s[1:])!r} (T={sizes.T} micro-ticks each; per "
        f"launch {[round(x, 3) for x in launch_s[1:]]}); "
        f"drain flush s={flush_s!r}")
    rows = pipe.embeddings()
    check_rows(name, rows, oracle)
    check_serving(name, pipe, session, asked, oracle)
    log(f"[{name}] peak_bytes_in_use {peak_bytes(jax.devices())}")
    return rows


def compare_runs(name, a: dict, b: dict, oracle: Oracle):
    """Two engine runs of one stream: each is within a bar of the oracle,
    so they are within twice that bar of each other."""
    import numpy as np

    vids = sorted(a)
    assert vids == sorted(b), f"{name}: different materialized vertices"
    rows = np.asarray([oracle.index[v] for v in vids])
    diff = np.abs(np.stack([a[v] for v in vids])
                  - np.stack([b[v] for v in vids]))
    log(f"[{name}] max_abs_diff={float(diff.max())!r}")
    hold(name, "difference", diff, {"2_bar": 2 * oracle.bar[rows],
                                    "2_typical_bar":
                                    2 * oracle.typical[rows]})


def one_chip(model, params, sizes, stream, oracle, base, args):
    """The xla phase at deployment caps, then the pallas phase at the
    largest caps whose program fits the chip."""
    import jax

    dev = jax.devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit", 16 * 2 ** 30)
    budget = int(FIT_SHARE * limit)
    log(f"fit budget: {budget} bytes ({FIT_SHARE} x bytes_limit {limit})")

    _, nbytes, comp_s = compile_programs(model, base, sizes.T)
    log(f"[xla] compiled super-tick program: {nbytes} bytes")
    xla_rows = run_phase("xla", model, params, base, sizes, stream, oracle,
                         args.seed, compiled_s=comp_s)
    gc.collect()

    cfg = replace(base, delivery_backend="pallas")
    while True:
        main, nbytes, comp_s = compile_programs(model, cfg, sizes.T)
        log(f"[pallas] node_cap={cfg.node_cap} repl_cap={cfg.repl_cap}: "
            f"compiled super-tick program {nbytes} bytes "
            f"({'fits' if nbytes <= budget else 'over'} the budget)")
        if nbytes <= budget:
            break
        if cfg.node_cap <= sizes.node_cap // 8:
            raise RuntimeError("pallas phase fits at no cap down to 1/8")
        cfg = replace(cfg, node_cap=cfg.node_cap // 2,
                      repl_cap=cfg.repl_cap // 2)
    log(f"[pallas] cut from the xla phase: node_cap {base.node_cap} -> "
        f"{cfg.node_cap}, repl_cap {base.repl_cap} -> {cfg.repl_cap}")
    kernel = "tpu_custom_call" in main.as_text()
    log(f"[pallas] compiled program holds a Mosaic kernel "
        f"(tpu_custom_call): {kernel}")
    if not args.rehearse:
        assert kernel, "pallas program has no tpu_custom_call"
    del main
    pallas_rows = run_phase("pallas", model, params, cfg, sizes, stream,
                            oracle, args.seed, compiled_s=comp_s)
    compare_runs("pallas vs xla", pallas_rows, xla_rows, oracle)


def four_chips(model, params, sizes, stream, oracle, base, args):
    """MeshRouter over make_stream_mesh(4), dense wire, a live reshard onto
    two data shards halfway; compared with the oracle and a LocalRouter
    run on one device."""
    import jax
    from repro.launch.mesh import make_stream_mesh, survivor_mesh

    mesh = make_stream_mesh(4)
    mesh_rows = run_phase("mesh4", model, params, base, sizes, stream,
                          oracle, args.seed, mesh=mesh,
                          reshard=survivor_mesh(mesh, [2, 3]))
    gc.collect()
    local_rows = run_phase("local", model, params, base, sizes, stream,
                           oracle, args.seed)
    compare_runs("mesh4 vs local", mesh_rows, local_rows, oracle)
    log(f"peak_bytes_in_use per device {peak_bytes(jax.devices())}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, kernels interpreted")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " --xla_force_host_"
                f"platform_device_count={args.chips}")
    cache_dir = enable_compile_cache()

    import jax
    from importlib import metadata

    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: no TPU visible (jax found {dev.platform}); "
              "pass --rehearse for the CPU rehearsal", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "devices visible", file=sys.stderr)
        return 2
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"jax {jax.__version__} libtpu {libtpu} device_kind "
        f"{dev.device_kind!r} platform {dev.platform} count {len(devices)}")
    log(f"compile cache: {cache_dir}")

    from repro.configs.d3gnn_sage import D_HID, D_IN
    from repro.configs.gnn_common import GNN_SHAPES
    from repro.graph.sage import GraphSAGE

    t0 = time.perf_counter()
    n_nodes = GNN_SHAPES["minibatch_lg"].dims["global_nodes"]
    sizes = TINY if args.rehearse else full_sizes(n_nodes)
    model = GraphSAGE((D_IN, D_HID, D_HID))
    params = model.init(jax.random.key(args.seed))
    edges, feats = make_stream(sizes, D_IN, args.seed)
    log(f"model GraphSAGE({D_IN}, {D_HID}, {D_HID}); stream: "
        f"{len(edges)} edges over {sizes.n_nodes} node ids touching "
        f"{len(feats)} vertices (one feature row each); "
        f"{sizes.super_ticks} super-ticks x T={sizes.T} x "
        f"{sizes.tick_edges} edges")
    base = pipeline_config(sizes, args.seed)
    P, N = base.n_parts, base.node_cap
    log(f"caps: n_parts={P} node_cap={N} repl_cap={base.repl_cap} "
        f"edge_cap={base.edge_cap} feat_cap={base.feat_cap} "
        f"(node_cap budgets RF {RF_BUDGET} over {sizes.n_nodes} masters)")
    for li, d in enumerate((D_IN, D_HID)):
        log(f"layer {li}: feat, x_sent, agg [{P}, {N}, {d}] f32 = "
            f"{P * N * d * 4} bytes each")
    log(f"sink [{P}, {N}, {D_HID}] f32 = {P * N * D_HID * 4} bytes")
    oracle = oracle_and_bar(model, params, edges, feats, D_IN)
    log(f"stream + oracle set up in {time.perf_counter() - t0!r} s")
    stream = (edges, feats)
    if args.chips == 4:
        four_chips(model, params, sizes, stream, oracle, base, args)
    else:
        one_chip(model, params, sizes, stream, oracle, base, args)
    log(f"total {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
