"""Closed-loop ingest in passes.

A pass ingests the traffic's whole edge stream into freshly zeroed
tables, one launch of T micro-ticks after another (the next launch is
staged when the last one has synced), and then drains it
(`ServeSession.flush`). Slots are never freed, so the tables are
re-zeroed between passes; that, and reading the sink back for the check,
is the only work off the clock. The window runs whole passes until
`--seconds` have elapsed: the pass that is running then finishes and
counts.

Set-up warms up both programs a pass launches: the configured window's
super-tick, with one launch of real edges, and the drain's STREAMING one.

The check holds every pass's drained sink rows, one per vertex the
stream touched, to the plain reference over the whole stream.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import flops
import reference
import streams
from sink import read_rows, rows_numbers


def stream(run):
    s = run.traffic["stream"]
    edges = streams.edge_stream(run.config["num_nodes"], s["n_edges"],
                                s["alpha"], s["structure_seed"], run.seed)
    return edges, streams.feature_rows(edges, run.config["in_dim"], run.seed)


def _one_pass(run, session, edges, feats) -> None:
    T, tick = run.config["T"], run.config["tick_edges"]
    with run.phase("chunk"):
        e_chunks, f_chunks = session.pipe.chunk_stream(edges, feats, tick)
    for lo in range(0, len(e_chunks), T):
        with run.phase("launch"):
            session.advance_super(e_chunks[lo: lo + T],
                                  f_chunks[lo: lo + T], T=T)
    with run.phase("flush"):
        session.flush()


def pass_flops(run, edges: np.ndarray) -> float:
    """Operations an exact engine needs for one pass (`flops.py`)."""
    per_launch = run.config["T"] * run.config["tick_edges"]
    n_launch = -(-len(edges) // per_launch)
    ids, first = np.unique(edges.reshape(-1), return_index=True)
    at = first // (2 * per_launch)
    return flops.exact_flops(
        [edges[k * per_launch: (k + 1) * per_launch] for k in range(n_launch)],
        [ids[at == k] for k in range(n_launch)], run.dims())


def measure(run) -> None:
    T, tick = run.config["T"], run.config["tick_edges"]
    with run.phase("generate"):
        edges, feats = stream(run)
    ids = np.asarray(sorted(feats), np.int64)
    session = run.new_session()
    e_chunks, f_chunks = session.pipe.chunk_stream(edges[: T * tick], feats,
                                                   tick)
    session.advance_super(e_chunks, f_chunks, T=T)
    session.flush()

    passes, rows = [], []
    t_window = compiles0 = None
    while True:
        with run.phase("rezero"):
            session = None
            gc.collect()
            session = run.new_session()
        if t_window is None:
            run.setup_done()
            compiles0, t_window = run.compiles, time.perf_counter()
        traced = run.trace and not passes
        if traced:
            run.start_trace()
        s0, l0 = run.stage_s, run.launches
        t = time.perf_counter()
        with run.phase("pass"):
            _one_pass(run, session, edges, feats)
        dt = time.perf_counter() - t
        if traced:
            run.stop_trace()
        passes.append({"edges": len(edges), "seconds": dt,
                       "stage_s": run.stage_s - s0,
                       "launches": run.launches - l0, "traced": traced})
        with run.phase("readback"):
            rows.append(read_rows(session.pipe, ids))
        if time.perf_counter() - t_window >= run.seconds:
            break
    run.rec["compiles_in_window"] = run.compiles - compiles0
    session = None
    gc.collect()
    run.rec["passes"] = passes
    run.rec["flops_per_pass"] = pass_flops(run, edges)
    if run.trace:
        run.reduce_trace("pass")
    run.stream, run.rows = (edges, feats), rows


def control(run) -> None:
    """Put the reference, computed in bfloat16, in the engine's place:
    one pass's rows for `check` (bench/tests/readings.py)."""
    edges, feats = stream(run)
    snap = reference.snapshot(edges, feats)
    rows = reference.forward(run.params, snap, "bfloat16")
    run.stream = (edges, feats)
    run.rows = [(rows, np.ones(len(rows), bool), 0)]


def check(run) -> None:
    """Hold each pass's rows to the reference: `rows_gap` is the widest
    error as a share of the typical-case bf16 bar, `rows_missing` the
    rows not materialized, not finite, or for a vertex outside the
    stream."""
    from harness import log

    edges, feats = run.stream
    ref = reference.reference(run.params, reference.snapshot(edges, feats))
    gap, worst, missing = rows_numbers(ref, run.rows)
    log(f"rows: {len(run.rows)} x {len(ref.ref)}; widest error over the "
        f"worst-case bar {worst!r}")
    run.rec["attempted"] = len(run.rows) * len(ref.ref)
    run.rec["failed"] = missing
    run.rec["checks"] = {"rows_gap": gap, "rows_missing": missing}
