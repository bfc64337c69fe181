"""Online query plane under concurrent update load (ISSUE 4 tentpole).

Metric: answered queries per second and end-to-end enqueue->answer
latency percentiles (p50/p99) while the same device launches ingest the
edge stream — the paper's online-query setting. Rows cover
{local, mesh} x {stale_ok, consistent}:

  * stale_ok rows measure the serving fast path: answers ride the
    super-tick's single host sync, so p50 tracks the launch cadence;
  * consistent rows measure the freshness tax: answers hold until a
    locally-clean, globally-silent tick, which under a continuous
    STREAMING load means the drain at the end — the p99 gap between the
    row pair IS the consistency/latency tradeoff.

Each device count runs in a SUBPROCESS (the XLA host-platform device
count is fixed at backend initialization), mirroring bench_scaling. On
one CPU the mesh row tracks the routing overhead of the extra query
lane, not real scaling; on a multi-chip mesh the same harness reports
the true serving numbers.
"""
from __future__ import annotations

import subprocess
import sys
from pathlib import Path

from benchmarks.common import fmt_row

REPO = Path(__file__).resolve().parents[1]

_WORKER = """
import time
import numpy as np
import jax
from repro.core import windowing as win
from repro.core.pipeline import D3Pipeline, PipelineConfig
from repro.graph.graphs import powerlaw_edges
from repro.graph.sage import GraphSAGE
from repro.launch.mesh import make_stream_mesh
from repro.serve.session import ServeSession

D = {n_devices}
N_EDGES = {n_edges}
CONSISTENT = {consistent}
TICK_EDGES, SUPER_T, Q_PER_LAUNCH = 64, 8, 24

rng = np.random.default_rng(0)
n_nodes = 200
edges = powerlaw_edges(rng, n_nodes, N_EDGES, 1.3)
feats = {{v: rng.normal(size=16).astype(np.float32) for v in range(n_nodes)}}


def build(mesh=None):
    model = GraphSAGE((16, 32, 32))
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=8, node_cap=256, edge_cap=2048,
                         repl_cap=512, feat_cap=512, edge_tick_cap=64,
                         query_cap=32, query_tick_cap=64, max_nodes=n_nodes,
                         window=win.WindowConfig(kind=win.STREAMING))
    return D3Pipeline(model, params, cfg, mesh=mesh)


def serve(mesh=None):
    s = ServeSession(build(mesh), super_ticks=SUPER_T)
    e_chunks, f_chunks = s.pipe.chunk_stream(edges, feats, TICK_EDGES)
    known = []
    t0 = time.perf_counter()
    for lo in range(0, len(e_chunks), SUPER_T):
        if known:
            vids = rng.choice(known, Q_PER_LAUNCH - 4)
            s.submit_embed(vids, consistent=CONSISTENT)
            pairs = rng.choice(known, (4, 2))
            s.submit_link([(int(a), int(b)) for a, b in pairs],
                          consistent=CONSISTENT)
        s.advance_super(e_chunks[lo: lo + SUPER_T],
                        f_chunks[lo: lo + SUPER_T], T=SUPER_T)
        ingested = np.concatenate(
            [c.reshape(-1) for c in e_chunks[lo: lo + SUPER_T]])
        known = sorted(set(known) | set(int(u) for u in ingested))
    s.flush()
    wall = time.perf_counter() - t0
    lat = np.asarray([a.latency_s for a in s.answers.values()
                      if a.latency_s is not None]) * 1e3
    stale = np.asarray([a.staleness_ticks for a in s.answers.values()])
    assert s.outstanding == 0, "all queries must resolve by the flush"
    print(f"RESULT,{{len(lat)}},{{wall:.4f}},{{np.percentile(lat, 50):.2f}},"
          f"{{np.percentile(lat, 99):.2f}},{{np.percentile(stale, 50):.1f}},"
          f"{{N_EDGES / wall:.1f}}")


serve(make_stream_mesh(D) if D > 1 else None)
"""


def _worker(n_devices: int, n_edges: int, consistent: bool,
            timeout: int = 560):
    env = {"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
           "HOME": "/root", "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_devices}"}
    r = subprocess.run(
        [sys.executable, "-c",
         _WORKER.format(n_devices=n_devices, n_edges=n_edges,
                        consistent=consistent)],
        env=env, capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(f"serving worker D={n_devices} failed:\n"
                           + r.stderr[-2000:])
    for line in r.stdout.splitlines():
        if line.startswith("RESULT,"):
            _, n, wall, p50, p99, stale50, evs = line.split(",")
            return {"answered": int(n), "wall": float(wall),
                    "p50_ms": float(p50), "p99_ms": float(p99),
                    "staleness_p50": float(stale50),
                    "events_per_s": float(evs)}
    raise RuntimeError("serving worker printed no RESULT row")


def run(scale: str = "small"):
    n_edges = {"small": 800, "full": 4000}[scale]
    rows = []
    for name, d in (("local", 1), ("mesh,D=2", 2)):
        for mode in ("stale_ok", "consistent"):
            res = _worker(d, n_edges, mode == "consistent")
            qps = res["answered"] / res["wall"]
            rows.append(fmt_row(
                f"serving[{name},{mode}]", 1e6 / max(qps, 1e-9),
                f"answered_per_s={qps:.1f};p50_ms={res['p50_ms']:.1f};"
                f"p99_ms={res['p99_ms']:.1f};"
                f"staleness_ticks_p50={res['staleness_p50']:.1f};"
                f"events_per_s={res['events_per_s']:.0f};"
                f"answered={res['answered']}"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
