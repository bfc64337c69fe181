"""CI telemetry lane: record a short hub-heavy trace, run the
capacity advisor, and REPLAY its recommendation.

Everything runs in one forced-4-device subprocess (the XLA host-platform
device count is fixed at backend init, same pattern as bench_scaling):

  1. stream a hub-heavy power-law graph through the super-tick driver
     with the telemetry plane on and a DENSE exchange (route_cap=None —
     peaks recorded under a capped config reflect that config's deferral
     dynamics, see telemetry/advisor.py), saving TRACE.npz;
  2. run `telemetry/advisor.py` -> RECS.json (caps already validated
     against PipelineConfig.validate() by the advisor itself);
  3. replay the SAME stream under the recommended caps and assert the
     acceptance bar: dropped == 0, route_dropped == 0, wire bytes <=
     the dense config, and a bit-identical sink.

CLI:  PYTHONPATH=src:. python benchmarks/record_trace.py \
          --trace TRACE.npz --recs RECS.json
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

_WORKER = """
import json
import numpy as np
import jax
from repro.core import windowing as win
from repro.core.pipeline import D3Pipeline, PipelineConfig
from repro.graph.graphs import powerlaw_edges
from repro.graph.sage import GraphSAGE
from repro.launch.mesh import make_stream_mesh
from repro.telemetry import (apply_recommendation, load_trace, recommend,
                             replay_ok)

D = {n_devices}
N_EDGES = {n_edges}
TICK_EDGES, SUPER_T = 32, 8
TRACE, RECS = {trace!r}, {recs!r}

rng = np.random.default_rng(0)
n_nodes = 160
edges = powerlaw_edges(rng, n_nodes, N_EDGES, 1.3)       # hub-heavy
feats = {{v: rng.normal(size=16).astype(np.float32)
          for v in range(n_nodes)}}
mesh = make_stream_mesh(D)

def build(cfg=None, telemetry=False):
    model = GraphSAGE((16, 24, 24))
    params = model.init(jax.random.key(0))
    cfg = cfg or PipelineConfig(
        n_parts=8, node_cap=128, edge_cap=1024, repl_cap=512,
        feat_cap=512, edge_tick_cap=TICK_EDGES, max_nodes=n_nodes,
        telemetry=telemetry,
        window=win.WindowConfig(kind=win.STREAMING))
    return model, params, D3Pipeline(model, params, cfg, mesh=mesh)

def drive(pipe):
    pipe.run_stream_super(edges, feats, tick_edges=TICK_EDGES,
                          super_ticks=SUPER_T)
    pipe.flush_super(max_ticks=64, T=SUPER_T)

# 1. record the dense observability trace
model, params, dense = build(telemetry=True)
drive(dense)
dense.save_trace(TRACE)
trace = load_trace(TRACE)

# 2. advisor (bounds-checked inside recommend())
recs = recommend(trace)
with open(RECS, "w") as f:
    json.dump(recs, f, indent=2)

# 3. replay the recommendation through the real pipeline
cfg2 = apply_recommendation(
    PipelineConfig(n_parts=8, node_cap=128, edge_cap=1024, repl_cap=512,
                   max_nodes=n_nodes), recs)
_, _, pipe2 = build(cfg=cfg2)
drive(pipe2)
out = replay_ok(pipe2)                    # raises on any drop
assert pipe2._wire_bytes_per_tick <= dense._wire_bytes_per_tick, \
    "recommended caps cost MORE wire than dense"
np.testing.assert_array_equal(np.asarray(pipe2.sink),
                              np.asarray(dense.sink))
print("RESULT,record_trace,"
      f"{{len(trace)}},{{recs['caps']['route_cap']}},{{out['wire_bytes']}},"
      f"{{dense.metrics.wire_bytes}}")
"""


def run(trace: str, recs: str, n_devices: int = 4, n_edges: int = 960,
        timeout: int = 560) -> dict:
    env = {"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
           "HOME": "/root", "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_devices}"}
    r = subprocess.run(
        [sys.executable, "-c",
         _WORKER.format(n_devices=n_devices, n_edges=n_edges,
                        trace=str(trace), recs=str(recs))],
        env=env, capture_output=True, text=True, timeout=timeout)
    sys.stderr.write(r.stderr[-2000:])
    if r.returncode != 0:
        raise RuntimeError("record_trace worker failed:\n" + r.stderr[-3000:])
    for line in r.stdout.splitlines():
        if line.startswith("RESULT,record_trace,"):
            _, _, ticks, route_cap, wire_rec, wire_dense = line.split(",")
            return {"ticks": int(ticks),
                    "route_cap": None if route_cap == "None"
                    else int(route_cap),
                    "wire_bytes_recommended": int(wire_rec),
                    "wire_bytes_dense": int(wire_dense)}
    raise RuntimeError("record_trace worker printed no RESULT:\n"
                       + r.stdout[-2000:])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace", default="TRACE.npz")
    ap.add_argument("--recs", default="RECS.json")
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--edges", type=int, default=960)
    args = ap.parse_args()
    out = run(args.trace, args.recs, args.devices, args.edges)
    with open(args.recs) as f:
        recs = json.load(f)
    print(json.dumps({"summary": out, "caps": recs["caps"]}, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
