"""Fail-stop recovery drill (ISSUE 10): time-to-first-answer + degraded p99.

Metric: what serving actually pays for a fail-stop shard loss. The worker
streams the chaos plane's hub-heavy stream on a 4-shard mesh with
consistent-cut checkpoints, then loses 2 shards mid-stream and recovers
live: checkpoint-restore, `D3Pipeline.reshard` onto the survivor mesh,
replay of the chunks since the cut — with the ServeSession in declared
degraded mode the whole time. Queries submitted during the degraded
window measure the p99 a client would see mid-recovery;
`time-to-first-answer` is the wall clock from the moment of failure to
the first post-failure answer landing on the host.

Row:

  recovery[failstop,D=4->2]
    us_per_call   = recovery wall time (failure -> stream resumed), in us
    first_answer_ms = failure -> first post-failure answer
    p99_degraded_ms = answer p99 over queries issued while degraded
    dropped / route_dropped = MUST be 0 (the CI validator gates this)
    replayed      = chunks replayed from the last consistent cut

Runs in a subprocess with a forced 4-device CPU backend (the XLA device
count is fixed at backend init), mirroring bench_serving/bench_scaling.
"""
from __future__ import annotations

import subprocess
import sys
from pathlib import Path

from benchmarks.common import fmt_row

REPO = Path(__file__).resolve().parents[1]

_WORKER = """
import tempfile
import time
import numpy as np
import jax
from repro.ft.chaos import (ChaosConfig, hub_heavy_stream, _chunks,
                            _feat_rows, build_pipeline, _advance)
from repro.ft.checkpoint import CheckpointManager
from repro.launch.mesh import make_stream_mesh, survivor_mesh
from repro.serve.session import ServeSession

cfg = ChaosConfig(n_events={n_events})
edges, feats, hubs = hub_heavy_stream(cfg)
chunks = _chunks(cfg, edges)
fail_at = min(cfg.fail_at_chunk, len(chunks) - 1)

pipe = build_pipeline(cfg, make_stream_mesh(4))
session = ServeSession(pipe, super_ticks=1, max_retries=2)
mgr = CheckpointManager(tempfile.mkdtemp(), keep=3)

recovery_s = first_answer_s = None
degraded_qids = []
replayed = 0
for i, chunk in enumerate(chunks):
    if i == fail_at:
        t_fail = time.perf_counter()
        session.degrade("failstop")
        surv = survivor_mesh(pipe.mesh, cfg.lose_shards, n_data=2)
        restored = mgr.restore_pipeline(pipe)
        pipe.reshard(surv)
        # queries issued while degraded: the p99 a client sees
        degraded_qids = session.submit_embed([int(h) for h in hubs])
        n_before = len(session.answers)
        for j in range(restored, i):
            _advance(session, chunks[j], feats)
            replayed += 1
        t = 0
        while len(session.answers) <= n_before and t < 64:
            _advance(session, np.zeros((0, 2), np.int64), feats)
            t += 1
        first_answer_s = time.perf_counter() - t_fail
        session.restore_normal()
        recovery_s = first_answer_s
    _advance(session, chunk, feats)
    if (i + 1) % cfg.checkpoint_every == 0 and i < fail_at:
        mgr.save_pipeline(i + 1, pipe)
session.flush()

lat = [session.answers[q].latency_s for q in degraded_qids
       if q in session.answers
       and session.answers[q].latency_s is not None]
p99 = float(np.percentile(np.asarray(lat), 99) * 1e3) if lat else float("nan")
st = session.latency_stats()
print(f"RESULT,{{recovery_s:.6f}},{{first_answer_s:.6f}},{{p99:.3f}},"
      f"{{int(pipe.metrics.dropped)}},{{int(pipe.metrics.route_dropped)}},"
      f"{{replayed}},{{st['answered']}}")
"""


def _worker(n_events: int, timeout: int = 560):
    env = {"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
           "HOME": "/root", "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4 "
                        "--xla_backend_optimization_level=0"}
    r = subprocess.run(
        [sys.executable, "-c",
         _WORKER.format(n_events=n_events)],
        env=env, capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError("recovery worker failed:\n"
                           + r.stderr[-2000:])
    for line in r.stdout.splitlines():
        if line.startswith("RESULT,"):
            _, rec, first, p99, drop, rdrop, rep, ans = line.split(",")
            return {"recovery_s": float(rec), "first_answer_s": float(first),
                    "p99_ms": float(p99), "dropped": int(drop),
                    "route_dropped": int(rdrop), "replayed": int(rep),
                    "answered": int(ans)}
    raise RuntimeError("recovery worker printed no RESULT row")


def run(scale: str = "small"):
    n_events = {"small": 288, "full": 1152}[scale]
    res = _worker(n_events)
    return [fmt_row(
        "recovery[failstop,D=4->2]",
        res["recovery_s"] * 1e6,
        f"recovery_s={res['recovery_s']:.3f};"
        f"first_answer_ms={res['first_answer_s'] * 1e3:.1f};"
        f"p99_degraded_ms={res['p99_ms']:.1f};"
        f"dropped={res['dropped']};"
        f"route_dropped={res['route_dropped']};"
        f"replayed={res['replayed']};answered={res['answered']}")]


if __name__ == "__main__":
    for r in run():
        print(r)
