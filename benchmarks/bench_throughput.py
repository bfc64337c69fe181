"""Paper Fig. 4a: inference throughput scaling — Streaming vs windowed
(Tumbling/Session/Adaptive) across parallelism levels — plus one-tick
launches against 16-tick launches of the same scan program.

Metric: final-layer representations produced per second (the paper's
"rate of producing final layer representations"); for the launch-size
comparison, stream events ingested per second end-to-end.
"""
from __future__ import annotations

import time

from repro.core import windowing as win

from benchmarks.common import fmt_row, make_case, make_pipeline, run_and_time

POLICIES = {
    "streaming": win.WindowConfig(kind=win.STREAMING),
    "tumbling": win.WindowConfig(kind=win.TUMBLING, interval=4),
    "session": win.WindowConfig(kind=win.SESSION, interval=4),
    "adaptive": win.WindowConfig(kind=win.ADAPTIVE),
}

# fine micro-ticks: the paper's low-latency coalescing regime, where a
# one-tick launch pays a launch's fixed cost (staging, dispatch, host
# sync) every 32 events and a T=16 launch amortizes it over 16 ticks
SUPER_T = 16
SUPER_TICK_EDGES = 32


def _lean_pipeline(case, window=None):
    return make_pipeline(case, n_parts=8, window=window, node_cap=128,
                         edge_cap=1024, feat_cap=256, edge_tick_cap=128)


def run_driver_comparison(n_edges: int = 4000):
    """events/sec: T=1 launches (`run_stream`) vs T=16 launches on
    n_parts=8. Row names keep `driver[per_tick]` / `driver[super_tick]`
    so CI baselines stay comparable."""
    case = make_case(n_edges=n_edges)
    warm = case.edges[:640]

    _, _, pipe = _lean_pipeline(case)
    pipe.run_stream(warm, case.feats, tick_edges=SUPER_TICK_EDGES)
    pipe.flush(max_ticks=64)
    _, _, pipe = _lean_pipeline(case)
    t0 = time.perf_counter()
    pipe.run_stream(case.edges, case.feats, tick_edges=SUPER_TICK_EDGES)
    pipe.flush(max_ticks=128)
    per_tick_evs = n_edges / (time.perf_counter() - t0)

    _, _, pipe = _lean_pipeline(case)
    pipe.run_stream_super(warm, case.feats, tick_edges=SUPER_TICK_EDGES,
                          super_ticks=SUPER_T)
    pipe.flush_super(max_ticks=64, T=4)
    _, _, pipe = _lean_pipeline(case)
    t0 = time.perf_counter()
    pipe.run_stream_super(case.edges, case.feats,
                          tick_edges=SUPER_TICK_EDGES, super_ticks=SUPER_T)
    pipe.flush_super(max_ticks=128, T=4)
    super_evs = n_edges / (time.perf_counter() - t0)

    speedup = super_evs / per_tick_evs
    return [
        fmt_row("driver[per_tick]", 1e6 / per_tick_evs,
                f"events_per_s={per_tick_evs:.0f}"),
        fmt_row(f"driver[super_tick,T={SUPER_T}]", 1e6 / super_evs,
                f"events_per_s={super_evs:.0f};speedup={speedup:.2f}x"),
    ]


def run(scale: str = "small"):
    n_edges = {"small": 1500, "full": 20000}[scale]
    case = make_case(n_edges=n_edges)
    rows = []
    for par in (2, 4, 8):
        for name, policy in POLICIES.items():
            _, _, pipe = make_pipeline(case, n_parts=8, window=policy,
                                       base_parallelism=par)
            wall = run_and_time(pipe, case, tick_edges=128)
            thr = pipe.metrics.emitted_total / wall
            rows.append(fmt_row(
                f"fig4a_throughput[{name},p={par}]",
                1e6 * wall / max(pipe.metrics.emitted_total, 1),
                f"emitted={pipe.metrics.emitted_total};rep_per_s={thr:.0f}"))
    rows.extend(run_driver_comparison())
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
