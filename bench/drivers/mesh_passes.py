"""Closed-loop ingest in passes, on a mesh of the cell's chips.

The passes driver (`passes.py`), loaded and run as it is. The one
difference: each session's `D3Pipeline` is built on
`make_stream_mesh(cell["chips"])`, so `MeshRouter` shards the parts over
the chips and exchanges records with one all_to_all per route. The
session is otherwise the harness's own (`Run.new_session`, with its timed
staging).

Besides what `passes.py` records, a run keeps:

  wire        for each timed pass, the pipeline's `StreamMetrics`
              `wire_bytes`, `launches` (drains included) and
              `drain_launches`;
  spans       in a traced run, `span_reduce.reduce` of the traced pass
              over all chips (`all`, the mean) and for each chip alone
              (`chips`: each device plane that ran an op; a TPU trace
              also holds device planes that run none), by the op names
              of both compiled programs, lowered from the window's last
              session.

and logs each chip's peak memory, each pass's drain launches and, per
launch of the untraced passes, the host spans a launch is made of.

A traced run's window does not end with its traced pass: a pass here
outlasts `--seconds`, and the readers of host time (`stage_ms.ingest`)
read the untraced passes, so at least one follows the traced pass.
"""
from __future__ import annotations

import functools
import gc
from pathlib import Path

import harness
import span_reduce as sr
import trace_reduce as tr

HERE = Path(__file__).resolve().parent
passes = harness.load_module(HERE / "passes.py")
stream, control, check = passes.stream, passes.control, passes.check
HOST_SPANS = ("d3.stage.partition", "d3.stage.pack", "d3.dispatch",
              "d3.sync", "d3.harvest")


def _mesh_session(run, mesh, kept: list, last: list = None):
    """`Run.new_session` with the pipeline built on `mesh`; keeps the
    session's metrics, and in `last` (when given) the newest session,
    which the trace's reduction lowers the programs from and then frees."""
    from repro.core import pipeline

    if last:                   # the old tables go before the new are made
        last.clear()
        gc.collect()
    build = pipeline.D3Pipeline
    pipeline.D3Pipeline = functools.partial(build, mesh=mesh)
    try:
        session = harness.Run.new_session(run)
    finally:
        pipeline.D3Pipeline = build
    kept.append(session.pipe.metrics)
    if last is not None:
        last[:] = [session]
    return session


def _reduce_spans(run, reduce_trace, last: list, stretch: str) -> None:
    """Reduce the traced pass by the engine's names over all chips and
    for each chip, then let the harness reduce it its own way. The
    programs' texts come from the window's last session (the persistent
    cache gives back what its launches compiled), which is then freed."""
    span_readings = harness.load_module(HERE.parent / "tests" /
                                        "span_readings.py")
    texts = span_readings.program_texts(run, last[0].pipe)
    last.clear()
    gc.collect()
    names = tuple(sr.op_names_from_hlo(t) for t in texts)
    planes = sr.read_planes(tr.newest_xplane(run._trace_dir), stretch)
    host = [p for p in planes if not tr._is_device(p[0])]
    chips = (sr.reduce(host + [p], stretch, *names)
             for p in planes if tr._is_device(p[0]))
    run.rec["spans"] = {"all": sr.reduce(planes, stretch, *names),
                        "chips": [c for c in chips if c is not None]}
    reduce_trace(stretch)


def _window_past_trace(run) -> None:
    """Hold the window open through the traced pass: `--seconds` is
    tested again only after the pass that follows it, untraced."""
    seconds, stop, new = run.seconds, run.stop_trace, run.new_session

    def stop_trace():
        stop()
        run.seconds = float("inf")

    def new_session():
        run.seconds = seconds
        return new()

    run.stop_trace, run.new_session = stop_trace, new_session


def measure(run) -> None:
    from repro.launch.mesh import make_stream_mesh

    mesh = make_stream_mesh(run.cell["chips"])
    kept, last = [], []
    run.new_session = lambda: _mesh_session(run, mesh, kept, last)
    run.reduce_trace = functools.partial(_reduce_spans, run,
                                         run.reduce_trace, last)
    if run.trace:
        _window_past_trace(run)
    passes.measure(run)
    last.clear()
    timed = run.rec["passes"]
    # kept[0] is the warm-up's session
    run.rec["wire"] = [{"wire_bytes": m.wire_bytes, "launches": m.launches,
                        "drain_launches": m.drain_launches,
                        "traced": p["traced"]}
                       for m, p in zip(kept[1:], timed)]
    harness.log("drain launches per pass: "
                f"{[w['drain_launches'] for w in run.rec['wire']]}")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in mesh.devices.flat]
    harness.log(f"memory peak per chip: {peaks}")
    untraced = [m for m, p in zip(kept[1:], timed) if not p["traced"]]
    n = sum(m.launches for m in untraced)
    if n:
        per = {k: round(1e3 * sum(m.spans[k].self_s for m in untraced
                                  if k in m.spans) / n, 3)
               for k in HOST_SPANS}
        harness.log(f"host spans per launch (self ms, {n} launches): {per}")
