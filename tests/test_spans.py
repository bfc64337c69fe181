"""Host spans, counters and device plane scopes (`telemetry/spans.py`,
`core/tick.py`, `core/pipeline.py`), driven one tick a launch ("tick")
and T ticks a launch ("super"): the spans' totals
and self times, their launch numbers and nesting in a profiler trace,
the staging counters, host seconds with the telemetry plane off, the
`d3.*` op names in the compiled tick, and a compiled program that the
scopes leave unchanged apart from metadata.
"""
import contextlib
import glob
import os
import re
import time

import jax
import numpy as np
import pytest

from repro.core import pipeline as pl
from repro.core import windowing as win
from repro.core.pipeline import D3Pipeline, PipelineConfig
from repro.graph.sage import GraphSAGE
from repro.telemetry.spans import SpanClock

DRIVERS = ["tick", "super"]
N_NODES, D_IN, TICK, T = 48, 8, 16, 4
HOST = ("d3.chunk", "d3.launch", "d3.stage", "d3.stage.partition",
        "d3.stage.pack", "d3.dispatch", "d3.sync", "d3.harvest",
        "d3.drain")


def make_stream(seed=0, n_edges=96):
    rng = np.random.default_rng(seed)
    edges = np.stack([rng.integers(0, N_NODES, n_edges),
                      rng.integers(0, N_NODES, n_edges)], 1)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = {v: rng.normal(size=D_IN).astype(np.float32)
             for v in range(N_NODES)}
    return edges, feats


def build(telemetry=False):
    model = GraphSAGE((D_IN, 12, 12))
    cfg = PipelineConfig(n_parts=4, node_cap=32, edge_cap=128,
                         repl_cap=128, feat_cap=64, edge_tick_cap=TICK,
                         max_nodes=N_NODES, query_cap=8, query_tick_cap=4,
                         telemetry=telemetry,
                         window=win.WindowConfig(kind=win.SESSION,
                                                 interval=2))
    return D3Pipeline(model, model.init(jax.random.key(0)), cfg)


def drive(pipe, driver, edges, feats):
    e_chunks, f_chunks = pipe.chunk_stream(edges, feats, TICK)
    if driver == "tick":
        for e, f in zip(e_chunks, f_chunks):
            pipe.tick(e, f)
        pipe.flush(max_ticks=64)
    else:
        for lo in range(0, len(e_chunks), T):
            pipe.run_super_tick(e_chunks[lo: lo + T], f_chunks[lo: lo + T],
                                T=T)
        pipe.flush_super(max_ticks=64, T=T)
    return pipe


@pytest.fixture(scope="module")
def driven():
    """One pipeline per launch size, driven over the same tiny stream."""
    edges, feats = make_stream()
    return {d: drive(build(), d, edges, feats) for d in DRIVERS}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Host events of each launch size's run under the profiler, on the CPU:
    {driver: [(name, start_ns, end_ns, stats)]} of the d3.* spans."""
    edges, feats = make_stream(seed=1)
    out = {}
    for d in DRIVERS:
        pipe = build()
        drive(pipe, d, edges[:TICK * T], feats)       # compile off-trace
        pipe = build()
        log_dir = str(tmp_path_factory.mktemp(f"trace_{d}"))
        with jax.profiler.trace(log_dir):
            drive(pipe, d, edges, feats)
        path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
        evs = []
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("d3."):
                        evs.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats), plane.name))
        out[d] = (pipe, evs)
    return out


def test_span_clock_self_time_and_launch_number(monkeypatch):
    seen = []

    class Ann:
        def __init__(self, name, **kw):
            seen.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Ann)
    table = {}
    clock = SpanClock(table)
    with clock.span("outer", 3, step=True):
        with clock.span("a", 3):
            time.sleep(0.01)
        with clock.span("b", 3):
            with clock.span("c", 3):
                time.sleep(0.01)
        with clock.span("a", 3):
            pass
    assert seen == [("outer", {"step_num": 3}), ("a", {"launch": 3}),
                    ("b", {"launch": 3}), ("c", {"launch": 3}),
                    ("a", {"launch": 3})]
    approx = lambda x: pytest.approx(x, rel=1e-9, abs=1e-12)
    assert table["a"].count == 2 and table["outer"].count == 1
    assert table["a"].self_s == approx(table["a"].total_s)
    assert table["b"].self_s == approx(table["b"].total_s
                                       - table["c"].total_s)
    assert table["outer"].self_s == approx(
        table["outer"].total_s - table["a"].total_s - table["b"].total_s)
    assert table["c"].total_s >= 0.01
    assert clock.total("a") == table["a"].total_s
    assert clock.total("never") == 0.0


@pytest.mark.parametrize("driver", DRIVERS)
def test_self_time_is_total_minus_children(driven, driver):
    sp = driven[driver].metrics.spans
    assert set(sp) == set(HOST)
    tot = lambda n: sp[n].total_s
    approx = lambda x: pytest.approx(x, rel=1e-9, abs=1e-9)
    # the launch holds staging, the dispatch, the sync and the harvest
    assert sp["d3.launch"].self_s == approx(
        tot("d3.launch") - tot("d3.stage") - tot("d3.dispatch")
        - tot("d3.sync") - tot("d3.harvest"))
    assert sp["d3.stage"].self_s == approx(
        tot("d3.stage") - tot("d3.stage.partition") - tot("d3.stage.pack"))
    for leaf in ("d3.stage.partition", "d3.stage.pack", "d3.dispatch",
                 "d3.sync", "d3.harvest", "d3.chunk"):
        assert sp[leaf].self_s == approx(tot(leaf))
    assert 0 < sp["d3.drain"].self_s < tot("d3.drain")
    assert all(v.self_s > 0 for v in sp.values())


@pytest.mark.parametrize("driver", DRIVERS)
def test_counters_match_the_staged_batches(driver, monkeypatch):
    edges, feats = make_stream(seed=2)
    pipe = build()
    staged = []
    orig = pipe._stage_super_batches

    def keep(*a, **k):
        out = orig(*a, **k)
        staged.append(out)
        return out

    monkeypatch.setattr(pipe, "_stage_super_batches", keep)
    drive(pipe, driver, edges, feats)
    m = pipe.metrics
    fbs = [b[0] for b in staged]
    assert m.launches == len(staged) == m.spans["d3.launch"].count
    assert m.spans["d3.sync"].count == m.launches
    assert 0 < m.drain_launches < m.launches
    assert m.edges_staged == len(edges)
    assert m.feat_rows_staged == sum(int(np.sum(np.asarray(fb.valid)))
                                     for fb in fbs)
    assert m.feat_rows_staged == len(np.unique(edges))
    cap = pipe.cfg.feat_cap
    assert m.feat_slots_uploaded == sum(fb.valid.size for fb in fbs) \
        == cap * (1 if driver == "tick" else T) * m.launches
    assert m.upload_bytes == sum(x.nbytes for b in staged
                                 for x in jax.tree.leaves(b))


@pytest.mark.parametrize("driver", DRIVERS)
def test_host_seconds_without_the_telemetry_plane(driven, driver):
    pipe = driven[driver]
    assert pipe.trace is None
    m = pipe.metrics
    assert m.host_seconds > 0
    assert m.host_seconds == m.spans["d3.stage"].total_s
    assert m.wall_seconds == m.spans["d3.launch"].total_s > m.host_seconds
    assert m.throughput == m.emitted_total / m.wall_seconds


@pytest.mark.parametrize("driver", DRIVERS)
def test_trace_rows_carry_the_staging_time(driver):
    edges, feats = make_stream(seed=3)
    pipe = drive(build(telemetry=True), driver, edges, feats)
    cols = pipe.trace.columns()
    assert (cols["host_s"] > 0).all()
    assert cols["host_s"].sum() == pytest.approx(pipe.metrics.host_seconds)
    assert cols["wall_s"].sum() <= pipe.metrics.wall_seconds


@pytest.mark.parametrize("driver", DRIVERS)
def test_spans_of_one_launch_share_its_number(traced, driver):
    pipe, evs = traced[driver]
    launches = sorted((s, e, int(st["step_num"]))
                      for n, s, e, st, _ in evs if n == "d3.launch")
    assert [n for _, _, n in launches] == list(
        range(1, pipe.metrics.launches + 1))
    inside = 0
    for n, s, e, st, _ in evs:
        if n == "d3.launch":
            continue
        owner = [k for s0, e0, k in launches if s0 <= s and e <= e0]
        number = int(st["launch"])
        if owner:
            inside += 1
            assert number == owner[0], (n, number, owner)
        else:   # between launches: the number of the last one opened
            assert number == sum(1 for s0, _, _ in launches if s0 < s)
    assert inside >= 4 * pipe.metrics.launches


@pytest.mark.parametrize("driver", DRIVERS)
def test_cpu_trace_nests_spans_as_placed(traced, driver):
    pipe, evs = traced[driver]
    assert {n for n, *_ in evs} == set(HOST)
    assert all(p.startswith("/host:") for *_, p in evs)
    counts = {n: sum(1 for m, *_ in evs if m == n) for n in HOST}
    assert counts == {n: v.count for n, v in pipe.metrics.spans.items()}

    def parent(ev):
        n, s, e = ev[:3]
        outer = [o for o in evs if o is not ev and o[1] <= s and e <= o[2]
                 and (o[1], -o[2]) < (s, -e)]
        return max(outer, key=lambda o: o[1])[0] if outer else None

    want = {"d3.stage": {"d3.launch"}, "d3.dispatch": {"d3.launch"},
            "d3.sync": {"d3.launch"}, "d3.harvest": {"d3.launch"},
            "d3.stage.partition": {"d3.stage"},
            "d3.stage.pack": {"d3.stage"},
            "d3.launch": {None, "d3.drain"}, "d3.chunk": {None},
            "d3.drain": {None}}
    for ev in evs:
        assert parent(ev) in want[ev[0]], (ev[0], parent(ev))


def _compiled(driver, pipe):
    """The compiled text of the launch program for `pipe`: one tick
    ("tick") or T ticks ("super") a launch."""
    ticks = 1 if driver == "tick" else T
    return pl.lower_super_tick(pipe.model, pipe.cfg,
                               ticks).compile().as_text()


@contextlib.contextmanager
def no_scopes():
    """The program as it would be without named scopes."""
    orig = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    jax.clear_caches()
    try:
        yield
    finally:
        jax.named_scope = orig
        jax.clear_caches()


@pytest.mark.parametrize("driver", DRIVERS)
def test_compiled_tick_carries_the_plane_scopes(driver):
    text = _compiled(driver, build())
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("d3.layer0/d3.deliver", "d3.layer0/d3.round_b",
                  "d3.layer0/d3.forward", "d3.layer0/d3.round_a",
                  "d3.layer1/d3.deliver", "d3.topo", "d3.sink",
                  "d3.query"):
        assert any(scope in n for n in names), scope
    assert any("d3.quiet" in n for n in names)


@pytest.mark.parametrize("driver", DRIVERS)
def test_scopes_leave_the_program_unchanged(driver):
    def strip(text):
        """The program without its metadata: op names, and the source
        tables of the module's header."""
        keep = ("%", "ROOT", "ENTRY", "}", "HloModule")
        return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(
            ln for ln in text.splitlines() if ln.lstrip().startswith(keep)))

    edges, feats = make_stream(seed=4)
    scoped = _compiled(driver, build())
    sink = np.asarray(drive(build(), driver, edges, feats).sink)
    with no_scopes():
        bare = _compiled(driver, build())
        bare_sink = np.asarray(drive(build(), driver, edges, feats).sink)
    assert "/d3." in scoped and "/d3." not in bare
    assert strip(scoped) == strip(bare)
    np.testing.assert_array_equal(sink, bare_sink)
