#!/usr/bin/env python3
"""Where a launch's time goes, read from the engine's own spans, scopes
and counters, on the chip, in one process.

Runs a cell's passes the way its driver does (`drivers/passes.py`):
warm-up, then `--passes` untraced passes and one traced pass, each on a
freshly zeroed session. For every pass it keeps the wall time, the
harness's own timing of host staging, and the pipeline's
`metrics.spans` and staging counters; the traced pass is reduced by
`span_reduce.reduce` (device time per plane, idle per host span).

    python bench/tests/span_readings.py --workload reddit-hub-ingest \\
        --seed 7 --passes 3 --out spans_out \\
        --fixture bench/tests/data/spans_fixture.json

Prints one JSON object: per launch, the host layers (from the untraced
passes), the device planes and the idle split (from the traced pass),
and the closures the breakdown is checked by. `--out` keeps the raw
trace (gzipped) and the result; `--fixture` writes the small reduced
trace that `test_span_reduce.py` reads: two launches of the traced
pass, their leaf and parent ops with the scope each ran under, and the
host `d3.*` spans. `--cpu` runs the cell cut to a tiny size on the CPU
(no device plane: the device part reads nothing).
"""
from __future__ import annotations

import argparse
import gc
import gzip
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for _p in (BENCH, BENCH.parent / "src", BENCH / "tests"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import harness  # noqa: E402
import span_reduce as sr  # noqa: E402
import trace_reduce as tr  # noqa: E402

HOST_LAYERS = {"partition": "d3.stage.partition", "pack": "d3.stage.pack",
               "dispatch": "d3.dispatch", "sync": "d3.sync",
               "harvest": "d3.harvest"}
COUNTERS = ("edges_staged", "feat_rows_staged", "feat_slots_uploaded",
            "upload_bytes", "launches", "drain_launches")


def _pass_record(run, driver, session, edges, feats) -> dict:
    s0, l0 = run.stage_s, run.launches
    t = time.perf_counter()
    with run.phase("pass"):
        driver._one_pass(run, session, edges, feats)
    dt = time.perf_counter() - t
    m = session.pipe.metrics
    return {"seconds": dt, "stage_s": run.stage_s - s0,
            "launches": run.launches - l0,
            "spans": {k: [v.count, v.total_s, v.self_s]
                      for k, v in m.spans.items()},
            "counters": {k: getattr(m, k) for k in COUNTERS}}


def _per_launch(passes: list) -> dict:
    """Host layers per launch over `passes` (self seconds, in ms)."""
    n = sum(p["launches"] for p in passes)
    tot = lambda name, i: sum(p["spans"].get(name, [0, 0.0, 0.0])[i]
                              for p in passes)
    out = {k: 1e3 * tot(s, 2) / n for k, s in HOST_LAYERS.items()}
    out.update({
        "stage_in_program_ms": 1e3 * tot("d3.stage", 1) / n,
        "stage_outside_ms": 1e3 * sum(p["stage_s"] for p in passes) / n,
        "launch_ms": 1e3 * tot("d3.launch", 1) / n,
        "wall_ms": 1e3 * sum(p["seconds"] for p in passes) / n,
        # the top-level spans: drain launches nest in d3.drain
        "top_level_cover": (tot("d3.chunk", 1) + tot("d3.launch", 1)
                            + tot("d3.drain", 2))
        / sum(p["seconds"] for p in passes),
        "upload_mb": sum(p["counters"]["upload_bytes"] for p in passes)
        / n / 1e6,
        "spans_per_launch": sum(v[0] for p in passes
                                for v in p["spans"].values()) / n})
    return out


def program_texts(run, pipe) -> tuple:
    """The compiled text of the super-tick as the run launched it, for
    the configured window and for the drain's STREAMING one (the same
    lowering as the launches: the persistent cache gives them back)."""
    import jax.numpy as jnp
    from repro.core import pipeline as pl
    from repro.core import state as st
    from repro.core import windowing as win

    cfg = pipe.cfg
    empty = [None] * run.config["T"]
    batches = pipe._stage_super_batches(empty, empty, empty, empty)
    carry = st.PipelineCarry(
        topo=pipe.topo, layers=tuple(pipe.states), sink=pipe.sink,
        sink_seen=pipe.sink_seen, queries=pipe.queries,
        now=jnp.asarray(pipe.now, jnp.int32), quiet=jnp.asarray(0, jnp.int32),
        train=pipe.train_state)
    return tuple(pl._super_tick_scan.lower(
        tuple(pipe.layers), pipe.params, carry, batches, w,
        cfg.capacities().outbox, pipe.router, pipe.delivery, pipe.mesh,
        cfg.delta_eps, pipe.train_cfg, pipe._head,
        cfg.telemetry).compile().as_text()
        for w in (cfg.window, win.WindowConfig(kind=win.STREAMING)))


def fixture(planes: list, names: tuple, n_launch: int = 2) -> dict:
    """Launches 2 .. n_launch + 1 of the traced pass: every device op
    with the scope path it ran under (`names`: the op names of the
    steady and the drain program), and the host d3.* spans, in ns from
    the first launch's start. Ops are rows [instruction index, start
    less the previous op's start, duration, scope index]."""
    host = sorted((n, s, e) for name, lines in planes
                  if not tr._is_device(name) for _, evs in lines
                  for n, s, e, _ in evs if n.startswith(sr.PREFIX))
    drains = [(s, e) for n, s, e in host if n == "d3.drain"]
    starts = sorted(s for n, s, _ in host if n == "d3.launch")
    w0 = starts[1]
    w1 = starts[1 + n_launch] if len(starts) > 1 + n_launch else max(
        e for _, _, e in host)
    ops = []
    for name, lines in planes:
        if tr._is_device(name):
            for ln, evs in lines:
                if ln in tr.OP_LINES:
                    ops += [(n, s, e, st) for n, s, e, st in evs
                            if s >= w0 and e <= w1]
            break
    scopes, instrs, rows, last = {}, {}, [], w0
    for n, s, e, st in sorted(ops, key=lambda o: o[1]):
        in_drain = any(d0 <= s < d1 for d0, d1 in drains)
        path = "/".join("d3." + p for p in
                        sr.scope_of(n, st, names[1] if in_drain
                                    else names[0]))
        instr = sr._INSTR.match(n).group(1)
        rows.append([instrs.setdefault(instr, len(instrs)), int(s - last),
                     int(e - s), scopes.setdefault(path, len(scopes))])
        last = s
    return {"about": f"{n_launch} launches of a traced reddit-hub-ingest "
                     "pass on a TPU v5e, ns from the first one's start",
            "window_ns": int(w1 - w0), "scopes": list(scopes),
            "instructions": list(instrs), "ops": rows,
            "spans": [[n, int(s - w0), int(e - w0)] for n, s, e in host
                      if s >= w0 and e <= w1]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="reddit-hub-ingest")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--out", default=None)
    ap.add_argument("--fixture", default=None)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    t_start = time.perf_counter()

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = harness.load_json(harness.ROOT / entry["file"])
    traffic = harness.load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    import jax
    if args.cpu:
        import tiny
        config, traffic = tiny.shrink(config, traffic)
    else:
        jax.config.update("jax_compilation_cache_dir",
                          str(harness.CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        if jax.devices()[0].platform != "tpu":
            print("no TPU visible", file=sys.stderr)
            return 2
    dev = jax.devices()[0]
    run = harness.Run(cell, config, traffic, args.seed, 0.0, False, t_start)
    harness._listen(run)
    driver = harness.load_module(BENCH / "drivers" /
                                 f"{traffic['driver']}.py")
    run.build_model()
    T, tick = config["T"], config["tick_edges"]
    edges, feats = driver.stream(run)
    session = run.new_session()
    e_chunks, f_chunks = session.pipe.chunk_stream(edges[: T * tick], feats,
                                                   tick)
    session.advance_super(e_chunks, f_chunks, T=T)
    session.flush()
    setup_s = time.perf_counter() - t_start

    def fresh():
        nonlocal session
        session = None
        gc.collect()
        session = run.new_session()
        return session

    untraced = [_pass_record(run, driver, fresh(), edges, feats)
                for _ in range(args.passes)]
    compiles0 = run.compiles
    fresh()
    run.trace = True
    run.start_trace()
    traced = _pass_record(run, driver, session, edges, feats)
    run.stop_trace()
    run.trace = False
    texts = program_texts(run, fresh().pipe)
    session = None
    gc.collect()

    path = tr.newest_xplane(run._trace_dir)
    planes = sr.read_planes(path, "pass")
    names = tuple(sr.op_names_from_hlo(t) for t in texts)
    red = sr.reduce(planes, "pass", *names)
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "seed": args.seed, "setup_s": setup_s,
           "compiles_after_warmup": run.compiles - compiles0,
           "untraced": _per_launch(untraced), "traced": _per_launch([traced]),
           "passes": untraced + [traced], "reduction": red}
    if red is not None:
        n = red["launches"]
        per = lambda d: {k: 1e3 * v / n for k, v in d.items()}
        out["per_launch_device"] = {
            "busy_ms": 1e3 * red["busy_s"] / n,
            "leaf_busy_ms": 1e3 * red["leaf_busy_s"] / n,
            "leaf_ms": 1e3 * red["leaf_s"] / n,
            "idle_ms": 1e3 * red["idle_s"] / n,
            "planes_ms": per(red["planes_s"]),
            "layers_ms": per(red["layers_s"]),
            "idle_by_span_ms": per(red["idle_by_span"])}
        sample = [(n, st) for name, lines in planes if tr._is_device(name)
                  for _, evs in lines for n, _, _, st in evs[:200]]
        out["op_stat_keys"] = sorted({k for _, st in sample for k in st})
        out["op_samples"] = [[n[:160], {k: str(v)[:160] for k, v in
                                        st.items()}] for n, st in sample[:8]]
        if args.fixture:
            Path(args.fixture).parent.mkdir(parents=True, exist_ok=True)
            with open(args.fixture, "w") as f:
                json.dump(fixture(planes, names), f, separators=(",", ":"))
    if args.out:
        o = Path(args.out)
        o.mkdir(parents=True, exist_ok=True)
        with open(path, "rb") as src, \
                gzip.open(o / "pass.xplane.pb.gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
        (o / "span_readings.json").write_text(json.dumps(out, indent=1))
        for tag, text in zip(("steady", "drain"), texts):
            with gzip.open(o / f"{tag}.hlo.txt.gz", "wt") as f:
                f.write(text)
    shutil.rmtree(run._trace_dir, ignore_errors=True)
    out.pop("passes")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
