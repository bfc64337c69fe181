"""One run of one cell: load, warm up, measure, check, print one line.

Everything is found by name. `BENCHMARK.json` names the cell's
configuration (the file its `configs` entry gives, under
`bench/configs/`) and traffic (`bench/traffic/<traffic>.json`, whose
`driver` names the module under `bench/drivers/` that runs it); the
correctness limits of the cell are `bench/limits/<cell>.json`, and each
metric is read from the run's record by `bench/metrics/<metric>.py`. A
later cell, traffic mix or metric is a new file, and no file here
changes.

The run's record is a dict that the driver fills: `setup_s`, the
driver's own measurements, `peak` (the chip's row of `peaks.json`),
`trace` (the reduced profiler trace, `--trace 1`), and `checks`, the
numbers compared with the reference, each beside its limit.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
# the host phases a traced run annotates; `trace_reduce.reduce` names
# idle gaps by them
PHASES = ("generate", "rezero", "pass", "chunk", "launch", "stage",
          "flush", "readback")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Run:
    """What a driver is given, and the record it fills."""

    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool, t_start: float):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t_start = t_start
        self.rec: dict = {"checks": {}}
        self.compiles = 0
        self.stage_s = 0.0          # host staging, summed over launches
        self.launches = 0
        self._trace_dir = None

    # ------------------------------------------------------------ timing
    @contextlib.contextmanager
    def phase(self, name: str):
        """A host phase; a TraceAnnotation in a traced run."""
        if not self.trace:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield

    def setup_done(self) -> None:
        """Set-up ends where the first timed launch begins."""
        self.rec["setup_s"] = time.perf_counter() - self.t_start

    def start_trace(self) -> None:
        import jax
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self._trace_dir)

    def stop_trace(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def reduce_trace(self, stretch: str) -> None:
        """Keep the reduction of the first span named `stretch` of the
        trace taken; read after the window, so that parsing it costs the
        window nothing."""
        import shutil
        import trace_reduce as tr
        try:
            planes = tr.read_planes(tr.newest_xplane(self._trace_dir))
            self.rec["trace"] = tr.reduce(planes, stretch, PHASES)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)

    # ------------------------------------------------------------ engine
    def dims(self) -> tuple:
        c = self.config
        return ((c["in_dim"],) + (c["hidden_dim"],) * (c["num_layers"] - 1)
                + (c["out_dim"],))

    def build_model(self) -> None:
        """The model and its weights, drawn from the seed on the device in
        one jitted call, in float32 as the engine serves them."""
        import jax
        import jax.numpy as jnp
        from streams import WEIGHTS, sub_seed
        from repro.graph.sage import GraphSAGE

        dims = self.dims()

        def init(key):
            params = {}
            for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
                k1, k2, k3, key = jax.random.split(key, 4)
                s = 1.0 / jnp.sqrt(jnp.float32(a))
                params[f"l{i}"] = {
                    "self": {"w": s * jax.random.normal(k1, (a, b)),
                             "b": 0.1 * jax.random.normal(k2, (b,))},
                    "neigh": {"w": s * jax.random.normal(k3, (a, b))}}
            return params

        self.model = GraphSAGE(dims)
        self.params = jax.jit(init)(
            jax.random.key(sub_seed(self.seed, WEIGHTS)))
        jax.block_until_ready(self.params)

    def pipeline_config(self):
        from repro.core import windowing as win
        from repro.core.pipeline import PipelineConfig
        from streams import PARTITIONER, sub_seed

        p = dict(self.config["pipeline"])
        p["window"] = win.WindowConfig(**p["window"])
        return PipelineConfig(seed=sub_seed(self.seed, PARTITIONER), **p)

    def new_session(self):
        """A freshly zeroed pipeline in a serving session; its host
        staging is timed and counted as launches."""
        import jax
        from repro.core.pipeline import D3Pipeline
        from repro.serve.session import ServeSession

        pipe = D3Pipeline(self.model, self.params, self.pipeline_config())
        stage = pipe._stage_super_batches

        def timed_stage(*a, **k):
            t = time.perf_counter()
            with self.phase("stage"):
                out = stage(*a, **k)
            self.stage_s += time.perf_counter() - t
            self.launches += 1
            return out

        pipe._stage_super_batches = timed_stage
        jax.block_until_ready(pipe.states)
        return ServeSession(pipe, driver="super",
                            super_ticks=self.config["T"],
                            max_retained=2 ** 22)


_RUNS: list = []          # the run the compile listener counts for


def _listen(run: Run) -> None:
    """Count every trip to the compiler (a persistent-cache hit included)
    against `run`; the listener is registered once per process."""
    import jax

    if not _RUNS:
        def count(event, duration, **kw):
            if event == COMPILE_EVENT:
                _RUNS[-1].compiles += 1
        jax.monitoring.register_event_duration_secs_listener(count)
    _RUNS.append(run)


def _device(devices) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def selected_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics (--trace 0) or per-layer metrics
    (--trace 1): those whose `workloads` lists it, or that have none."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in bench[key] if cell in m.get("workloads", [cell])]


def main(argv=None, require_tpu: bool = True, t_start: float = None,
         shrink=None, cache: bool = True) -> int:
    """One run. The keywords serve the tests under bench/tests: they run
    the harness on the CPU (`require_tpu=False`) at sizes cut by
    `shrink(config, traffic) -> (config, traffic)`, without the persistent
    cache (`cache=False`)."""
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        log(f"no workload named {args.workload!r} in BENCHMARK.json")
        return 2
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == cell["config"])
    config = load_json(ROOT / conf_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{cell['name']}.json")
    if shrink is not None:
        config, traffic = shrink(config, traffic)

    for p in (BENCH, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    import jax
    if cache:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    else:
        jax.config.update("jax_enable_compilation_cache", False)

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        log(f"no TPU visible: jax found {devices[0].platform}")
        return 2
    if len(devices) < cell["chips"]:
        log(f"the cell needs {cell['chips']} chips, {len(devices)} visible")
        return 2
    log(f"device: {devices[0].platform} {devices[0].device_kind!r} "
        f"x{len(devices)}; jax {jax.__version__}; cache {CACHE_DIR}")
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if require_tpu and devices[0].device_kind not in peaks:
        raise KeyError(f"{devices[0].device_kind!r} is not in "
                       "bench/peaks.json")

    run = Run(cell, config, traffic, args.seed, args.seconds,
              bool(args.trace), t_start)
    run.rec["peak"] = peaks.get(devices[0].device_kind)
    _listen(run)
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    run.build_model()
    try:
        driver.measure(run)      # frees the engine's state before it returns
        log(f"compilations inside the window: "
            f"{run.rec['compiles_in_window']}")
        device = _device(devices)
        gc.collect()
        driver.check(run)
    except Exception:            # a run that breaks is not correct
        import traceback
        log(traceback.format_exc())
        device = _device(devices)
        run.rec.update(checks={"run_broke": 1}, attempted=0, failed=0)

    rec = run.rec
    if args.trace and rec.get("trace"):
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
    metrics = {}
    for m in selected_metrics(bench, cell["name"], bool(args.trace)):
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = {}
    limits["run_broke"] = 0
    for name, value in rec["checks"].items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in "
                           f"bench/limits/{cell['name']}.json")
        checks[name] = {"value": value, "limit": limits[name]}
    correct = bool(checks) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    out = {"correct": correct, "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    if device.get("busy_s") is not None:
        out["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                            "idle_gaps": rec["trace"]["idle_gaps"]}
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0
