"""The benchmark's four-chip cell, `products-mesh4-ingest` (bench/), on the
CPU: its configuration, the reference it is held to, its metric readers,
and whole harness runs on a 4-device mesh.

The suite's own process has one CPU device, so the mesh runs happen in
ONE child process forced to four (`python tests/test_bench_mesh4.py`).
It runs, at the tiny sizes of `bench/tests/tiny.py`:

  * the harness on the cell, untraced and traced, sound, with nothing
    compiled inside the window; the traced run with a window of 0 s,
    which its traced pass outlasts, as a full pass outlasts the chip's;
  * the harness with each fault of `bench/tests/test_faults.py`, and with
    the exchange left out (every device keeps what it would send);
  * one pass of the cell's 3-layer stack on MeshRouter and on
    LocalRouter, whose sink rows are compared with each other and with
    `bench/reference.py`;
  * the lowering of the mesh program, whose ops are put into planes by
    `bench/span_reduce.py`;

and prints one JSON object that the tests below read.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
BENCH = REPO / "bench"
for _p in (BENCH / "tests", BENCH, REPO / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import harness  # noqa: E402

CELL = "products-mesh4-ingest"
SEED = 3_000_000_019
READERS = ("wire_ms.mesh4", "wire_mb.mesh4", "chip_skew.mesh4",
           "step_mfu.mesh4")


def _cell():
    bench = harness.load_json(REPO / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = harness.load_json(REPO / entry["file"])
    traffic = harness.load_json(BENCH / "traffic" /
                                f"{cell['traffic']}.json")
    return cell, config, traffic


# ------------------------------------------------------------ the child

def _harness(trace=0, seconds=0.5):
    """One harness run of the cell, cut to a tiny size; its result line."""
    import contextlib
    import io
    import tiny

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(["--workload", CELL, "--seed", str(SEED),
                           "--seconds", str(seconds), "--trace", str(trace)],
                          require_tpu=False, shrink=tiny.shrink, cache=False)
    assert rc == 0, rc
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    rec = harness._RUNS[-1].rec
    line.update(passes=len(rec["passes"]),
                traced_passes=[p["traced"] for p in rec["passes"]],
                compiles_in_window=rec["compiles_in_window"],
                drains=[w["drain_launches"] for w in rec["wire"]])
    return line


def _no_exchange(orig):
    """The all_to_all left out: each device receives what it sent."""
    def all_to_all(x, axis_name, split_axis, concat_axis, tiled=False):
        return x
    return all_to_all


def _tiny_run():
    """The cell cut to tiny sizes, its model built; and its driver."""
    import tiny

    cell, config, traffic = _cell()
    config, traffic = tiny.shrink(config, traffic)
    run = harness.Run(cell, config, traffic, SEED, 0.0, False, 0.0)
    run.build_model()
    return run, harness.load_module(BENCH / "drivers" / "mesh_passes.py")


def _sink_rows():
    """One pass of the tiny cell on LocalRouter and on MeshRouter, read
    back and held to the reference."""
    import reference
    from sink import read_rows, rows_numbers
    from repro.launch.mesh import make_stream_mesh

    run, driver = _tiny_run()
    edges, feats = driver.stream(run)
    ids = np.asarray(sorted(feats), np.int64)
    ref = reference.reference(run.params,
                              reference.snapshot(edges, feats))
    local = harness.Run.new_session(run)
    mesh = driver._mesh_session(run, make_stream_mesh(4), [])
    out = {"layers": len(run.model.layers), "dims": list(run.dims())}
    for name, session in (("local", local), ("mesh", mesh)):
        driver.passes._one_pass(run, session, edges, feats)
        rows = read_rows(session.pipe, ids)
        out[name] = rows[0].tolist()
        out[name + "_gap"], _, out[name + "_missing"] = rows_numbers(
            ref, [rows])
    return out


def _planes():
    """Plane of every op of the mesh program's compiled text, by the
    reduction's own reading of its op names: {plane: [opcodes]}."""
    import re
    import span_reduce as sr
    from repro.launch.mesh import make_stream_mesh

    run, driver = _tiny_run()
    span_readings = harness.load_module(BENCH / "tests" /
                                        "span_readings.py")
    session = driver._mesh_session(run, make_stream_mesh(4), [])
    text = span_readings.program_texts(run, session.pipe)[0]
    names = sr.op_names_from_hlo(text)
    out = {}
    for instr, opcode in re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = "
                                    r"(?:\([^)]*\)|\S+) ([\w\-]+)\(",
                                    text, re.M):
        plane = sr.plane_of(sr.scope_of(instr, {}, names))
        out.setdefault(plane, []).append(opcode)
    return out


def child() -> None:
    """Everything the tests read from a 4-device mesh, as one JSON line."""
    import jax
    import test_faults
    from repro.dist import router

    assert len(jax.devices()) == 4, jax.devices()
    out = {"sound": _harness(seconds=5),
           "traced": _harness(trace=1, seconds=0), "faults": {}}
    faults = dict(test_faults.FAULTS,
                  no_exchange=(router.lax, "all_to_all", _no_exchange))
    for name, (owner, attr, make) in sorted(faults.items()):
        jax.clear_caches()            # the fault must be traced anew
        with test_faults.patched(owner, attr, make):
            out["faults"][name] = _harness()["correct"]
    jax.clear_caches()
    out["sink"] = _sink_rows()
    out["planes"] = _planes()
    print(json.dumps(out))


@pytest.fixture(scope="module")
def mesh4():
    env = {"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
           "HOME": os.environ.get("HOME", str(REPO)),
           "TMPDIR": os.environ.get("TMPDIR", "/tmp"),
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4 "
                        "--xla_backend_optimization_level=0"}
    r = subprocess.run([sys.executable, __file__], env=env, cwd=str(REPO),
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-5000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


# ------------------------------------------------- on a 4-device mesh

def test_mesh4_cell_runs_correct(mesh4):
    for key in ("sound", "traced"):
        out = mesh4[key]
        assert out["correct"], out["checks"]
        assert out["device"]["count"] == 4
        assert out["checks"]["rows_missing"]["value"] == 0
    assert "edges_per_s" in mesh4["sound"]["metrics"]
    # a re-zeroed session, and every launch after a pipeline's first,
    # run what the warm-up compiled
    assert mesh4["sound"]["passes"] >= 2
    assert mesh4["sound"]["compiles_in_window"] == 0
    # every pass records its drain, which ends long before flush's cap
    # of 128 ticks (32 launches of T = 4)
    assert len(mesh4["sound"]["drains"]) == mesh4["sound"]["passes"]
    assert 1 <= max(mesh4["sound"]["drains"]) <= 4
    # no device plane on the CPU: of the cell's own readers only the
    # program's counter reads something
    assert mesh4["traced"]["metrics"]["wire_mb.mesh4"]["value"] > 0


def test_mesh4_traced_window_holds_an_untraced_pass(mesh4):
    """The window does not end with the traced pass, so a traced run
    reads host staging from an untraced pass even where the traced one
    outlasts `--seconds`."""
    traced = mesh4["traced"]
    assert traced["traced_passes"] == [True, False]
    assert traced["metrics"]["stage_ms.ingest"]["value"] > 0


@pytest.mark.parametrize("fault", ["altered_sink", "half_batch",
                                   "no_exchange", "state_unchanged"])
def test_mesh4_fault_is_caught(mesh4, fault):
    assert mesh4["faults"][fault] is False


def test_mesh4_sink_matches_local_and_reference(mesh4):
    s = mesh4["sink"]
    assert s["layers"] == 3 and len(s["dims"]) == 4
    np.testing.assert_allclose(np.asarray(s["mesh"]), np.asarray(s["local"]),
                               rtol=1e-5, atol=1e-6)
    limits = harness.load_json(BENCH / "limits" / f"{CELL}.json")
    for key in ("local", "mesh"):
        assert s[key + "_missing"] == 0
        assert s[key + "_gap"] <= limits["rows_gap"]


def test_mesh4_all_to_all_is_under_the_wire_plane(mesh4):
    planes = mesh4["planes"]
    assert "all-to-all" in planes.get("wire", [])
    assert all("all-to-all" not in ops for p, ops in planes.items()
               if p != "wire")
    # packing (route_plan's sort, route_pack's scatter) stays with route
    assert not {"sort", "scatter", "gather"} & set(planes["wire"])
    assert {"scatter", "gather"} <= set(planes.get("route", []))


# ------------------------------------------------------- in this process

def test_products_config_is_ogb_products_sage():
    """The harness builds OGB's products GraphSAGE from the configuration:
    100 -> 256 -> 256 -> 47, relu on all but the last layer, a self and
    a neighbour matrix per layer, 206,895 parameters."""
    import jax

    cell, config, traffic = _cell()
    assert cell["chips"] == 4
    assert config["num_nodes"] == config["pipeline"]["max_nodes"] == 2449029
    run = harness.Run(cell, config, traffic, SEED, 0.0, False, 0.0)
    assert run.dims() == (100, 256, 256, 47)
    run.build_model()
    assert [l.act for l in run.model.layers] == [True, True, False]
    shapes = {k: jax.tree.map(np.shape, v) for k, v in run.params.items()}
    assert shapes == {
        f"l{i}": {"self": {"w": (a, b), "b": (b,)}, "neigh": {"w": (a, b)}}
        for i, (a, b) in enumerate([(100, 256), (256, 256), (256, 47)])}
    assert sum(np.size(x) for x in jax.tree.leaves(run.params)) == 206895


def test_products_caps_hold_a_pass():
    """HDRF places the cell's whole stream within the configured caps
    (the partitioner raises on a full part)."""
    import streams
    from repro.core.partitioner import StreamingPartitioner

    cell, config, traffic = _cell()
    s, p = traffic["stream"], config["pipeline"]
    edges = streams.edge_stream(config["num_nodes"], s["n_edges"],
                                s["alpha"], s["structure_seed"], SEED)
    part = StreamingPartitioner(
        p["n_parts"], p["max_nodes"], method=p["partitioner"],
        seed=streams.sub_seed(SEED, streams.PARTITIONER),
        node_cap=p["node_cap"], edge_cap=p["edge_cap"],
        repl_cap=p["repl_cap"])
    tick = config["tick_edges"]
    for lo in range(0, len(edges), tick):
        part.ingest_edges(edges[lo: lo + tick])
    assert part.t.next_eslot.sum() == s["n_edges"]


def test_products_outbox_holds_the_emission_demand():
    """The configured outbox holds every part's emission demand, so no
    layer falls behind while the stream is ingested: the telemetry
    plane's per-part demand over the cell's first 6 launches stays
    within `outbox_cap / n_parts` and already exceeds the default
    (`feat_cap / n_parts`), under which the deeper layers' backlog grows
    for the rest of the pass and is left to the drain. Which vertices
    emit does not depend on the widths, so they are cut to 8."""
    cell, config, traffic = _cell()
    config["pipeline"]["telemetry"] = True
    config.update(in_dim=8, hidden_dim=8, out_dim=8)
    T, tick = config["T"], config["tick_edges"]
    traffic["stream"]["n_edges"] = 6 * T * tick
    run = harness.Run(cell, config, traffic, SEED, 0.0, False, 0.0)
    run.build_model()
    edges, feats = harness.load_module(
        BENCH / "drivers" / "passes.py").stream(run)
    pipe = harness.Run.new_session(run).pipe
    e_chunks, f_chunks = pipe.chunk_stream(edges, feats, tick)
    for lo in range(0, len(e_chunks), T):
        pipe.run_super_tick(e_chunks[lo: lo + T], f_chunks[lo: lo + T], T=T)
    cols = pipe.trace.columns()
    p = config["pipeline"]
    assert len(cols["dropped"]) == 6 * T
    assert int(cols["dropped"].sum()) == 0
    demand = int(cols["outbox_part_peak"].max())
    assert p["feat_cap"] // p["n_parts"] < demand
    assert demand <= p["outbox_cap"] // p["n_parts"]


def test_reference_covers_three_layers_and_logits():
    """`bench/reference.py` at the cell's depth and output width: a plain
    numpy SAGE-mean stack gives the same rows, and the 47 logits keep
    their sign (no relu on the last layer)."""
    import reference

    rng = np.random.default_rng(0)
    dims = (12, 16, 16, 47)
    params = {f"l{i}": {"self": {"w": rng.normal(size=(a, b)) / np.sqrt(a),
                                 "b": 0.1 * rng.normal(size=b)},
                        "neigh": {"w": rng.normal(size=(a, b)) / np.sqrt(a)}}
              for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))}
    params = {k: {kk: {n: x.astype(np.float32) for n, x in vv.items()}
                  for kk, vv in v.items()} for k, v in params.items()}
    edges = rng.integers(0, 40, size=(300, 2)).astype(np.int32)
    feats = {v: rng.normal(size=dims[0]).astype(np.float32)
             for v in range(0, 40, 2)}
    snap = reference.snapshot(edges, feats)

    x = snap.x.astype(np.float64)
    n = len(x)
    for i in range(3):
        p = params[f"l{i}"]
        agg = np.zeros_like(x)
        cnt = np.zeros(n)
        np.add.at(agg, snap.receivers, x[snap.senders])
        np.add.at(cnt, snap.receivers, 1.0)
        agg /= np.maximum(cnt, 1.0)[:, None]
        h = x @ p["self"]["w"] + p["self"]["b"] + agg @ p["neigh"]["w"]
        x = np.maximum(h, 0.0) if i < 2 else h
    got = reference.forward(params, snap)
    assert got.shape == (n, 47)
    np.testing.assert_allclose(got, x, rtol=1e-5, atol=1e-5)
    assert (got < 0).any()
    np.testing.assert_allclose(reference.reference(params, snap).ref, got,
                               rtol=1e-6, atol=1e-6)


def _record():
    """A mesh run's record as the driver and the harness leave it: two
    timed passes of 35 launches, the first traced."""
    return {
        "passes": [{"edges": 262144, "seconds": 12.0, "stage_s": 6.0,
                    "launches": 35, "traced": True},
                   {"edges": 262144, "seconds": 10.0, "stage_s": 5.0,
                    "launches": 35, "traced": False}],
        "trace": {"busy_s": 7.0, "window_s": 12.0, "n_devices": 4},
        "peak": {"bf16_flops": 197e12},
        "flops_per_pass": 1e13,
        "spans": {"all": {"busy_s": 7.0,
                          "planes_s": {"route": 1.0, "wire": 0.7}},
                  "chips": [{"busy_s": 6.0}, {"busy_s": 8.0},
                            {"busy_s": 7.0}, {"busy_s": 7.0}]},
        "wire": [{"wire_bytes": 35 * 8_000_000, "launches": 35,
                  "traced": True},
                 {"wire_bytes": 35 * 8_000_000, "launches": 35,
                  "traced": False}]}


EXPECTED = {"wire_ms.mesh4": 1e3 * 0.7 / 35,
            "wire_mb.mesh4": 8.0,
            "chip_skew.mesh4": 8.0 / (28.0 / 4),
            "step_mfu.mesh4": 100 * 1e13 / (7.0 * 4 * 197e12)}
# what each reader reads: without it, it reads nothing
INPUT = {"wire_ms.mesh4": "spans", "wire_mb.mesh4": "wire",
         "chip_skew.mesh4": "spans", "step_mfu.mesh4": "trace"}


def _reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read


@pytest.mark.parametrize("name", READERS)
def test_mesh4_reader_reads_a_record(name):
    assert _reader(name)(_record()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_mesh4_reader_without_its_input_is_none(name):
    rec = _record()
    del rec[INPUT[name]]
    assert _reader(name)(rec) is None
    assert _reader(name)({}) is None


def test_wire_ms_is_none_without_a_wire_scope():
    """A program with no `d3.wire` scope (the engine before it) gives no
    plane `wire`: the reader reads nothing there, not 0."""
    rec = _record()
    del rec["spans"]["all"]["planes_s"]["wire"]
    assert _reader("wire_ms.mesh4")(rec) is None


if __name__ == "__main__":
    child()
