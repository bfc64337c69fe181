"""The reduction of a profiler trace by the engine's names
(`span_reduce.py`): leaf device ops summed by plane scope, device idle
split by the innermost host span, on hand-made planes and on two
launches of a pass traced on a TPU v5e (`data/spans_fixture.json`,
written by `span_readings.py --fixture`).

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_span_reduce.py
"""
from __future__ import annotations

import json
from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import span_reduce as sr

FIXTURE = Path(__file__).with_name("data") / "spans_fixture.json"
MS = 1e6
LAYER0 = "jit(_super_tick_scan)/while/body/closed_call/d3.layer0/"


def _planes():
    """Two launches inside a 200 ms pass. The first runs a `while` that
    holds three body ops; an idle gap from 90 to 155 ms straddles the
    first launch's sync and harvest, the time between the launches and
    the second launch's staging, dispatch and sync."""
    host = [("pass", 0, 200), ("other", 1, 2),
            ("d3.launch", 0, 100), ("d3.stage", 0, 8),
            ("d3.stage.partition", 0, 5), ("d3.stage.pack", 5, 8),
            ("d3.dispatch", 8, 9), ("d3.sync", 9, 92),
            ("d3.harvest", 92, 100),
            ("d3.launch", 102, 200), ("d3.stage", 102, 150),
            ("d3.stage.partition", 102, 140), ("d3.stage.pack", 140, 150),
            ("d3.dispatch", 150, 152), ("d3.sync", 152, 190),
            ("d3.harvest", 190, 200)]
    ops = [("while.6", 10, 90, {}),
           ("fusion.1", 12, 20, {"tf_op": LAYER0 + "d3.round_a/scatter"}),
           ("%fusion.7 = f32[8,64]{1,0} fusion(%p.1)", 25, 40,
            {"long_name": "fusion.7"}),
           ("add.3", 45, 50, {"tf_op": "jit(_super_tick_scan)/add"}),
           ("fusion.2", 60, 62, {"tf_op": LAYER0 + "select_n"}),
           ("copy.3", 160, 170, {}),
           ("fusion.9", 170, 185,
            {"tf_op": "jit(_super_tick_scan)/while/body/d3.layer1/"
                      "d3.forward/dot_general"})]
    op_names = {"fusion.7": LAYER0 + "d3.deliver/scatter-add"}
    ms = lambda evs: [(n, s * MS, e * MS, *rest) for n, s, e, *rest in evs]
    planes = [("/host:CPU", [("python", ms([h + ({},) for h in host]))]),
              ("/device:TPU:0", [("XLA Modules", ms([("jit", 0, 200, {})])),
                                 ("XLA Ops", ms(ops))])]
    return planes, op_names


def test_leaf_ops_by_plane_and_layer():
    planes, op_names = _planes()
    r = sr.reduce(planes, "pass", op_names)
    assert r["window_s"] == pytest.approx(0.2)
    assert r["launches"] == 2 and r["n_devices"] == 1
    # the while is busy time, but not a leaf: its body ops are
    assert r["busy_s"] == pytest.approx(0.080 + 0.025)
    assert r["leaf_s"] == pytest.approx(0.008 + 0.015 + 0.005 + 0.002
                                        + 0.010 + 0.015)
    assert r["leaf_busy_s"] == pytest.approx(r["leaf_s"])
    assert r["planes_s"] == pytest.approx({
        "round_a": 0.008, "deliver": 0.015, "layer": 0.002,
        "forward": 0.015, "unscoped": 0.015})
    assert r["layers_s"] == pytest.approx({
        "layer0": 0.025, "layer1": 0.015, "-": 0.015})
    assert sum(r["planes_s"].values()) == pytest.approx(r["leaf_s"])


def test_idle_is_split_by_the_innermost_span():
    planes, op_names = _planes()
    r = sr.reduce(planes, "pass", op_names)
    assert r["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"])
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])
    # [0, 10]: staging, dispatch, sync; [90, 160]: the straddling gap;
    # [185, 200]: the second launch's sync and harvest
    assert r["idle_by_span"] == pytest.approx({
        "d3.stage.partition": 0.005 + 0.038, "d3.stage.pack": 0.003 + 0.010,
        "d3.dispatch": 0.001 + 0.002,
        "d3.sync": 0.001 + 0.002 + 0.008 + 0.005,
        "d3.harvest": 0.008 + 0.010, "none": 0.002})


def test_host_spans_count_total_and_self():
    planes, _ = _planes()
    hs = sr.reduce(planes, "pass")["host_spans"]
    assert hs["d3.launch"] == pytest.approx([2, 0.198, 0.0])
    assert hs["d3.stage"] == pytest.approx([2, 0.056, 0.0])
    assert hs["d3.sync"] == pytest.approx([2, 0.121, 0.121])
    assert "pass" not in hs and "other" not in hs


def test_op_scope_from_stats_then_from_the_program():
    assert sr.scope_of("x", {"tf_op": LAYER0 + "d3.forward/dot"}, {}) == \
        ("layer0", "forward")
    text = ('  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%c, '
            f'metadata={{op_name="{LAYER0}d3.round_b/gather" '
            'stack_frame_id=3}\n  %copy.1 = f32[8]{0} copy(%b)\n')
    names = sr.op_names_from_hlo(text)
    assert names == {"fusion.7": LAYER0 + "d3.round_b/gather"}
    assert sr.scope_of("%fusion.7 = f32[8]{0} fusion(%a)", {}, names) == \
        ("layer0", "round_b")
    assert sr.scope_of("copy.1", {}, names) == ()
    assert sr.plane_of(()) == "unscoped"
    assert sr.plane_of(("layer1",)) == "layer"


def test_drain_ops_are_named_by_the_drain_program():
    """Both programs name an instruction fusion.4; the op that starts
    inside the host's d3.drain span takes the drain program's scope."""
    host = [("pass", 0, 100, {}), ("d3.launch", 0, 40, {}),
            ("d3.drain", 50, 100, {}), ("d3.launch", 50, 100, {})]
    ops = [("fusion.4", 10, 20, {}), ("fusion.4", 60, 65, {})]
    planes = [("/host:CPU", [("python", host)]),
              ("/device:TPU:0", [("XLA Ops", ops)])]
    r = sr.reduce(planes, "pass", {"fusion.4": LAYER0 + "d3.round_a/add"},
                  {"fusion.4": LAYER0 + "d3.forward/add"})
    assert r["planes_s"] == pytest.approx({"round_a": 10e-9,
                                           "forward": 5e-9})
    # the drain's launch opens with it: the launch is the innermost
    assert r["idle_by_span"] == pytest.approx({"d3.launch": 75e-9,
                                               "none": 10e-9})


def test_no_stretch_or_no_device_op_reads_nothing():
    planes, _ = _planes()
    assert sr.reduce(planes, "nothing") is None
    assert sr.reduce([planes[0]], "pass") is None


def _fixture_planes(fx: dict) -> list:
    """Planes as `read_planes` gives them, each op's scope path put in
    its stats."""
    scopes, ops, t = fx["scopes"], [], 0.0
    for i, ds, d, k in fx["ops"]:
        t += ds
        ops.append((fx["instructions"][i], t, t + d,
                    {"tf_op": "jit/" + scopes[k]} if scopes[k] else {}))
    host = [(n, float(s), float(e), {}) for n, s, e in fx["spans"]]
    host.append(("window", 0.0, float(fx["window_ns"]), {}))
    return [("/host:CPU", [("python", host)]),
            ("/device:TPU:0", [("XLA Ops", ops)])]


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_tpu_launches():
    fx = json.loads(FIXTURE.read_text())
    r = sr.reduce(_fixture_planes(fx), "window")
    assert r["launches"] == 2
    # the scan's while holds its body: not a leaf, but busy
    assert any(n.startswith("while") for n in fx["instructions"])
    assert r["leaf_s"] < r["busy_s"] < r["window_s"]
    planes = r["planes_s"]
    for p in ("round_a", "round_b", "deliver", "forward", "topo", "sink",
              "query"):
        assert planes.get(p, 0.0) > 0, p
    assert sum(planes.values()) == pytest.approx(r["leaf_s"])
    assert sum(r["layers_s"].values()) == pytest.approx(r["leaf_s"])
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])
    assert r["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["idle_by_span"].get("d3.stage.partition", 0.0) > 0
    assert set(r["host_spans"]) >= {"d3.launch", "d3.stage", "d3.dispatch",
                                    "d3.sync", "d3.harvest"}
