"""The reduction from a profiler trace to busy time, top ops and named idle
gaps, on hand-made planes and on a small trace recorded on a TPU v5e
(`data/fixture.xplane.pb`: three launches of a 512x512 jitted program,
each after a 2 ms "stage" span, with 3 ms "wait" spans between them,
all inside one "pass" span).

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_trace_reduce.py
"""
from __future__ import annotations

from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import trace_reduce as tr

PHASES = ("pass", "launch", "stage", "wait")
FIXTURE = Path(__file__).with_name("data") / "fixture.xplane.pb"
MS = 1e6


def _planes(ops, host):
    return [("/host:CPU", [("python", host)]),
            ("/device:TPU:0", [("XLA Modules", [("jit_f", 0.0, 100 * MS)]),
                               ("XLA Ops", ops)])]


def test_busy_is_the_union_of_ops_inside_the_stretch():
    ops = [("dot", 10 * MS, 30 * MS), ("add", 20 * MS, 40 * MS),
           ("dot", 60 * MS, 70 * MS), ("late", 95 * MS, 120 * MS)]
    host = [("pass", 0.0, 100 * MS), ("stage", 40 * MS, 58 * MS),
            ("wait", 70 * MS, 95 * MS)]
    r = tr.reduce(_planes(ops, host), "pass", PHASES)
    assert r["window_s"] == pytest.approx(0.1)
    # [10, 40] + [60, 70] + [95, 100]: the late op is clipped to the pass
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["n_devices"] == 1
    assert r["device_ops"][0] == ["dot", pytest.approx(0.03)]
    gaps = dict((round(s, 6), n) for n, s in r["idle_gaps"])
    assert gaps == {0.025: "wait", 0.02: "stage", 0.01: "other"}


def test_no_stretch_or_no_device_op_reads_nothing():
    host = [("pass", 0.0, 100 * MS)]
    assert tr.reduce(_planes([], host), "pass", PHASES) is None
    assert tr.reduce(_planes([("dot", 0.0, MS)], []), "pass", PHASES) is None


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_tpu_trace():
    planes = tr.read_planes(str(FIXTURE))
    assert any(name.startswith("/device:TPU") for name, _ in planes)
    r = tr.reduce(planes, "pass", PHASES)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["n_devices"] == 1
    names = {n for n, _ in r["idle_gaps"]}
    assert "wait" in names
    assert all(s > 0 for _, s in r["device_ops"])
