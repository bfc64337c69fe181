"""The control of the ingest cell: the reference computed in bfloat16, put
in the program's place, has to fail the cell's limits. At the cell's own
stream (98,304 edges over reddit's ids, d = 602) the reference and the
control run on the CPU in seconds; on the chip they are read by
`readings.py`.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_control.py
"""
from __future__ import annotations

import tiny  # noqa: F401  (puts bench/ and src/ on the path)

import harness
import readings


def test_bf16_control_fails_the_ingest_limits():
    limits = harness.load_json(harness.BENCH / "limits" /
                               "reddit-hub-ingest.json")
    for seed in (4_000_000_021, 4_000_000_022):
        checks = readings.control_checks("reddit-hub-ingest", seed)
        assert any(v > limits[k] for k, v in checks.items()), checks
