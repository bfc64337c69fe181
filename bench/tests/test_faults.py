"""A whole harness run on the CPU at tiny sizes (the look for a chip
skipped), with the timed path broken underneath: `correct` has to come
out false for every fault a cell can have, and true without one.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_faults.py

The cell runs on one chip, so there is no exchange between chips to
leave out, and serves no queries, so there is no answer to alter.
"""
from __future__ import annotations

import contextlib

import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)

from repro.core import pipeline
from repro.core.pipeline import D3Pipeline

CELL = "reddit-hub-ingest"


def _run(capsys, seconds=0.5):
    return tiny.run(CELL, seconds=seconds, capsys=capsys, cache=False)


@contextlib.contextmanager
def patched(owner, name, make):
    """Replace owner.<name> by make(original) for the run."""
    orig = getattr(owner, name)
    setattr(owner, name, make(orig))
    try:
        yield
    finally:
        setattr(owner, name, orig)


def state_unchanged(orig):
    """A launch computes its stats and answers, and hands back the state
    it was given (its clock and quiet counter moved on)."""
    import dataclasses
    import jax
    import jax.numpy as jnp

    def scan(layers, params, carry, batches, *a, **k):
        final, *rest = orig(layers, params, jax.tree.map(jnp.copy, carry),
                            batches, *a, **k)
        return (dataclasses.replace(carry, now=final.now,
                                    quiet=final.quiet), *rest)
    return scan


def half_batch(orig):
    """Every micro-tick's edges lose their second half."""
    def run_super_tick(self, edge_chunks=None, *a, **k):
        if edge_chunks is not None:
            edge_chunks = [None if c is None else c[: (len(c) + 1) // 2]
                           for c in edge_chunks]
        return orig(self, edge_chunks, *a, **k)
    return run_super_tick


def altered_sink(orig):
    """The sink a launch produces has one entry moved by 1."""
    def run_super_tick(self, *a, **k):
        out = orig(self, *a, **k)
        self.sink = self.sink.at[0, 0, 0].add(1.0)
        return out
    return run_super_tick


FAULTS = {"state_unchanged": (pipeline, "_super_tick_scan", state_unchanged),
          "half_batch": (D3Pipeline, "run_super_tick", half_batch),
          "altered_sink": (D3Pipeline, "run_super_tick", altered_sink)}


def test_sound_run_is_correct(capsys):
    out = _run(capsys)
    assert out["correct"], out["checks"]
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(fault, capsys):
    owner, name, make = FAULTS[fault]
    with patched(owner, name, make):
        out = _run(capsys)
    assert not out["correct"], out["checks"]


def test_no_tpu_no_result(capsys):
    import harness

    rc = harness.main(["--workload", "reddit-hub-ingest", "--seed", "1",
                       "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""
