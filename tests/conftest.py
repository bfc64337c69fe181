import os

# Tests run on the single real CPU device (the dry-run sets its own flags in
# a separate process). Keep XLA quiet and deterministic; optimization level
# 0 cuts compile time ~25% across the suite with identical semantics (the
# suite asserts numerics, never runtime perf).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_cpu_multi_thread_eigen=false "
                                   "--xla_backend_optimization_level=0")

import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]


def needs_devices(n: int):
    """Skip marker: the test needs >= n jax devices. The suite's default
    environment has ONE real CPU device; CI's forced-device lanes (and the
    subprocess smokes below) set
    XLA_FLAGS=--xla_force_host_platform_device_count=<n> so these tests run
    there in-process. Usage: `needs4 = needs_devices(4)` at module scope."""
    import jax
    return pytest.mark.skipif(
        len(jax.devices()) < n,
        reason=f"needs >={n} devices (CI lane forces an {n}-device "
               "CPU backend)")


def run_forced_devices(n: int, test_file, pytest_args=(), timeout=540):
    """Re-run `test_file` under pytest in a subprocess whose XLA backend is
    forced to n CPU devices — the shared smoke harness for multi-device
    suites on single-device machines (jax device count is fixed at backend
    init, so a fresh process is the only way to widen it mid-suite)."""
    env = {"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
           "HOME": os.environ.get("HOME", "/root"), "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={n} "
                        "--xla_backend_optimization_level=0"}
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         str(test_file)] + list(pytest_args),
        env=env, cwd=str(REPO), capture_output=True, text=True,
        timeout=timeout)


def load_chip_smoke():
    """Import the repo-root `chip_smoke.py` script as a module (once)."""
    import importlib.util
    if "chip_smoke" not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", REPO / "chip_smoke.py")
        module = importlib.util.module_from_spec(spec)
        sys.modules["chip_smoke"] = module
        spec.loader.exec_module(module)
    return sys.modules["chip_smoke"]


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


# ------------------------------------------------------- shared pipelines
# Building + streaming + flushing a pipeline costs seconds (jit compiles
# dominate); read-only assertions share ONE session-scoped instance instead
# of rebuilding per test. Tests that mutate pipeline state must build their
# own via the factories inside each test module.

@pytest.fixture(scope="session")
def stream_case():
    """The canonical small stream (seed 0): 60 nodes, ~200 edges, d_in 8."""
    rng = np.random.default_rng(0)
    n_nodes, n_edges, d_in = 60, 200, 8
    edges = np.stack([rng.integers(0, n_nodes, n_edges),
                      rng.integers(0, n_nodes, n_edges)], 1)
    edges = edges[edges[:, 0] != edges[:, 1]]
    feats = {v: rng.normal(size=d_in).astype(np.float32)
             for v in range(n_nodes)}
    return SimpleNamespace(edges=edges, feats=feats,
                           n_nodes=n_nodes, d_in=d_in)


def _build_pipe(case, window):
    import jax
    from repro.core.pipeline import D3Pipeline, PipelineConfig
    from repro.graph.sage import GraphSAGE
    model = GraphSAGE((case.d_in, 16, 16))
    params = model.init(jax.random.key(0))
    cfg = PipelineConfig(n_parts=4, node_cap=64, edge_cap=256, repl_cap=256,
                         feat_cap=512, edge_tick_cap=64,
                         max_nodes=case.n_nodes, window=window)
    return model, params, D3Pipeline(model, params, cfg)


@pytest.fixture(scope="session")
def streamed_pipeline(stream_case):
    """stream_case fully streamed (per-tick driver) + flushed, STREAMING
    policy. READ-ONLY: do not tick or mutate it."""
    from repro.core import windowing as win
    model, params, pipe = _build_pipe(
        stream_case, win.WindowConfig(kind=win.STREAMING))
    pipe.run_stream(stream_case.edges, stream_case.feats, tick_edges=32)
    pipe.flush(max_ticks=128)
    return SimpleNamespace(model=model, params=params, pipe=pipe,
                           case=stream_case)


@pytest.fixture(scope="session")
def super_streamed_pipeline(stream_case):
    """Same stream driven by the super-tick driver. READ-ONLY."""
    from repro.core import windowing as win
    model, params, pipe = _build_pipe(
        stream_case, win.WindowConfig(kind=win.STREAMING))
    pipe.run_stream_super(stream_case.edges, stream_case.feats,
                          tick_edges=32, super_ticks=4)
    pipe.flush_super(max_ticks=128, T=4)
    return SimpleNamespace(model=model, params=params, pipe=pipe,
                           case=stream_case)
