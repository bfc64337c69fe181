"""`chip_smoke.py` rehearsed on the CPU, so the script cannot rot between
chip runs, and the compile-cache placement it relies on."""
import json

import jax
import pytest

from conftest import REPO, load_chip_smoke


@pytest.fixture
def cache_config(monkeypatch):
    """Restore JAX's persistent-cache settings after the test."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    yield monkeypatch
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_rehearsal_runs_end_to_end(cache_config, tmp_path, capsys):
    """--rehearse drives the whole script — xla and pallas phases, the
    oracle bar, every query answered — and reports the CPU."""
    cache_config.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert load_chip_smoke().main(["--rehearse"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert f"compile cache: {tmp_path}" in out
    assert any(l.startswith("[pallas vs xla]") for l in out)
    last = json.loads(out[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": 1}}


def test_without_tpu_exits_nonzero_with_no_result(cache_config, tmp_path,
                                                  capsys):
    cache_config.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert load_chip_smoke().main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err


def test_compile_cache_placement(cache_config):
    """JAX_COMPILATION_CACHE_DIR wins and the code sets no other;
    without it the cache goes to the fixed in-checkout directory."""
    from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache

    assert DEFAULT_DIR == REPO / ".jax_cache"
    cache_config.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/elsewhere")
    jax.config.update("jax_compilation_cache_dir", None)
    assert enable_compile_cache() == "/placed/elsewhere"
    assert jax.config.jax_compilation_cache_dir is None
    cache_config.delenv("JAX_COMPILATION_CACHE_DIR")
    assert enable_compile_cache() == str(DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)


def test_importing_the_library_places_no_cache_and_warns_nothing():
    """Only entry points place the cache: a fresh interpreter that imports
    the engine, the session and the serving CLI has none — and raises no
    DeprecationWarning doing so (jax.shard_map, not the experimental
    module)."""
    import subprocess
    import sys

    code = ("import jax, repro.core.pipeline, repro.serve.session, "
            "repro.launch.serve; "
            "print(jax.config.jax_compilation_cache_dir)")
    env = {"PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": "/usr/bin:/bin"}
    out = subprocess.run([sys.executable, "-W", "error::DeprecationWarning",
                          "-c", code], env=env, capture_output=True,
                         text=True, timeout=120, check=True)
    assert out.stdout.strip() == "None"
