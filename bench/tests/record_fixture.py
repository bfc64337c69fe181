#!/usr/bin/env python3
"""Record the small TPU trace that `test_trace_reduce.py` reads: inside
one "pass" span, three launches of a 512x512 jitted program, each after
a 2 ms "stage" span and followed by a 3 ms "wait" span. Needs a TPU.

    python bench/tests/record_fixture.py <out.xplane.pb>
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("no TPU visible", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    log_dir = tempfile.mkdtemp(prefix="fixture_trace_")
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation("pass"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("stage"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("launch"):
                x = f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("wait"):
                time.sleep(0.003)
    jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    shutil.copyfile(path, out)
    shutil.rmtree(log_dir, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
