"""Record ``cpu_trace.xplane.pb``, the small trace that
``tests/test_bench_trace_reduce.py`` reduces.

    JAX_PLATFORMS=cpu python bench/testdata/record_cpu_trace.py

Under a ``fit`` annotation: three calls of a jitted matmul, a 50 ms sleep
inside ``loadgen.wait``, then two calls of ``bench_probe`` inside
``dispatch_probe``. On the CPU the XLA ops run on the ``tf_XLA...`` thread
lines of the host plane, which the test treats as the device.
"""
import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


def main() -> None:
    @jax.jit
    def step(a):
        return jnp.tanh(a @ a).sum()

    @jax.jit
    def bench_probe(a):
        return (a * 2.0 + 1.0).sum()

    a = jnp.ones((384, 384), jnp.float32)
    jax.block_until_ready((step(a), bench_probe(a)))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("fit"):
        for _ in range(3):
            jax.block_until_ready(step(a))
        with TraceAnnotation("loadgen.wait"):
            time.sleep(0.05)
        with TraceAnnotation("dispatch_probe"):
            for _ in range(2):
                jax.block_until_ready(bench_probe(a))
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    dst = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "cpu_trace.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print(dst, os.path.getsize(dst))


if __name__ == "__main__":
    main()
