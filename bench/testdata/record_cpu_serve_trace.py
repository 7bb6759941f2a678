"""Record ``cpu_serve_trace.xplane.pb``, the small serving trace that
``tests/test_bench_spans.py`` reads.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/testdata/record_cpu_serve_trace.py

A ``ClusterEngine`` over a model fit on 300 rows, warmed up before the
trace starts. Under a ``serve`` annotation: six rounds of a 20 ms sleep
inside ``loadgen.wait``, then one to three requests of 10–150 rows and
``step()`` calls inside ``engine.step`` until they are answered, so some
batches take the first rows of several requests and some take none. On the
CPU the XLA ops run on the ``tf_XLA...`` thread lines of the host plane,
which the test treats as the device. The profiler's Python tracer and its
HLO protos are off to keep the file small.
"""
import glob
import os
import shutil
import tempfile
import time

import jax
from jax.profiler import TraceAnnotation

from repro.core import SCRBConfig, SCRBModel, SolverOptions
from repro.data.synthetic import make_blobs
from repro.serve.cluster_engine import ClusterEngine, EngineConfig

ROUNDS = ((10,), (40, 150), (64,), (5, 7, 9), (130,), (20, 30))


def main() -> None:
    x, _ = make_blobs(300, 6, 4, seed=0)
    model = SCRBModel.fit(x, SCRBConfig(
        n_clusters=4, n_grids=16, sigma=1.5, d_g=128, kmeans_replicates=1,
        seed=0, solver_options=SolverOptions(tol=1e-2)))
    engine = ClusterEngine(EngineConfig(buckets=(32, 64)))
    engine.load_model("m", model)
    engine.warmup("m")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=options)
    with TraceAnnotation("serve"):
        for sizes in ROUNDS:
            with TraceAnnotation("loadgen.wait"):
                time.sleep(0.02)
            tickets = [engine.submit("m", x[:n]) for n in sizes]
            while engine.stats()["pending"]:
                with TraceAnnotation("engine.step"):
                    engine.step()
            for t in tickets:
                engine.take(t)
    jax.profiler.stop_trace()
    src, = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    dst = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "cpu_serve_trace.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print(dst, os.path.getsize(dst))


if __name__ == "__main__":
    main()
