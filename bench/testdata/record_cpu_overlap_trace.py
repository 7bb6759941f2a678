"""Record ``cpu_overlap_trace.xplane.pb``, a small serving trace of an engine
that dispatches the next batch while the previous one is in flight, which
``tests/test_bench_overlap.py`` reads.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/testdata/record_cpu_overlap_trace.py

As ``record_cpu_serve_trace.py`` (a model fit on 300 rows, warmed up before
the trace starts, rounds of a 20 ms sleep inside ``loadgen.wait`` and then
``step()`` calls inside ``engine.step`` until the round's requests are
answered, all under a ``serve`` annotation), but with 512-row buckets and
requests of up to 2,000 rows, so that a round holds several batches and the
engine dispatches a batch whenever the one before it is not yet ready. Its
``engine.dispatch`` spans carry the ``overlapped`` attribute.
"""
import glob
import os
import shutil
import tempfile
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import SCRBConfig, SCRBModel, SolverOptions
from repro.data.synthetic import make_blobs
from repro.serve.cluster_engine import ClusterEngine, EngineConfig

ROUNDS = ((10,), (2000,), (64, 700), (5, 7, 9), (1500, 30), (20,))


def main() -> None:
    x, _ = make_blobs(300, 6, 4, seed=0)
    model = SCRBModel.fit(x, SCRBConfig(
        n_clusters=4, n_grids=16, sigma=1.5, d_g=128, kmeans_replicates=1,
        seed=0, solver_options=SolverOptions(tol=1e-2)))
    rows = np.tile(x, (7, 1))
    engine = ClusterEngine(EngineConfig(buckets=(64, 512)))
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
            tickets = [engine.submit("m", rows[:n]) for n in sizes]
            while engine.stats()["pending"]:
                with TraceAnnotation("engine.step"):
                    engine.step()
            for t in tickets:
                engine.take(t)
    jax.profiler.stop_trace()
    src, = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    dst = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "cpu_overlap_trace.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print(dst, os.path.getsize(dst), engine.stats()["overlapped_batches"],
          engine.stats()["batches"])


if __name__ == "__main__":
    main()
