"""Mean length of the program's ``engine.batch`` spans in the traced serving
slice: one ``ClusterEngine.step`` as the engine records it, on the
profiler's clock (the inside twin of ``engine_step_ms``)."""
import spans


def read(ctx):
    return spans.mean_ms(spans.of(ctx), "engine.batch")
