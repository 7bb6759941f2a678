"""Share of the traced serving slice (its ``serve`` annotation) in which the
device is idle while the host is inside an ``engine.batch`` span: the part
of ``device_idle_pct.serve`` that the engine's own host path (staging, H2D
copy, dispatch, read-back) leaves. The rest is idle time with no engine
work on the host, mostly waiting for arrivals."""
import spans


def read(ctx):
    return spans.idle_engine_pct(spans.of(ctx))
