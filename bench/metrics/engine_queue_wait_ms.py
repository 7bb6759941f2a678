"""Mean wait of a request in the engine's queue in the traced serving slice,
from ``submit`` to the start of the step that takes its first rows: Σ
``queue_wait_us`` ÷ Σ ``requests`` over the ``engine.batch`` spans."""
import spans


def read(ctx):
    return spans.queue_wait_ms(spans.of(ctx))
