"""Mean length of the program's ``engine.readback`` spans in the traced
serving slice: the blocking read of a batch's output, so the device's
compute and the copy back as the host waits on them."""
import spans


def read(ctx):
    return spans.mean_ms(spans.of(ctx), "engine.readback")
