"""Mean host-clock time of one ``ClusterEngine.step()`` call in the traced
serving slice. The call ends with the blocking read-back, so it holds the
batch's staging, H2D copy, cell and D2H copy."""


def read(ctx):
    return ctx.probes.get("engine_step_ms")
