"""Share of the traced serving slice (its ``serve`` annotation) in which no
operation ran on the device: 100 · (1 − busy ÷ window), from the trace."""


def read(ctx):
    if ctx.trace is None or "engine_step_ms" not in ctx.probes:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
