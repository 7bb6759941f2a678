"""Real rows per device batch in the traced serving slice: the engine's
``rows_served`` over its ``batches`` counter, both counted over the slice."""


def read(ctx):
    batches = ctx.probes.get("engine_batches")
    if not batches:
        return None
    return float(ctx.probes["engine_rows"]) / float(batches)
