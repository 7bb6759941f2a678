"""Share of the traced serving slice's device batches whose model differs
from the previous batch's: the engine's ``model_switches`` over its
``batches`` counter, both counted over the slice. None where the engine has
no such counter."""


def read(ctx):
    switches = ctx.probes.get("engine_model_switches")
    batches = ctx.probes.get("engine_batches")
    if switches is None or not batches:
        return None
    return 100.0 * float(switches) / float(batches)
