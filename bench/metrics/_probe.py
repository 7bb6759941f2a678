"""Shared by the dispatcher probes: time a jitted wrapper of one layer entry
under the profiler, and turn its device time into a roofline share."""
from __future__ import annotations

import work

CALLS = 5


def run(ctx, name: str, fn, args, least: dict) -> None:
    """Call ``fn`` (a jitted function named ``name``) once to compile and
    ``CALLS`` more times, each to completion; the profiler is running."""
    jax = ctx.jax
    with jax.default_matmul_precision("highest"):
        for _ in range(CALLS + 1):
            jax.block_until_ready(fn(*args))
    ctx.probes[name] = least


def read(ctx, name: str):
    import trace_reduce
    least = ctx.probes.get(name)
    if least is None or ctx.trace is None:
        return None
    module = f"jit_{name}"
    calls = trace_reduce.module_calls(ctx.trace["trace"], module)
    if not calls:
        return None
    seconds = trace_reduce.module_seconds(ctx.trace["trace"], module) / calls
    kind = ctx.jax.devices()[0].device_kind
    roof = work.roofline(least, seconds, kind)
    ctx.probes[name + ".roofline"] = dict(roof, seconds=seconds, calls=calls)
    return roof["pct"]
