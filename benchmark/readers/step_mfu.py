"""The share of the chip's peak that a token loop's useful operations are,
over the device's busy time in the trace, in percent: `{"kernel",
"calls_per_step"` (as `token_steps` takes them), `"costs": the module of
`benchmark/trace/`, "flops": its function that counts ONE turn's useful
operations from the loop's shapes}`. Turns seen x operations a turn over
peak FLOP/s x busy time: what `token_step_device_ms` is read against."""

import importlib

from benchmark.readers import token_steps


def read(params: dict, ctx: dict):
    n = token_steps.turns(params, ctx)
    if not n or not ctx["trace"]["busy_s"]:
        return None
    costs = importlib.import_module(f"benchmark.trace.{params['costs']}")
    try:
        flops = getattr(costs, params["flops"])(**ctx["shapes"])
    except TypeError:  # the loop gave no such shape
        return None
    peak = ctx["costs"].peaks(ctx["device"]["kind"])
    return 100.0 * n * flops / (peak["bf16_flops"] * ctx["trace"]["busy_s"])
