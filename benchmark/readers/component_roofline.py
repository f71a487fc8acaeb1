"""A component's share of its roofline in a token loop, from the trace:
`{"kernel", "calls_per_step"` (as `token_steps` takes them, to count the
turns: the work is done `calls_per_step` times a turn), `"program": regex on
the module's name in the trace, "components": [the components whose device
time is the work's], "costs": the module of `benchmark/trace/` with the cost
function, "cost": the function that counts ONE call}`.

The least time the chip could take for the calls of the turns seen (the
larger of operations over peak FLOP/s and bytes over peak bytes/s) over the
device time the program's table places in those components: it reads the
same work whether a kernel or XLA's own operations do it. Left out where the
join placed under 98% of the trace, or a shape is missing.
"""

import importlib

from benchmark.readers import token_steps
from benchmark.readers.component_share import PLACED_FLOOR
from benchmark.readers.scope_join import joined


def read(params: dict, ctx: dict):
    got = joined(params, ctx)
    # turns x calls_per_step: every call of the work in the traced window
    calls = token_steps.turns({**params, "calls_per_step": 1}, ctx)
    if got is None or not calls or got["placed_share"] < PLACED_FLOOR:
        return None
    took = sum(s for c in params["components"]
               for s in got["seconds"].get(c, {}).values())
    costs = importlib.import_module(f"benchmark.trace.{params['costs']}")
    try:
        ops, nbytes = getattr(costs, params["cost"])(**ctx["shapes"])
    except TypeError:  # the loop gave no such shape
        return None
    if not took:
        return None
    peak = ctx["costs"].peaks(ctx["device"]["kind"])
    return 100.0 * calls * ctx["costs"].least_seconds(ops, nbytes, peak)[0] / took
