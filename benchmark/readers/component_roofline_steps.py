"""A component's share of its roofline in a token loop, the work counted by
the STEP: `component_roofline` for a component that has no kernel of its own
to count its calls by. `{"kernel", "calls_per_step"` (as `token_steps` takes
them: a kernel that every step calls a fixed number of times counts the
steps), `"program": regex on the module's name in the trace, "components":
[the components whose device time is the work's], "costs": the module of
`benchmark/trace/` with the cost function, "cost": the function that counts
ONE STEP's work of those components}`.

The least time the chip could take for the steps seen (the larger of
operations over peak FLOP/s and bytes over peak bytes/s) over the device time
the program's table places in those components: it reads the same work
whether a kernel or XLA's own operations do it. Left out where the join
placed under 98% of the trace, a shape is missing, or the program has no such
component (any commit before it).
"""

import importlib

from benchmark.readers import token_steps
from benchmark.readers.component_share import PLACED_FLOOR
from benchmark.readers.scope_join import joined


def read(params: dict, ctx: dict):
    got = joined(params, ctx)
    steps = token_steps.turns(params, ctx)
    if got is None or not steps or got["placed_share"] < PLACED_FLOOR:
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
    return 100.0 * steps * ctx["costs"].least_seconds(ops, nbytes, peak)[0] / took
