"""A component's share of one program's device time, in percent:
`{"program": regex on the module's name in the trace, "components": [names
of `dalle_pytorch_tpu/obs/scopes.py:COMPONENTS`; empty for all], "phase":
"fwd" | "bwd" | "remat", optional}`.

Device time is attributed through the table the program keeps from XLA's
instruction names to its own components (`readers/scope_join.py` says how
it is joined). The share is of the time that was PLACED, so the components
of one program sum to 100 with `unscoped` among them; where under 98% of
the trace's time could be placed the metric is left out, rather than
reported over part of the work.
"""

from benchmark.readers.scope_join import joined, scopes

PLACED_FLOOR = 0.98


def read(params: dict, ctx: dict):
    got = joined(params, ctx)
    if got is None or got["placed_share"] < PLACED_FLOOR:
        return None
    return scopes.share(got, params.get("components", ()), params.get("phase"))
