"""A kernel family's share of its roofline, from the trace.

`{"kernels": [{"match": regex of the kernel's name in the trace, "cost": the
function of `trace/costs.py` that counts one call}, ...]}`. The least time
the chip could take for the calls seen (the larger of operations over peak
FLOP/s and bytes over peak bytes/s, from the loop's shapes) over the time
the kernels took. A kernel named here that the trace lacks makes the metric
unreadable, so it is left out rather than reported over part of the work.
"""

import re


def kernel_rows(trace: dict, match: str):
    pat = re.compile(match)
    return [v for k, v in trace["ops"].items() if pat.search(k)]


def read(params: dict, ctx: dict):
    trace = ctx["trace"]
    if not trace:
        return None
    costs = ctx["costs"]
    found = [(k, kernel_rows(trace, k["match"])) for k in params["kernels"]]
    if not all(rows for _, rows in found):
        return None
    peak = costs.peaks(ctx["device"]["kind"])
    least = took = 0.0
    for k, rows in found:
        ops, nbytes = getattr(costs, k["cost"])(**ctx["shapes"])
        least += sum(r["count"] for r in rows) * costs.least_seconds(ops, nbytes, peak)[0]
        took += sum(r["seconds"] for r in rows)
    return 100.0 * least / took
