"""`kernel_roofline` with the cost functions of a module the metric file
names: `{"costs": a module of `benchmark/trace/`, "kernels": [{"match": regex
of the kernel's name in the trace, "cost": the function that counts one
call}, ...]}`; the peaks are `trace/costs.py`'s. A kernel named here that the
trace lacks, or a shape the loop did not give, makes the metric unreadable:
it is left out.
"""

import importlib

from benchmark.readers.kernel_roofline import kernel_rows


def read(params: dict, ctx: dict):
    trace = ctx["trace"]
    if not trace:
        return None
    found = [(k, kernel_rows(trace, k["match"])) for k in params["kernels"]]
    if not all(rows for _, rows in found):
        return None
    costs = importlib.import_module(f"benchmark.trace.{params['costs']}")
    peak = ctx["costs"].peaks(ctx["device"]["kind"])
    least = took = 0.0
    for k, rows in found:
        try:
            ops, nbytes = getattr(costs, k["cost"])(**ctx["shapes"])
        except TypeError:  # the loop gave no such shape
            return None
        least += sum(r["count"] for r in rows) * ctx["costs"].least_seconds(ops, nbytes, peak)[0]
        took += sum(r["seconds"] for r in rows)
    return 100.0 * least / took if took else None
