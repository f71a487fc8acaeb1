"""Device time of one turn of a token loop that lives inside ONE program, in
milliseconds, the turns counted by a kernel's NAME: `{"kernel": regex of a
kernel's name in the trace, "calls_per_step": how often a turn calls it: a
number, or the name of a count or of a list among the loop's shapes (one
call a layer: the list of the layers' kinds)}`.

A kernel is named by the program (`name=` of its `pallas_call`, which the
chip makes its instruction's name), so no shape is matched by hand; the
device's busy time in the traced window over the turns seen there. The
window cuts the first and last turn short: good to one turn in the count.
A trace without the kernel (a program that lacks it) has nothing to read.

`turns(params, ctx)` is shared with `component_roofline` and `step_mfu`.
"""

from benchmark.readers.kernel_roofline import kernel_rows


def turns(params: dict, ctx: dict):
    """Token steps in the traced window, or None."""
    trace = ctx["trace"]
    if not trace or not trace.get("ops"):
        return None
    per_step = params.get("calls_per_step", 1)
    if isinstance(per_step, str):
        per_step = ctx["shapes"].get(per_step)
        if isinstance(per_step, (list, tuple)):
            per_step = len(per_step)
    calls = sum(r["count"] for r in kernel_rows(trace, params["kernel"]))
    return calls / per_step if calls and per_step else None


def read(params: dict, ctx: dict):
    n = turns(params, ctx)
    return 1e3 * ctx["trace"]["busy_s"] / n if n else None
