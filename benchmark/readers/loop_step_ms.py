"""Device time of one turn of a loop that lives inside ONE program (the
sampler's token loop), in milliseconds: `{"once_per_step": regex}` names an
operation that runs exactly once per turn; the device's busy time in the
traced window over the number of its events there. The window cuts the first
and last turn short, so the reading is good to one turn in the window's count.
"""

from benchmark.readers.kernel_roofline import kernel_rows


def read(params: dict, ctx: dict):
    trace = ctx["trace"]
    if not trace:
        return None
    turns = sum(r["count"] for r in kernel_rows(trace, params["once_per_step"]))
    return 1e3 * trace["busy_s"] / turns if turns else None
