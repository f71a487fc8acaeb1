"""The share of the device's busy time that a kernel family took, in percent:
`{"kernels": [regex, ...]}` over the trace's operation table."""

from benchmark.readers.kernel_roofline import kernel_rows


def read(params: dict, ctx: dict):
    trace = ctx["trace"]
    if not trace or not trace["busy_s"]:
        return None
    took = sum(r["seconds"] for m in params["kernels"] for r in kernel_rows(trace, m))
    return 100.0 * took / trace["busy_s"] if took else None
