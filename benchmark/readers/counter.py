"""A count or a ratio the loop kept: `{"counter": name, "scale": 1.0}`."""


def read(params: dict, ctx: dict):
    value = ctx["counters"].get(params["counter"])
    return None if value is None else float(value) * float(params.get("scale", 1.0))
