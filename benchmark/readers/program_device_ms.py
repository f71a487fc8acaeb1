"""Device time of one compiled program, from the trace, in milliseconds.

`{"match": regex of the program's name in the trace, "per": N or the name of
a count in the loop's shapes (e.g. token steps per program call)}`: the mean
duration of the matching program events divided by `per`.
"""

import re


def read(params: dict, ctx: dict):
    trace = ctx["trace"]
    if not trace:
        return None
    pat = re.compile(params["match"])
    rows = [v for k, v in trace["modules"].items() if pat.search(k)]
    calls = sum(r["count"] for r in rows)
    if not calls:
        return None
    per = params.get("per", 1)
    if isinstance(per, str):
        per = ctx["shapes"].get(per)
        if not per:
            return None
    return 1e3 * sum(r["seconds"] for r in rows) / calls / float(per)
