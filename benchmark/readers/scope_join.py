"""The share of the device's time in the trace that the program's own table
(`dalle_pytorch_tpu/obs/scopes.py`) could place, in percent: `{"program":
regex on the module's name in the trace}`.

The table maps XLA's instruction names to the program's components; it is
lowered again, after the window, from the shapes the program remembered at
its first dispatch. An operation is placed only where its instruction name
AND its result shape are the table's. What is not placed is `unjoined`:
another program's operations (the trace pools every program of the window
in one table), or a table that is not this program's. `component_share`
reads nothing under 98%.

`joined(params, ctx)` is shared with `component_share` and kept in the
run's context, so that thirteen metric files lower a program once. A trace
without device operations by HLO name (a CPU rehearsal) places nothing, and
a program that keeps no table (any commit before `obs/scopes.py`) has
nothing to read: the metric is left out.
"""

import re
import time

try:
    from dalle_pytorch_tpu.obs import scopes
except ImportError:  # a program older than its table
    scopes = None

MODULE = re.compile(r"^jit_(.+?)(\(\d+\))?$")


def program_names(trace: dict, pattern: str) -> list:
    """The program's names (less `jit_`) whose module the pattern finds: in
    the trace's module table, or, where the trace has none (the CPU), among
    the programs remembered."""
    pat = re.compile(pattern)
    modules = list(trace.get("modules") or []) or [f"jit_{n}(0)" for n in scopes.names()]
    found = []
    for module in modules:
        m = MODULE.match(module)
        if m and pat.search(module) and m.group(1) not in found:
            found.append(m.group(1))
    return found


def joined(params: dict, ctx: dict):
    """The best join of the trace's operations with a table of a program
    that `params["program"]` names; None where there is no such program."""
    from benchmark import harness

    trace = ctx["trace"]
    if scopes is None or not trace or not trace.get("ops"):
        return None
    kept = ctx.setdefault("scope_joins", {})
    key = tuple(program_names(trace, params["program"]))
    if key not in kept:
        t = time.perf_counter()
        joins = [j for j in (scopes.best_join(trace["ops"], name) for name in key)
                 if j is not None]
        best = max(joins, key=lambda j: j["placed_s"]) if joins else None
        kept[key] = best
        if best is not None:
            harness.say(
                "scopes", programs=key, seconds=time.perf_counter() - t,
                lowered=scopes.lowered, total_s=best["total_s"],
                placed_pct=100.0 * best["placed_share"],
                unjoined_pct=100.0 * (1.0 - best["placed_share"]),
                components={c: scopes.share(best, [c]) for c in sorted(best["seconds"])},
                phases={p: scopes.share(best, [], p) for p in scopes.PHASES},
            )
    return kept[key]


def read(params: dict, ctx: dict):
    got = joined(params, ctx)
    return None if got is None or got["total_s"] <= 0 else 100.0 * got["placed_share"]
