"""Seconds, or a count, from the program's compile ledger
(`dalle_pytorch_tpu/utils/compile_guard.py`): `{"program": regex on the
ledger's program names, "fields": [...]}` -> the sum of those fields over the
programs the regex finds.

The ledger keeps, per program (JAX's `fun_name` less its `jit(...)`), what
the process's one set of `jax.monitoring` listeners heard of it: `traces`,
`trace_s`, `lower_s`, `compiles`, `compile_s` (XLA really ran), `cache_hits`,
`load_s` (the persistent cache answered: retrieval and the executable's
load). Seconds are top level only: an inner jit's trace inside its caller's
is in the caller's seconds and nowhere else, so the sums stay under the wall
time they span. The ledger is the process's whole life, and the references
and `compare` compile AFTER the window: the regex names the programs that
the cell's window dispatches, and its prefill, and that is what keeps the
rest out of the sum.

The first metric read in a run says the whole ledger on a `[compile_ledger]`
line: every program that cost 10 ms or more and whether the regex found it,
the ledger's records as a `timeline` (`[program, phase, start, end, nested]`
in epoch seconds: every backend event, and every top-level trace or lowering
of 10 ms or more; the `[setup]` line's `compiles` is how many backend events
lie before the window), and what the listeners themselves cost. A program
without a ledger (any commit before it) has nothing to read, and a regex
that finds no program has nothing to sum: the metric is left out.
"""

import re

try:
    from dalle_pytorch_tpu.utils import compile_guard
except ImportError:  # a program older than its guard
    compile_guard = None

SECONDS = ("trace_s", "lower_s", "compile_s", "load_s")
SAID_FROM_S = 0.01


def say_once(ledger: dict, pattern, ctx: dict) -> None:
    from benchmark import harness

    if ctx.get("compile_ledger_said"):
        return
    ctx["compile_ledger_said"] = True
    cost = lambda p: sum(p[f] for f in SECONDS)
    rows = [
        dict(p, program=name, matched=bool(pattern.search(name)))
        for name, p in sorted(ledger.items(), key=lambda kv: -cost(kv[1]))
        if cost(p) >= SAID_FROM_S
    ]
    records = compile_guard.records()
    timeline = [
        [r["program"], r["phase"], r["start"], r["end"], r["nested"]] for r in records
        if r["phase"] in ("compile", "load")
        or (not r["nested"] and r["end"] - r["start"] >= SAID_FROM_S)
    ]
    harness.say(
        "compile_ledger", programs=rows, names=len(ledger),
        all_programs={f: sum(p[f] for p in ledger.values()) for f in SECONDS},
        listener=compile_guard.listener_cost(), records=len(records), timeline=timeline,
    )


def read(params: dict, ctx: dict):
    programs = getattr(compile_guard, "programs", None)
    if programs is None:
        return None
    ledger, pattern = programs(), re.compile(params["program"])
    say_once(ledger, pattern, ctx)
    found = [p for name, p in ledger.items() if pattern.search(name)]
    return sum(p[f] for p in found for f in params["fields"]) if found else None
