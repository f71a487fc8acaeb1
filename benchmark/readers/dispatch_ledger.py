"""Device seconds, or a ratio of them, from what the program's compile ledger
keeps of its DISPATCHES (`dalle_pytorch_tpu/utils/compile_guard.py`, told by
`obs/scopes.py:remembering`, the one wrapper every sampler and prefill program
is called through), in two forms:

`{"program": regex, "fields": [...]}` -> the sum of those fields over the
programs the regex finds that were dispatched: `device_s` (what the program's
dispatches OCCUPIED the device for: each from the later of the stamp before it
and its call's return to its own stamp, the stamp made by a thread that asks
the smallest leaf of the result whether it is ready), `first_device_s` (the
same of each instance's FIRST dispatch: a sampler setting's warm-up batch),
`gap_s`, `dispatch_s`, `dispatches`, `unstamped`.

`{"program": regex, "over": [a, b]}` -> the mean occupancy of instance `a`'s
dispatches over the mean of instance `b`'s, from the ledger's records. The
instances of a name are its compiled programs in order of first dispatch: a
loop warms "every sampler setting of the cycle once" in the cycle's order, so
instance 0 is `job.batches[0]`'s sampler and 1 the next setting's.

The prefill programs are dispatched in set-up and nowhere else, and an
instance's first dispatch is its warm-up, also set-up: the regex and the field
keep the window out, as `compile_ledger`'s regex keeps the references out.

The first metric read in a run says the whole of it once on a
`[dispatch_ledger]` line: `programs` (every dispatched program's row),
`dispatches` (every record: `[program, instance, first, start, end, done,
device_s, gap_s]`, epoch seconds, the `[compile_ledger]` line's `timeline`'s
clock), `device_s` and `unstamped` over all of them, `wall_s` from the first
call to the last stamp, and what the stamping cost (`listener`). A program
whose ledger has no dispatches (any commit before them, or the switch off) has
nothing to read: the metric is left out and nothing is said.
"""

import re

try:
    from dalle_pytorch_tpu.utils import compile_guard
except ImportError:  # a program older than its guard
    compile_guard = None

SAID = ("program", "instance", "first", "start", "end", "done", "device_s", "gap_s")
DRAIN_S = 5.0  # for the last dispatch's stamp: long made by the time a metric is read


def dispatch_records() -> list:
    records = getattr(compile_guard, "records", None)
    return [r for r in records() if r["phase"] == "dispatch"] if records else []


def say_once(rows: dict, ctx: dict) -> None:
    from benchmark import harness

    if ctx.get("dispatch_ledger_said"):
        return
    ctx["dispatch_ledger_said"] = True
    records = dispatch_records()
    stamps = [r["done"] for r in records if r["done"] is not None]
    harness.say(
        "dispatch_ledger", programs=[dict(p, program=name) for name, p in rows.items()],
        dispatches=[[r[k] for k in SAID] for r in records],
        device_s=sum(p["device_s"] for p in rows.values()),
        unstamped=sum(p["unstamped"] for p in rows.values()),
        wall_s=max(stamps) - min(r["start"] for r in records) if stamps else None,
        listener=compile_guard.listener_cost(),
    )


def read(params: dict, ctx: dict):
    programs = getattr(compile_guard, "programs", None)
    if programs is None:
        return None
    drain = getattr(compile_guard, "drain", None)
    if drain is not None:
        drain(DRAIN_S)
    rows = {name: p for name, p in programs().items() if p.get("dispatches")}
    if not rows:
        return None
    say_once(rows, ctx)
    pattern = re.compile(params["program"])
    found = [p for name, p in rows.items() if pattern.search(name)]
    if not found:
        return None
    if "over" not in params:
        return sum(p.get(f, 0) for p in found for f in params["fields"])
    stamped = [r for r in dispatch_records()
               if pattern.search(r["program"]) and r["device_s"] is not None]
    top, bottom = ([r["device_s"] for r in stamped if r["instance"] == k] for k in params["over"])
    if not top or not bottom or sum(bottom) <= 0:
        return None
    return (sum(top) / len(top)) / (sum(bottom) / len(bottom))
