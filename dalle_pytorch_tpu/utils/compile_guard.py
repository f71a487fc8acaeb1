"""Compile-count guard and compile ledger: pin that a code region compiles
NOTHING new, and say WHICH program traced, lowered, compiled or loaded, when.

The serving stack's latency story rests on a fixed ladder of compiled
shapes (engine warmup compiles every program the steady state will ever
dispatch). A stray recompile — a drifting shape, a new dtype, an eager op
with a data-dependent shape — silently turns a ~ms dispatch into a
~seconds compile, exactly the hazard class tracelint's TL001 hunts
statically. `assert_no_recompiles` is the RUNTIME end of that contract:

    engine.warmup()
    with assert_no_recompiles():
        ...steady-state serve cycle...   # raises if anything compiles

Counting is based on `jax.monitoring`'s backend-compile events (one per XLA
compilation or persistent-cache load; a hit in jit's own in-memory cache
emits nothing), which covers jit, pjit, AND first-execution compiles of
eager ops. The listeners are installed once per process and count into
module globals; the context manager snapshots the counter around the
block, so guards nest safely.

THE LEDGER. JAX passes `fun_name=` and the event's start and end on
`time.time()` (the epoch clock the profiler stamps its events with) along
with each of its three compile events. The listeners keep, per PROGRAM (the
`fun_name` less its `jit(...)` wrapper, so `lm_sample`'s trace and
`jit(lm_sample)`'s lowering and compile meet under one key):

    traces, trace_s    `jaxpr_trace_duration`
    lower_s            `jaxpr_to_mlir_module_duration`
    compiles, compile_s  backend compiles that ran XLA
    cache_hits, load_s   backend compiles served by the persistent cache:
                         the retrieval and the executable's load
    first_at, last_at  epoch seconds of its first event's start and its
                       last event's end

and the newest `MAX_RECORDS` events as records `{program, phase, start, end,
nested}`: every backend event, and every trace or lowering of `RECORD_FROM_S`
or more (a process hears tens of thousands of shorter ones, an eager
operation finding its jaxpr cached: they are summed and not recorded, or the
records would span the last few milliseconds).

An event that fires INSIDE another of its thread (an inner jit's trace inside
its caller's trace, what Pallas traces while its caller is being lowered, an
eager constant compiled in the middle of a trace) is `nested`: it is counted
(`traces`, `compiles`, `cache_hits`) but its seconds are left out of every
`*_s`, because its parent's seconds hold them already. So one thread's
seconds, summed over all programs, never exceed the wall time they span.
`programs()` and `records()` read the ledger; `log_compiles()` prints its
most expensive programs; `attributed(label)` keeps a deliberate second pass
through a program out of its entry.

DISPATCHES. The program's one dispatch site (`obs/scopes.py:remembering`, which
every sampler and prefill program and the sharded serving ladder pass) tells
the ledger of every call: `dispatched(name, key, first, start, end, args,
result)`, `start` and `end` the call's on `time.time()`, the compile events'
clock. When the result was READY comes from the device: one small leaf of the
result that the caller keeps (`_leaf_to_wait_on`) goes to ONE daemon thread,
started at the first dispatch, which asks it every `POLL_S` whether it is
ready (`is_ready`) and stamps `done`; the caller never waits. A TPU runs one
program after another, so with `prev` the newest stamp before it a dispatch
OCCUPIED the device for `done - max(prev, end)`:

    device busy when the call returned (`prev > end`, prefills queued back to
        back): `done - prev`, the program's own device time
    device idle when it returned: `done - end`, and `end - prev` is the GAP,
        the device with nothing of the ledger's to run, waiting for the host
    an instance's FIRST dispatch: `done - max(prev, end)` as well, which
        leaves its trace, lowering and compile or cache load (all inside
        `start .. end`) out of the device's seconds; it has no gap (the host
        was compiling, which the four `*_s` say)

whatever else the device ran in that time (an eager operation, another jit's
program queued before it) is counted with it, and the milliseconds between
the runtime starting the program and the call's return (a result of hundreds
of leaves takes about ten to hand back) are the gap's, not the occupancy's:
0.3% of a 4.4 s batch (my chip run, PR 49). Per program, beside the fields
above:

    dispatches, dispatch_s   calls, and the host's seconds inside them (`end -
                             start`: holds a first call's trace, lowering and
                             compile, so it is NOT one of the four `*_s`)
    device_s, first_device_s   occupancy of all dispatches, and of each
                               instance's first (a sampler's warm-up batch)
    gap_s, gap_max_s         the gaps before its dispatches, and the longest
    unstamped                dispatches that got no stamp: nothing to wait on,
                             or the leaf was donated to the next call before
                             it was seen ready (asking a deleted array
                             raises). Their seconds fall to the next stamped
                             dispatch of the timeline
    instances                `remembering` objects of this name dispatched so
                             far (a sampler compiled for two sampling settings
                             is two), numbered in order of first dispatch

and a record `{program, phase: "dispatch", start, end, done, instance, first,
device_s, gap_s, nested: False}` a dispatch, in the same ring. `stamping`
switches all of it off; `drain()` waits for the stamps still pending; at exit
the thread is stopped, pending stamps or not (it sleeps between two asks and
is never inside the runtime when the interpreter goes).

CAVEAT — the guard's attribution is process-wide, not per-thread: a
compilation triggered on ANY thread during the block (another engine warming
up in a parallel fixture, a lazy jit on a server thread) counts against the
guard and fails it. Guard regions while no other thread is dispatching to
JAX; the failure message names the programs so a cross-thread culprit is
identifiable.
"""

from __future__ import annotations

import atexit
import contextlib
import queue
import re
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Deque, Dict, Iterator, List, Optional, Sequence, Tuple

#: the three events `jax._src.dispatch.log_elapsed_time` emits, each with
#: `fun_name=`: as a scalar (the start) on entry, as a duration and as a
#: time span on exit
_PHASE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
#: the event the persistent compilation cache emits once per CACHE HIT —
#: on jax 0.9.0 (tests/test_compile_cache_dir.py): a hit still fires the
#: backend-compile event (around the executable load), so `compiles -
#: cache_hits` is the count of compilations that actually ran XLA. The
#: warm-boot contract (`utils/compile_cache.py`) pins `uncached == 0` on a
#: second boot. It carries no name and fires on the compiling thread just
#: before its program's backend-compile event ends: that event is the hit's.
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

#: `jit(lm_sample)`, `pjit(step)`, `pmap(f)` -> the function's own name
_WRAPPED = re.compile(r"^(?:jit|pjit|pmap)\((.*)\)$")

#: program names kept apart; what comes after them is pooled under `OTHER`
#: (eager operations compile as `jit(broadcast_in_dim)` and the like, and a
#: long-lived process must not grow a key for every one it ever meets)
MAX_PROGRAMS = 256
OTHER = "(other)"
#: events kept as records (a long-lived process keeps the newest), and the
#: shortest trace or lowering that is one: `flagship.train` hears 11,700
#: events a run and `olmohybrid.decode.512` 36,000, nearly all of them eager
#: operations' traces of some 20 us (my chip run, PR 35)
MAX_RECORDS = 1024
RECORD_FROM_S = 1e-3
#: `remembering` objects of one name whose ordinal is kept (a process that
#: builds samplers without end must not keep a number for every one)
MAX_INSTANCES = 64
#: how many of the newest backend events `recent_events()` formats
RECENT = 32

#: the switch of the dispatch stamps: off, `dispatched()` keeps nothing and
#: starts no thread (tests, and the measurement of what the stamping costs)
stamping = True
#: the longest `drain()` waits for stamps still pending unless told otherwise
#: (and the process at exit for the stamping thread to end), and how often
#: that thread asks whether a dispatch's result is ready: a stamp is late by
#: up to this, and the next one's occupancy short by as much
DRAIN_S = 0.5
POLL_S = 1e-3

_lock = threading.Lock()
_installed = False
_compile_count = 0
_cache_hit_count = 0
_compile_seconds = 0.0
_programs: Dict[str, dict] = {}
#: (program, phase, start, end, nested, thread, ordinal): `ordinal` is the
#: value `_compile_count` took with a backend event, 0 for trace and lower.
#: A dispatch is a LIST, the stamping thread fills it in: the seven, then
#: instance, first, done, device_s, gap_s
_records: Deque[Sequence] = deque(maxlen=MAX_RECORDS)
#: per thread: how many of the three events are open, whether the
#: persistent cache answered the backend compile that is open, and the
#: seconds `_on_enter` spent that no `_on_span` has counted yet
_thread = threading.local()
#: what the listeners themselves cost: events heard and seconds spent on them
_heard = 0
_heard_seconds = 0.0
#: the dispatches: program -> {key of the `remembering` object: [its ordinal,
#: the leaves its result had, which of them is waited on]};
#: (record, row, leaf) handed to the stamping thread, how many of them wait,
#: the newest stamp (the device's timeline is one, whatever the program), and
#: what `dispatched()` and the thread's bookkeeping cost (the wait is not cost)
_instances: Dict[str, Dict[int, list]] = {}
_pending: "queue.SimpleQueue[tuple]" = queue.SimpleQueue()
_stamped = threading.Condition(_lock)
_waiting = 0
_watcher: Optional[threading.Thread] = None
_closing = False
_last_done = 0.0
_dispatches_heard = 0
_dispatch_seconds = 0.0
_dispatch_errors = 0


def program_name(fun_name: str) -> str:
    """The ledger's key for one of JAX's `fun_name`s."""
    m = _WRAPPED.match(fun_name) if fun_name.endswith(")") else None
    return m.group(1) if m else fun_name


def _row(name: str, start: float, end: float) -> Tuple[str, dict]:
    """The ledger's key and row for `name`, the row made at its first event
    (the lock held); past `MAX_PROGRAMS` names, the pooled one's."""
    if name not in _programs and len(_programs) >= MAX_PROGRAMS:
        name = OTHER
    p = _programs.get(name)
    if p is None:
        p = _programs[name] = {
            "traces": 0, "trace_s": 0.0, "lower_s": 0.0, "compiles": 0,
            "compile_s": 0.0, "cache_hits": 0, "load_s": 0.0,
            "first_at": start, "last_at": end,
            "dispatches": 0, "dispatch_s": 0.0, "device_s": 0.0, "first_device_s": 0.0,
            "gap_s": 0.0, "gap_max_s": 0.0, "unstamped": 0, "instances": 0,
        }
    return name, p


def _on_enter(event: str, start: float, **kwargs) -> None:
    phase = _PHASE_OF.get(event)
    if phase is None:
        return
    t = time.perf_counter()
    _thread.depth = getattr(_thread, "depth", 0) + 1
    if phase == "compile":
        _thread.hit = False  # a hit that an aborted compile left behind
    _thread.spent = getattr(_thread, "spent", 0.0) + time.perf_counter() - t


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == _CACHE_HIT_EVENT:
        global _cache_hit_count
        _thread.hit = True
        with _lock:
            _cache_hit_count += 1


def _on_span(event: str, start: float, end: float, fun_name: str = "", **kwargs) -> None:
    phase = _PHASE_OF.get(event)
    if phase is None:
        return
    global _compile_count, _compile_seconds, _heard, _heard_seconds
    t = time.perf_counter()
    # a listener installed in the middle of an event hears an exit without
    # its entry: never below zero
    depth = _thread.depth = max(0, getattr(_thread, "depth", 0) - 1)
    nested, seconds, ordinal = depth > 0, end - start, 0
    hit = phase == "compile" and getattr(_thread, "hit", False)
    name = program_name(str(fun_name))
    label = getattr(_thread, "label", None)
    if label is not None:
        name = f"{label}:{name}"
    spent, _thread.spent = getattr(_thread, "spent", 0.0), 0.0
    # one lock for the counters and the ledger: `programs()`, `records()`
    # and the vitals state dump snapshot from other threads; compiles are
    # rare, the lock is noise
    with _lock:
        if phase == "compile":
            _compile_count += 1
            _compile_seconds += seconds
            ordinal = _compile_count
        name, p = _row(name, start, end)
        p["first_at"], p["last_at"] = min(p["first_at"], start), max(p["last_at"], end)
        if phase == "trace":
            p["traces"] += 1
        elif hit:
            phase = "load"
            p["cache_hits"] += 1
        elif phase == "compile":
            p["compiles"] += 1
        if not nested:
            p[phase + "_s"] += seconds
        if ordinal or seconds >= RECORD_FROM_S:
            _records.append((name, phase, start, end, nested, threading.get_ident(), ordinal))
        _heard += 1
        _heard_seconds += spent + time.perf_counter() - t


def _leaf_to_wait_on(leaves: list, args) -> int:
    """Which of a result's leaves the stamping thread is handed: the smallest
    by bytes of those the caller will not donate to its next call, as far as
    that shows. What it will donate is the state it was handed back: a leaf
    with the shape and dtype of an argument this call took (donated, the
    argument is deleted by now). Where every leaf is such (`lm_place` returns
    the cache alone), the smallest of all."""
    taken = {(x.shape, x.dtype) for x in _tree_leaves(args)
             if hasattr(x, "is_deleted") and x.is_deleted()}
    keeps = [i for i, x in enumerate(leaves)
             if (getattr(x, "shape", None), getattr(x, "dtype", None)) not in taken]
    return min(keeps or range(len(leaves)), key=lambda i: getattr(leaves[i], "nbytes", 0))


def _tree_leaves(tree) -> list:
    import jax

    return jax.tree_util.tree_leaves(tree)


def dispatched(name: str, key: int, first: bool, start: float, end: float, args, result) -> None:
    """One dispatch of the program `name` through `obs/scopes.py:remembering`:
    the call took `args`, ran from `start` to `end` on `time.time()` and
    returned `result`, whose arrays the device may still be computing. `key`
    tells the `remembering` objects of one name apart (the instances), `first`
    says this is the object's first dispatch. Keeps the record and hands one
    small leaf of `result` to the stamping thread (`_leaf_to_wait_on`: found
    at an instance's first dispatch and kept while the count of leaves stays).
    Never raises, never waits; a call inside another program's trace, whose
    result is tracers, is no dispatch."""
    if not stamping:
        return
    global _waiting, _dispatches_heard, _dispatch_seconds, _dispatch_errors
    t, lost = time.perf_counter(), 0
    try:
        leaves = _tree_leaves(result)
        if leaves and not hasattr(leaves[0], "is_ready"):  # tracers
            return
        _start_watcher()
        with _lock:
            name, p = _row(name, start, end)
            ids = _instances.setdefault(name, {})
            known = ids.get(key)
            if first or known is None:  # a new object may have an old one's id
                ids.pop(key, None)
                known = ids[key] = [p["instances"], -1, 0]
                p["instances"] += 1
                if len(ids) > MAX_INSTANCES:  # oldest out: numbered anew if it comes back
                    del ids[next(iter(ids))]
            if leaves and known[1] != len(leaves):
                known[1:] = len(leaves), _leaf_to_wait_on(leaves, args)
            leaf = leaves[known[2]] if leaves else None
            p["dispatches"] += 1
            p["dispatch_s"] += end - start
            record = [name, "dispatch", start, end, False, threading.get_ident(), 0,
                      known[0], bool(first), None, None, None]
            _records.append(record)
            if leaf is None:
                p["unstamped"] += 1
            else:
                _waiting += 1
        if leaf is not None:
            _pending.put((record, p, leaf))
    except Exception:  # the ledger loses a record; the caller loses nothing
        lost = 1
    finally:
        with _lock:
            _dispatches_heard += 1
            _dispatch_errors += lost
            _dispatch_seconds += time.perf_counter() - t


def _start_watcher() -> None:
    global _watcher
    if _watcher is not None:
        return
    with _lock:
        if _watcher is not None:
            return
        _watcher = threading.Thread(target=_watch, name="dispatch-stamps", daemon=True)
        atexit.register(_close)
        _watcher.start()


def _ready_at(leaf) -> Optional[float]:
    """When `leaf` was ready, or None: it was deleted (donated to a later
    call before its turn came), or the process is ending. Asked every
    `POLL_S`, and not waited on inside the runtime: a thread that comes back
    from a wait there while the interpreter is being torn down takes the
    process with it (`terminate called ...`, exit 134), a thread asleep does
    not. And asking holds the interpreter's lock, so no other thread can be
    donating the array meanwhile."""
    try:
        while not leaf.is_ready():
            if _closing:
                return None
            time.sleep(POLL_S)
        return time.time()
    except Exception:
        return None


def _watch() -> None:
    """The stamping thread: wait for each dispatch's leaf in the order the
    dispatches were made, which is the order the device runs them in."""
    global _waiting, _last_done, _dispatch_seconds
    while True:
        item = _pending.get()
        if item is None:  # `_close`
            return
        record, p, leaf = item
        item = None
        done = _ready_at(leaf)
        leaf = None  # no buffer is held past its stamp
        t = time.perf_counter()
        with _lock:
            if done is None:
                p["unstamped"] += 1
            else:
                end, first = record[3], record[8]
                device_s = done - max(_last_done, end)
                gap_s = max(0.0, end - _last_done) if _last_done and not first else 0.0
                record[9:] = done, device_s, gap_s
                p["device_s"] += device_s
                if first:
                    p["first_device_s"] += device_s
                p["gap_s"] += gap_s
                p["gap_max_s"] = max(p["gap_max_s"], gap_s)
                _last_done = done
            _waiting -= 1
            if _waiting == 0:
                _stamped.notify_all()
            _dispatch_seconds += time.perf_counter() - t


def _close() -> None:
    """At exit: the stamping thread is told to ask no more and given
    `DRAIN_S` to end (it takes a `POLL_S`): the runtime is torn down after
    this returns, and nobody reads a stamp made after it."""
    global _closing
    _closing = True
    _pending.put(None)  # wakes it where it waits for a dispatch
    _watcher.join(DRAIN_S)


def drain(timeout: float = DRAIN_S) -> bool:
    """Wait, `timeout` seconds at most, until every dispatch made so far has
    its stamp; whether it has."""
    with _lock:
        return _stamped.wait_for(lambda: _waiting == 0, timeout)


@contextlib.contextmanager
def attributed(label: str) -> Iterator[None]:
    """Keep what this thread traces, lowers and compiles inside the block
    under `<label>:<program>`: for work that goes through a program AGAIN on
    purpose (`obs/scopes.py` lowers one a second time to read its
    instructions), so that the program's own entry stays what it cost to
    bring up."""
    before = getattr(_thread, "label", None)
    _thread.label = label
    try:
        yield
    finally:
        _thread.label = before


def _install_listener() -> None:
    global _installed
    with _lock:
        if _installed:
            return
        import jax

        jax.monitoring.register_scalar_listener(_on_enter)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_time_span_listener(_on_span)
        _installed = True


def install_listener() -> None:
    """Public hook for consumers that read `compile_count()` outside a
    guard block — the span tracer (`obs/tracing.py`) installs it so spans
    can tally the compilations that happened while they were open.
    Idempotent; imports jax on first call."""
    _install_listener()


def compile_count() -> int:
    """Backend compilations observed so far this process (after the first
    guard/`track_compiles`/`install_listener` use installed the
    listener; 0 forever before that — readers treat it as a delta
    source, not an absolute truth)."""
    return _compile_count


def cache_hit_count() -> int:
    """Backend compilations that were served from the persistent
    compilation cache (`jax_compilation_cache_dir`) rather than run
    through XLA. Each hit ALSO fires the backend-compile event, so
    `compile_count() - cache_hit_count()` is the number of compilations
    that actually paid XLA time. 0 forever when no cache dir is
    configured."""
    return _cache_hit_count


def compile_seconds() -> float:
    """Wall seconds spent inside backend-compile events so far (a
    persistent-cache hit still fires one, around the executable load, so
    a warm run reports its load time here and `cache_hits == count`)."""
    return _compile_seconds


def programs() -> Dict[str, dict]:
    """The ledger, a copy: program name -> `traces`, `trace_s`, `lower_s`,
    `compiles`, `compile_s`, `cache_hits`, `load_s`, `first_at`, `last_at`,
    and of its dispatches `dispatches`, `dispatch_s`, `device_s`,
    `first_device_s`, `gap_s`, `gap_max_s`, `unstamped`, `instances` (the
    module's docstring says what each is)."""
    with _lock:
        return {name: dict(p) for name, p in _programs.items()}


def records() -> List[dict]:
    """The newest events (at most `MAX_RECORDS`; every backend event, a
    trace or lowering from `RECORD_FROM_S` up, and every dispatch), oldest
    first: `{program, phase: trace|lower|compile|load|dispatch, start, end,
    nested, thread}`, `start` and `end` in epoch seconds as JAX stamped them,
    which is the clock a profiler capture's events are on. A dispatch also
    has `instance`, `first`, `done` and, from `done`, `device_s` and `gap_s`
    (None where it has no stamp, or none yet)."""
    with _lock:
        kept = [tuple(r) for r in _records]
    out = []
    for n, ph, s, e, nested, thread, _, *stamp in kept:
        out.append({"program": n, "phase": ph, "start": s, "end": e, "nested": nested,
                    "thread": thread})
        if stamp:
            out[-1].update(zip(("instance", "first", "done", "device_s", "gap_s"), stamp))
    return out


def forget() -> None:
    """Drop the ledger's programs, records and instance numbers (tests: a
    process that has run other suites has its names used up). The
    process-wide counters stay, and the newest stamp: the device's timeline
    does not start again."""
    with _lock:
        _programs.clear()
        _records.clear()
        _instances.clear()


def listener_cost() -> dict:
    """What the ledger itself has cost this process: the `events` heard (one
    for each trace, lowering and backend compile, nested or not) and the
    `seconds` spent on them inside the listeners, from an event's entry
    being noted to its record being kept. What it leaves out is the call
    itself and the listeners' first line on the events that are not theirs
    (a dictionary miss or a string compare). And of the dispatches:
    `dispatches` heard, `dispatch_seconds` spent in `dispatched()` and in the
    stamping thread's bookkeeping (its wait for the device is no cost; the
    wrapper's two clock reads are not in it), `dispatch_errors` records lost
    to an exception."""
    with _lock:
        return {"events": _heard, "seconds": _heard_seconds,
                "dispatches": _dispatches_heard, "dispatch_seconds": _dispatch_seconds,
                "dispatch_errors": _dispatch_errors}


def _backend_events(after: int = 0) -> List[str]:
    """The recorded backend events past the `after`th of the process, said
    by program: `"lm_sample: compile 41.2 s (miss)"`."""
    with _lock:
        kept = [r for r in _records if r[6] > after]
    return [f"{name}: {phase} {end - start:.3g} s ({'miss' if phase == 'compile' else 'hit'})"
            for name, phase, start, end, *_ in kept]


def recent_events() -> List[str]:
    """The newest backend events by program, as `"lm_sample: compile 41.2 s
    (miss)"` or `"lm_sample: load 0.31 s (hit)"` (at most `RECENT`) —
    engine-state dumps (`/debug/state`) and stall reports include them so an
    unexpected mid-serve compile is identifiable without a guard block in
    place."""
    return _backend_events()[-RECENT:]


COMPILES_LINE_PREFIX = "[compiles] "
#: how many programs `log_compiles()` names after its totals
COSTLIEST = 5


def costliest(n: int = COSTLIEST) -> List[str]:
    """The `n` programs that cost most (trace + lower + compile + load), one
    line each: name, trace + lower, then what the backend did, hit or miss,
    and of a program dispatched through `remembering` what its dispatches
    cost the device (occupancy, its warm-ups' part) and the gaps before them."""
    def cost(p):
        return p["trace_s"] + p["lower_s"] + p["compile_s"] + p["load_s"]

    lines = []
    paid = [(name, p) for name, p in programs().items() if cost(p) > 0]
    for name, p in sorted(paid, key=lambda kv: -cost(kv[1]))[:n]:
        parts = [f"trace+lower {p['trace_s'] + p['lower_s']:.3g} s"]
        if p["compiles"]:
            parts.append(f"compile {p['compile_s']:.3g} s ({p['compiles']} miss)")
        if p["cache_hits"]:
            parts.append(f"load {p['load_s']:.3g} s ({p['cache_hits']} hit)")
        if p["dispatches"]:
            parts.append(f"{p['dispatches']} dispatches: device {p['device_s']:.3g} s "
                         f"(first {p['first_device_s']:.3g} s), "
                         f"gap {p['gap_s']:.3g} s (max {p['gap_max_s']:.3g} s)")
        lines.append(f"{name}: " + ", ".join(parts))
    return lines


def log_compiles() -> None:
    """Print this process's compile receipt — the batch CLIs end with it, so
    a cold run and a warm run of the same command can be told apart from
    their output alone. The first line holds the four totals as JSON; the
    lines after it name the programs that cost most, so a program that
    missed the cache on a warm run is named."""
    import json

    drain()  # the last dispatch's stamp, if the caller did not wait for it
    count, hits = _compile_count, _cache_hit_count
    print(COMPILES_LINE_PREFIX + json.dumps({
        "count": count, "cache_hits": hits,
        "uncached": max(0, count - hits),
        "seconds": round(_compile_seconds, 2),
    }), flush=True)
    for line in costliest():
        print(COMPILES_LINE_PREFIX + "  " + line, flush=True)


class RecompileError(AssertionError):
    """A guarded region compiled something new."""


@dataclass
class CompileTally:
    """Live view of compilations inside a guard block."""

    _start: int = 0
    allowed: int = 0
    _start_hits: int = 0

    @property
    def count(self) -> int:
        return _compile_count - self._start

    @property
    def cache_hits(self) -> int:
        """Compilations in the block that loaded from the persistent
        compilation cache instead of running XLA."""
        return _cache_hit_count - self._start_hits

    @property
    def uncached(self) -> int:
        """Compilations that actually paid XLA time — the warm-boot
        contract (`utils/compile_cache.py`) pins this at zero on a
        second boot against a populated cache."""
        return max(0, self.count - self.cache_hits)

    @property
    def events(self) -> List[str]:
        """The block's backend events by program (`recent_events()`'s form;
        those still among the newest `MAX_RECORDS` records) — context for
        the error message."""
        return _backend_events(after=self._start)


@contextlib.contextmanager
def track_compiles() -> Iterator[CompileTally]:
    """Count backend compilations in a block without asserting."""
    _install_listener()
    yield CompileTally(_start=_compile_count, _start_hits=_cache_hit_count)


@contextlib.contextmanager
def assert_no_recompiles(allowed: int = 0) -> Iterator[CompileTally]:
    """Raise `RecompileError` if the block triggers more than `allowed`
    backend compilations (default: zero — the steady-state contract)."""
    _install_listener()
    tally = CompileTally(
        _start=_compile_count, allowed=allowed, _start_hits=_cache_hit_count
    )
    yield tally
    if tally.count > allowed:
        raise RecompileError(
            f"guarded region compiled {tally.count} program(s) "
            f"(allowed {allowed}) — a shape/dtype drifted out of the "
            f"warmup set. Compiled in the region: {tally.events}"
        )
