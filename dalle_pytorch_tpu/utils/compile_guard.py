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

CAVEAT — the guard's attribution is process-wide, not per-thread: a
compilation triggered on ANY thread during the block (another engine warming
up in a parallel fixture, a lazy jit on a server thread) counts against the
guard and fails it. Guard regions while no other thread is dispatching to
JAX; the failure message names the programs so a cross-thread culprit is
identifiable.
"""

from __future__ import annotations

import contextlib
import re
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Deque, Dict, Iterator, List

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
#: how many of the newest backend events `recent_events()` formats
RECENT = 32

_lock = threading.Lock()
_installed = False
_compile_count = 0
_cache_hit_count = 0
_compile_seconds = 0.0
_programs: Dict[str, dict] = {}
#: (program, phase, start, end, nested, thread, ordinal): `ordinal` is the
#: value `_compile_count` took with a backend event, 0 for trace and lower
_records: Deque[tuple] = deque(maxlen=MAX_RECORDS)
#: per thread: how many of the three events are open, whether the
#: persistent cache answered the backend compile that is open, and the
#: seconds `_on_enter` spent that no `_on_span` has counted yet
_thread = threading.local()
#: what the listeners themselves cost: events heard and seconds spent on them
_heard = 0
_heard_seconds = 0.0


def program_name(fun_name: str) -> str:
    """The ledger's key for one of JAX's `fun_name`s."""
    m = _WRAPPED.match(fun_name) if fun_name.endswith(")") else None
    return m.group(1) if m else fun_name


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
        if name not in _programs and len(_programs) >= MAX_PROGRAMS:
            name = OTHER
        p = _programs.get(name)
        if p is None:
            p = _programs[name] = {
                "traces": 0, "trace_s": 0.0, "lower_s": 0.0, "compiles": 0,
                "compile_s": 0.0, "cache_hits": 0, "load_s": 0.0,
                "first_at": start, "last_at": end,
            }
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
    `compiles`, `compile_s`, `cache_hits`, `load_s`, `first_at`, `last_at`
    (the module's docstring says what each is)."""
    with _lock:
        return {name: dict(p) for name, p in _programs.items()}


def records() -> List[dict]:
    """The newest events (at most `MAX_RECORDS`; every backend event, and
    a trace or lowering from `RECORD_FROM_S` up), oldest first: `{program,
    phase: trace|lower|compile|load, start, end, nested, thread}`, `start`
    and `end` in epoch seconds as JAX stamped them, which is the clock a
    profiler capture's events are on."""
    with _lock:
        kept = list(_records)
    return [{"program": n, "phase": ph, "start": s, "end": e, "nested": nested,
             "thread": thread} for n, ph, s, e, nested, thread, _ in kept]


def forget() -> None:
    """Drop the ledger's programs and records (tests: a process that has run
    other suites has its names used up). The process-wide counters stay."""
    with _lock:
        _programs.clear()
        _records.clear()


def listener_cost() -> dict:
    """What the ledger itself has cost this process: the `events` heard (one
    for each trace, lowering and backend compile, nested or not) and the
    `seconds` spent on them inside the listeners, from an event's entry
    being noted to its record being kept. What it leaves out is the call
    itself and the listeners' first line on the events that are not theirs
    (a dictionary miss or a string compare)."""
    with _lock:
        return {"events": _heard, "seconds": _heard_seconds}


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
    line each: name, trace + lower, then what the backend did, hit or miss."""
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
        lines.append(f"{name}: " + ", ".join(parts))
    return lines


def log_compiles() -> None:
    """Print this process's compile receipt — the batch CLIs end with it, so
    a cold run and a warm run of the same command can be told apart from
    their output alone. The first line holds the four totals as JSON; the
    lines after it name the programs that cost most, so a program that
    missed the cache on a warm run is named."""
    import json

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
