"""Compile-count guard: pin that a code region compiles NOTHING new.

The serving stack's latency story rests on a fixed ladder of compiled
shapes (engine warmup compiles every program the steady state will ever
dispatch). A stray recompile — a drifting shape, a new dtype, an eager op
with a data-dependent shape — silently turns a ~ms dispatch into a
~seconds compile, exactly the hazard class tracelint's TL001 hunts
statically. `assert_no_recompiles` is the RUNTIME end of that contract:

    engine.warmup()
    with assert_no_recompiles():
        ...steady-state serve cycle...   # raises if anything compiles

Counting is based on `jax.monitoring`'s backend-compile duration events
(one per XLA compilation, cache hits emit nothing), which covers jit,
pjit, AND first-execution compiles of eager ops. The listener is installed
once per process and counts into a module global; the context manager
snapshots the counter around the block, so guards nest safely.

CAVEAT — attribution is process-wide, not per-thread: jax.monitoring
events carry no thread identity, so a compilation triggered on ANY thread
during the block (another engine warming up in a parallel fixture, a lazy
jit on a server thread) counts against the guard and fails it. Guard
regions while no other thread is dispatching to JAX; the failure message
lists the observed events so a cross-thread culprit is identifiable.
"""

from __future__ import annotations

import contextlib
import threading
from collections import deque
from dataclasses import dataclass
from typing import Deque, Iterator, List

#: the event jax.monitoring emits once per backend (XLA) compilation
_COMPILE_EVENT_SUFFIX = "backend_compile"
#: the event the persistent compilation cache emits once per CACHE HIT —
#: on jax 0.9.0 (tests/test_compile_cache_dir.py): a hit still fires the backend_compile event
#: (around the executable load), so `compiles - cache_hits` is the count
#: of compilations that actually ran XLA. The warm-boot contract
#: (`utils/compile_cache.py`) pins `uncached == 0` on a second boot.
_CACHE_HIT_EVENT_SUFFIX = "cache_retrieval_time_sec"

_lock = threading.Lock()
_installed = False
_compile_count = 0
_cache_hit_count = 0
_compile_seconds = 0.0
#: recent event names only (error-message context) — a bare counter plus a
#: bounded deque keeps a long-lived process from accumulating one string
#: per compilation forever
_recent_events: Deque[str] = deque(maxlen=32)


def _install_listener() -> None:
    global _installed
    with _lock:
        if _installed:
            return
        import jax

        def _on_event(name: str, duration: float, **kwargs) -> None:
            # '/jax/core/compile/backend_compile_duration' et al.
            if _COMPILE_EVENT_SUFFIX in name:
                global _compile_count, _compile_seconds
                # the deque append is guarded so `recent_events()` can
                # snapshot from other threads (the vitals state dump);
                # compiles are rare, the lock is noise
                with _lock:
                    _compile_count += 1
                    _compile_seconds += duration
                    _recent_events.append(name)
            elif _CACHE_HIT_EVENT_SUFFIX in name:
                global _cache_hit_count
                with _lock:
                    _cache_hit_count += 1

        jax.monitoring.register_event_duration_secs_listener(_on_event)
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


COMPILES_LINE_PREFIX = "[compiles] "


def log_compiles() -> None:
    """Print this process's compile receipt as one parseable line — the
    batch CLIs end with it, so a cold run and a warm run of the same
    command can be told apart from their output alone."""
    import json

    count, hits = _compile_count, _cache_hit_count
    print(COMPILES_LINE_PREFIX + json.dumps({
        "count": count, "cache_hits": hits,
        "uncached": max(0, count - hits),
        "seconds": round(_compile_seconds, 2),
    }), flush=True)


def recent_events() -> List[str]:
    """The most recent compile event names (bounded window) — engine-state
    dumps (`/debug/state`) and stall reports include them so an unexpected
    mid-serve compile is identifiable without a guard block in place.
    Snapshot under the lock: the listener appends from whichever thread
    compiles."""
    with _lock:
        return list(_recent_events)


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
        """The most recent compile event names (bounded window) — context
        for the error message, not a complete ledger."""
        return list(_recent_events)[-max(self.count, 0):] if self.count else []


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
            f"warmup set. Recent compile events: {tally.events}"
        )
