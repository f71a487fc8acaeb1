"""The compile ledger (`utils/compile_guard.py`): which program traced,
lowered, compiled or loaded, and when — on the CPU, with real compiles where
JAX's own behaviour is what is pinned and with events put through
`jax.monitoring` by hand where the ledger's arithmetic is."""

from __future__ import annotations

import json
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from dalle_pytorch_tpu.utils import compile_guard
from dalle_pytorch_tpu.utils.compile_cache import CompileCache

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_retrieval_time_sec"
SECONDS = ("trace_s", "lower_s", "compile_s", "load_s")


@pytest.fixture(autouse=True)
def fresh_ledger(monkeypatch):
    """The worker has run other files: their names may have filled the cap.
    And every event is recorded here, however short."""
    compile_guard.install_listener()
    compile_guard.forget()
    monkeypatch.setattr(compile_guard, "RECORD_FROM_S", 0.0)


def entry(name: str) -> dict:
    return compile_guard.programs().get(name, {})


def records_of(name: str) -> list:
    return [r for r in compile_guard.records() if r["program"] == name]


class event:
    """One of JAX's three compile events, emitted as `log_elapsed_time`
    emits it: a scalar on entry, a duration and a time span on exit."""

    def __init__(self, kind: str, fun_name: str, start: float, end: float, hit=False):
        self.kind, self.fun_name, self.start, self.end, self.hit = kind, fun_name, start, end, hit

    def __enter__(self):
        jax.monitoring.record_scalar(self.kind, self.start, fun_name=self.fun_name)

    def __exit__(self, *exc):
        if self.hit:  # the persistent cache answers inside the backend event
            jax.monitoring.record_event_duration_secs(HIT, 0.001)
        jax.monitoring.record_event_duration_secs(
            self.kind, self.end - self.start, fun_name=self.fun_name)
        jax.monitoring.record_event_time_span(
            self.kind, self.start, self.end, fun_name=self.fun_name)


def emit(kind, fun_name, start, end, hit=False):
    with event(kind, fun_name, start, end, hit):
        pass


@pytest.mark.parametrize("fun_name,program", [
    ("jit(lm_sample)", "lm_sample"),
    ("pjit(step)", "step"),
    ("pmap(shard_step)", "shard_step"),
    ("lm_prefill", "lm_prefill"),
    ("jit(jit(odd))", "jit(odd)"),
])
def test_the_three_events_of_a_program_meet_under_one_key(fun_name, program):
    assert compile_guard.program_name(fun_name) == program


def test_a_jitted_function_lands_under_its_name_and_a_second_call_adds_nothing():
    def ledger_probe_named(x):
        return jnp.tanh(x) * 3.0 + 1.0

    f = jax.jit(ledger_probe_named)
    x = jnp.arange(13.0)
    assert entry("ledger_probe_named") == {}
    f(x).block_until_ready()
    first = entry("ledger_probe_named")
    assert first["traces"] == 1 and first["compiles"] == 1 and first["cache_hits"] == 0
    assert first["trace_s"] > 0 and first["lower_s"] > 0 and first["compile_s"] > 0
    assert first["load_s"] == 0.0 and first["first_at"] < first["last_at"]
    assert [r["phase"] for r in records_of("ledger_probe_named")] == ["trace", "lower", "compile"]
    f(x).block_until_ready()
    assert entry("ledger_probe_named") == first


def test_an_inner_jit_is_nested_and_top_level_seconds_stay_under_the_wall_time():
    @jax.jit
    def ledger_probe_inner(x):
        return jnp.sin(x) * 2.0

    def ledger_probe_outer(x):
        return ledger_probe_inner(x).sum() + jnp.cos(x).mean()

    t0 = time.time()
    jax.jit(ledger_probe_outer)(jnp.arange(17.0)).block_until_ready()
    wall = time.time() - t0
    after = compile_guard.programs()
    inner, outer = after["ledger_probe_inner"], after["ledger_probe_outer"]
    assert inner["traces"] == 1 and inner["trace_s"] == 0.0  # counted, not summed
    assert [r["nested"] for r in records_of("ledger_probe_inner")] == [True]
    assert outer["trace_s"] > 0 and not any(r["nested"] for r in records_of("ledger_probe_outer"))
    (nested,) = records_of("ledger_probe_inner")
    (around,) = [r for r in records_of("ledger_probe_outer") if r["phase"] == "trace"]
    assert around["start"] <= nested["start"] and nested["end"] <= around["end"]
    top_level = sum(p[f] for p in after.values() for f in SECONDS)
    assert 0 < top_level <= wall


def test_a_persistent_cache_hit_is_a_load_and_leaves_compile_s_alone(tmp_path):
    def make():  # one source line, two function objects: only the disk can hit
        def ledger_probe_cached(v):
            return v * 3.5 + 0.25

        return jax.jit(ledger_probe_cached)

    x = jnp.arange(19.0) * 1.5
    try:
        CompileCache(tmp_path).install()
        make()(x).block_until_ready()
        cold = entry("ledger_probe_cached")
        assert (cold["compiles"], cold["cache_hits"]) == (1, 0) and cold["compile_s"] > 0
        make()(x).block_until_ready()
        warm = entry("ledger_probe_cached")
    finally:
        CompileCache.uninstall()
    assert (warm["compiles"], warm["cache_hits"], warm["traces"]) == (1, 1, 2)
    assert warm["load_s"] > 0 and warm["compile_s"] == cold["compile_s"]
    assert [r["phase"] for r in records_of("ledger_probe_cached")] == [
        "trace", "lower", "compile", "trace", "lower", "load"]
    assert compile_guard.recent_events()[-1].startswith("ledger_probe_cached: load ")
    assert compile_guard.recent_events()[-1].endswith(" s (hit)")


def test_a_tripped_guard_names_the_offending_program():
    def ledger_probe_drifting(x):
        return x * 2

    f = jax.jit(ledger_probe_drifting)
    f(jnp.ones((3,)))
    with compile_guard.assert_no_recompiles() as tally:
        f(jnp.ones((3,)))
    assert tally.events == []
    with pytest.raises(compile_guard.RecompileError,
                       match=r"ledger_probe_drifting: compile \S+ s \(miss\)"):
        with compile_guard.assert_no_recompiles():
            f(jnp.ones((5,)))  # a new shape: a new program


def test_every_record_lies_inside_the_time_of_the_call_that_caused_it():
    """`start` / `end` are on `time.time()`, the clock the profiler stamps
    its events with: a record can be laid beside a capture as it is."""
    def ledger_probe_clock(x):
        return jnp.exp(x) - 1.0

    t0 = time.time()
    jax.jit(ledger_probe_clock)(jnp.arange(23.0)).block_until_ready()
    t1 = time.time()
    got = records_of("ledger_probe_clock")
    assert [r["phase"] for r in got] == ["trace", "lower", "compile"]
    for r in got:
        assert t0 <= r["start"] <= r["end"] <= t1
        assert r["thread"] == threading.get_ident() and r["nested"] is False
    p = entry("ledger_probe_clock")
    assert p["first_at"] == got[0]["start"] and p["last_at"] == got[-1]["end"]
    assert [r["end"] for r in got] == sorted(r["end"] for r in got)


def test_the_name_cap_pools_the_overflow(monkeypatch):
    monkeypatch.setattr(compile_guard, "MAX_PROGRAMS", 2)
    for i in range(5):
        emit(TRACE, f"ledger_probe_cap_{i}", 100.0 + i, 100.5 + i)
    assert sorted(compile_guard.programs()) == [
        compile_guard.OTHER, "ledger_probe_cap_0", "ledger_probe_cap_1"]
    other = entry(compile_guard.OTHER)
    assert other["traces"] == 3 and other["trace_s"] == pytest.approx(1.5)
    assert (other["first_at"], other["last_at"]) == (102.0, 104.5)
    emit(TRACE, "ledger_probe_cap_0", 200.0, 200.25)  # a known name still finds its entry
    assert entry("ledger_probe_cap_0")["trace_s"] == pytest.approx(0.75)
    assert [r["program"] for r in compile_guard.records()[-6:-1]].count(compile_guard.OTHER) == 3


def test_counts_and_seconds_equal_what_the_old_listener_gave(tmp_path):
    """The three process-wide numbers keep their meaning: every backend
    event counts and adds its duration, nested or not, hit or miss."""
    old = {"count": 0, "hits": 0, "seconds": 0.0}

    def old_listener(name, duration, **kwargs):  # PR 34's `_on_event`
        if "backend_compile" in name:
            old["count"] += 1
            old["seconds"] += duration
        elif "cache_retrieval_time_sec" in name:
            old["hits"] += 1

    def make():
        def ledger_probe_old(v):
            return jnp.sqrt(v + 2.0).sum() + jnp.arange(3.0).sum()  # an eager compile inside

        return jax.jit(ledger_probe_old)

    x = jnp.arange(29.0)
    count, hits, seconds = (compile_guard.compile_count(), compile_guard.cache_hit_count(),
                            compile_guard.compile_seconds())
    jax.monitoring.register_event_duration_secs_listener(old_listener)
    try:
        CompileCache(tmp_path).install()
        with compile_guard.track_compiles() as tally:
            make()(x).block_until_ready()
            make()(x).block_until_ready()
            with event(TRACE, "ledger_probe_old_outer", 10.0, 12.0):
                emit(COMPILE, "jit(ledger_probe_old_nested)", 10.5, 11.5)
    finally:
        CompileCache.uninstall()
        jax.monitoring.unregister_event_duration_listener(old_listener)
    assert old["count"] >= 3 and old["hits"] >= 1
    assert compile_guard.compile_count() - count == old["count"] == tally.count
    assert compile_guard.cache_hit_count() - hits == old["hits"] == tally.cache_hits
    assert compile_guard.compile_seconds() - seconds == pytest.approx(old["seconds"], abs=1e-9)
    nested = entry("ledger_probe_old_nested")
    assert nested["compiles"] == 1 and nested["compile_s"] == 0.0  # in the outer trace's 2 s
    assert entry("ledger_probe_old_outer")["trace_s"] == pytest.approx(2.0)


def test_recent_events_and_a_tally_name_programs_and_phases():
    with compile_guard.track_compiles() as tally:
        emit(TRACE, "ledger_probe_said", 50.0, 53.0)
        emit(LOWER, "jit(ledger_probe_said)", 53.0, 54.0)
        emit(COMPILE, "jit(ledger_probe_said)", 54.0, 95.2)
        emit(COMPILE, "jit(ledger_probe_said)", 96.0, 96.31, hit=True)
    want = ["ledger_probe_said: compile 41.2 s (miss)", "ledger_probe_said: load 0.31 s (hit)"]
    assert tally.events == want and tally.count == 2 and tally.uncached == 1
    assert compile_guard.recent_events()[-2:] == want
    assert len(compile_guard.recent_events()) <= compile_guard.RECENT
    p = entry("ledger_probe_said")
    assert (p["trace_s"], p["lower_s"]) == (3.0, 1.0)
    assert p["compile_s"] == pytest.approx(41.2) and p["load_s"] == pytest.approx(0.31)


def test_a_hit_left_by_an_aborted_compile_is_not_the_next_program_s():
    jax.monitoring.record_event_duration_secs(HIT, 0.001)  # and no backend event follows
    emit(COMPILE, "jit(ledger_probe_after_abort)", 60.0, 61.0)
    p = entry("ledger_probe_after_abort")
    assert (p["compiles"], p["cache_hits"], p["load_s"]) == (1, 0, 0.0)


def test_log_compiles_keeps_its_totals_first_and_names_the_costliest(capsys):
    emit(TRACE, "ledger_probe_costly", 0.0, 4000.0)
    emit(COMPILE, "jit(ledger_probe_costly)", 4000.0, 9000.0)
    emit(COMPILE, "jit(ledger_probe_costly)", 9000.0, 9002.0, hit=True)
    compile_guard.log_compiles()
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith(compile_guard.COMPILES_LINE_PREFIX)]
    totals = json.loads(lines[0][len(compile_guard.COMPILES_LINE_PREFIX):])
    assert set(totals) == {"count", "cache_hits", "uncached", "seconds"}
    assert totals["count"] == compile_guard.compile_count()
    assert 1 <= len(lines) - 1 <= compile_guard.COSTLIEST
    assert lines[1] == (compile_guard.COMPILES_LINE_PREFIX + "  ledger_probe_costly: "
                        "trace+lower 4e+03 s, compile 5e+03 s (1 miss), load 2 s (1 hit)")
    assert not any(l[len(compile_guard.COMPILES_LINE_PREFIX):].startswith("{") for l in lines[1:])


def test_attributed_keeps_a_second_pass_out_of_the_program_s_entry():
    def ledger_probe_again(x):
        return x + 7.0

    f = jax.jit(ledger_probe_again)
    x = jnp.arange(31.0)
    f(x).block_until_ready()
    own = entry("ledger_probe_again")
    with compile_guard.attributed("scope_table"):
        f.lower(jax.ShapeDtypeStruct((37,), jnp.float32)).compile()
    assert entry("ledger_probe_again") == own
    apart = entry("scope_table:ledger_probe_again")
    assert apart["traces"] == 1 and apart["lower_s"] > 0 and apart["compiles"] == 1
    f.lower(jax.ShapeDtypeStruct((41,), jnp.float32))  # the label ended with its block
    assert entry("ledger_probe_again")["traces"] == 2


def test_records_are_bounded_and_the_listeners_meter_themselves():
    cost = compile_guard.listener_cost()
    for i in range(compile_guard.MAX_RECORDS + 10):
        emit(LOWER, "jit(ledger_probe_many)", 300.0 + i, 300.5 + i)
    got = compile_guard.records()
    assert len(got) == compile_guard.MAX_RECORDS
    assert got[-1]["start"] == 300.0 + compile_guard.MAX_RECORDS + 9
    jax.monitoring.record_event_duration_secs("/jax/some/other/event", 1.0)  # not theirs
    after = compile_guard.listener_cost()
    assert after["events"] - cost["events"] == compile_guard.MAX_RECORDS + 10
    assert 0 < after["seconds"] - cost["seconds"] < 1.0


def test_short_traces_and_lowerings_are_summed_and_not_recorded(monkeypatch):
    """A process hears tens of thousands of eager operations' 20 us traces:
    recorded, they would push set-up's own events out in milliseconds."""
    monkeypatch.setattr(compile_guard, "RECORD_FROM_S", 1e-3)
    emit(TRACE, "ledger_probe_short", 400.0, 400.0005)
    emit(LOWER, "jit(ledger_probe_short)", 400.001, 400.0015)
    emit(COMPILE, "jit(ledger_probe_short)", 400.002, 400.0025)  # the guard's message needs it
    emit(TRACE, "ledger_probe_short", 401.0, 401.002)
    assert [(r["phase"], r["start"]) for r in records_of("ledger_probe_short")] == [
        ("compile", 400.002), ("trace", 401.0)]
    p = entry("ledger_probe_short")
    assert p["traces"] == 2 and p["trace_s"] == pytest.approx(0.0025)
    assert p["lower_s"] == pytest.approx(0.0005) and p["compile_s"] == pytest.approx(0.0005)


def test_threads_keep_their_own_nesting_and_no_update_is_lost():
    """More threads than cores, a short switch interval: every thread's
    events land, none is taken for nested because ANOTHER thread has an
    event open, and the process-wide count loses nothing."""
    workers, rounds = 16, 200
    count = compile_guard.compile_count()
    barrier = threading.Barrier(workers)

    def work(k: int):
        barrier.wait(timeout=30)
        for i in range(rounds):
            with event(TRACE, f"ledger_probe_thread_{k}", 1000.0 + i, 1000.5 + i):
                emit(TRACE, f"ledger_probe_thread_{k}_inner", 1000.1 + i, 1000.2 + i)
            emit(COMPILE, f"jit(ledger_probe_thread_{k})", 1000.5 + i, 1000.75 + i)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,)) for k in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert compile_guard.compile_count() - count == workers * rounds
    for k in range(workers):
        p, inner = entry(f"ledger_probe_thread_{k}"), entry(f"ledger_probe_thread_{k}_inner")
        assert (p["traces"], p["compiles"]) == (rounds, rounds)
        assert p["trace_s"] == pytest.approx(0.5 * rounds)
        assert p["compile_s"] == pytest.approx(0.25 * rounds)
        assert inner["traces"] == rounds and inner["trace_s"] == 0.0
