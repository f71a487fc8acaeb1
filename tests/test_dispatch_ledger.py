"""The compile ledger's dispatches (`utils/compile_guard.py:dispatched`, told
by `obs/scopes.py:remembering`): what each program's calls cost the host and
the device — on the CPU, with real jitted programs where the wrapper and the
runtime are what is pinned, and with leaves that are ready at a set time where
the ledger's arithmetic is."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from dalle_pytorch_tpu.obs import scopes
from dalle_pytorch_tpu.utils import compile_guard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SECONDS = ("trace_s", "lower_s", "compile_s", "load_s")
EARLY = 0.005  # seconds a stamp or a sleep may come before its time: none, but for rounding


@pytest.fixture(autouse=True)
def fresh_ledger(monkeypatch):
    """The worker has run other files: their dispatches may still wait for a
    stamp, and their names may have filled the cap."""
    compile_guard.install_listener()
    monkeypatch.setattr(compile_guard, "stamping", True)
    assert compile_guard.drain(60)
    compile_guard.forget()
    yield
    assert compile_guard.drain(60)


def entry(name: str) -> dict:
    return compile_guard.programs().get(name, {})


def dispatches_of(name: str) -> list:
    return [r for r in compile_guard.records()
            if r["program"] == name and r["phase"] == "dispatch"]


def program(name: str, rounds: int = 20, **jit_kwargs):
    """A `remembering` program called `name` with some device time to it."""
    def fn(x):
        return lax_rounds(x, rounds), x.sum()

    fn.__name__ = name
    return scopes.remembering(jax.jit(fn, **jit_kwargs))


def lax_rounds(x, rounds):
    return jax.lax.fori_loop(0, rounds, lambda i, a: a @ a / jnp.linalg.norm(a), x)


class Leaf:
    """What the stamping thread asks: ready `after` seconds from now, or
    deleted (asking raises, as a donated array does)."""

    nbytes = 4

    def __init__(self, after=0.0, deleted=False):
        self.at, self.deleted = time.time() + after, deleted

    def is_ready(self) -> bool:
        if self.deleted:
            raise RuntimeError("Array has been deleted.")
        return time.time() >= self.at


def test_three_dispatches_leave_three_records_one_first_and_device_time_under_the_wall():
    f, x = program("dispatch_probe_three"), jnp.ones((96, 96))
    for _ in range(3):
        f(x)
    other = program("dispatch_probe_three_other")
    other(x)
    assert compile_guard.drain(60)
    p, records = entry("dispatch_probe_three"), dispatches_of("dispatch_probe_three")
    assert p["dispatches"] == 3 and p["instances"] == 1 and p["unstamped"] == 0
    assert [r["first"] for r in records] == [True, False, False]
    assert [r["instance"] for r in records] == [0, 0, 0]
    for r in records:
        assert r["start"] <= r["end"] <= r["done"] and r["nested"] is False
        assert r["device_s"] >= 0 and r["gap_s"] >= 0
    assert p["device_s"] == pytest.approx(sum(r["device_s"] for r in records))
    assert p["first_device_s"] == pytest.approx(records[0]["device_s"])
    assert p["dispatch_s"] == pytest.approx(sum(r["end"] - r["start"] for r in records))
    every = [r for r in compile_guard.records() if r["phase"] == "dispatch"]
    wall = max(r["done"] for r in every) - min(r["start"] for r in every)
    assert sum(q["device_s"] for q in compile_guard.programs().values()) <= wall


def test_two_objects_of_one_name_are_instances_in_order_of_first_dispatch():
    a, b, x = program("dispatch_probe_twins"), program("dispatch_probe_twins"), jnp.ones((8, 8))
    b(x), a(x), b(x), a(x)
    assert compile_guard.drain(60)
    records = dispatches_of("dispatch_probe_twins")
    assert [(r["instance"], r["first"]) for r in records] == [
        (0, True), (1, True), (0, False), (1, False)]
    p = entry("dispatch_probe_twins")
    assert p["instances"] == 2 and p["dispatches"] == 4
    assert p["first_device_s"] == pytest.approx(records[0]["device_s"] + records[1]["device_s"])


def test_an_output_donated_to_the_next_call_raises_nothing_and_is_counted():
    """`lm_place` returns only the cache, which the next call takes: by the
    time the stamping thread asks, the array may be deleted. Either way the
    dispatch is counted, stamped or `unstamped`, and the last one, which
    nobody took, has its stamp."""
    def dispatch_probe_donated(cache):
        return {k: lax_rounds(v, 3) for k, v in cache.items()}

    f = scopes.remembering(jax.jit(dispatch_probe_donated, donate_argnums=(0,)))
    cache = {"k": jnp.ones((64, 64)), "v": jnp.ones((64, 64))}
    for _ in range(12):
        cache = f(cache)
    assert compile_guard.drain(60)
    p, records = entry("dispatch_probe_donated"), dispatches_of("dispatch_probe_donated")
    stamped = [r for r in records if r["done"] is not None]
    assert p["dispatches"] == 12 == len(stamped) + p["unstamped"]
    assert records[-1]["done"] is not None
    assert all(r["device_s"] is None and r["gap_s"] is None for r in records if r["done"] is None)
    assert p["device_s"] == pytest.approx(sum(r["device_s"] for r in stamped))
    assert compile_guard.listener_cost()["dispatch_errors"] == 0
    assert float(cache["k"].sum()) > 0  # the caller's arrays are its own


def test_state_handed_back_is_not_what_is_waited_on_where_the_result_has_more():
    """`lm_prefill` returns the cache and the routed layers' counts, and the
    cache's index is smaller than any count: waited on, it would be deleted
    by the next call's donation more often than not."""
    def dispatch_probe_state(cache, x):
        return {"k": lax_rounds(cache["k"], 3), "index": cache["index"] + 1}, (
            jnp.zeros((4,), jnp.int32), x.sum())

    f = scopes.remembering(jax.jit(dispatch_probe_state, donate_argnums=(0,)))
    cache = {"k": jnp.ones((64, 64)), "index": jnp.zeros((), jnp.int32)}
    for _ in range(12):
        cache, counts = f(cache, jnp.ones((8,)))
    assert compile_guard.drain(60)
    p = entry("dispatch_probe_state")
    assert (p["dispatches"], p["unstamped"]) == (12, 0)
    assert compile_guard._instances["dispatch_probe_state"][id(f)][1:] == [4, 3]  # x.sum(): the index before it is smaller, and the cache's
    assert int(cache["index"]) == 12


def test_a_deleted_leaf_is_unstamped_and_its_seconds_fall_to_the_next_stamp():
    t = time.time()
    compile_guard.dispatched("dispatch_probe_gone", 1, True, t, t, (), [Leaf(0.0)])
    assert compile_guard.drain(5)
    compile_guard.dispatched("dispatch_probe_gone", 1, False, t, t, (), [Leaf(deleted=True)])
    compile_guard.dispatched("dispatch_probe_gone", 1, False, t, t, (), [Leaf(0.3)])
    compile_guard.dispatched("dispatch_probe_gone", 1, False, t, t, (), {})  # nothing to wait on
    assert compile_guard.drain(5)
    first, gone, after, empty = dispatches_of("dispatch_probe_gone")
    assert gone["done"] is None and empty["done"] is None and entry(
        "dispatch_probe_gone")["unstamped"] == 2
    # the device was busy when the third call returned: from the stamp before it,
    # which holds what the deleted one took (the worker's load makes a stamp
    # late, never early: only the lower bounds are the clock's)
    assert after["device_s"] == pytest.approx(after["done"] - first["done"])
    assert after["done"] >= t + 0.3 - EARLY and after["gap_s"] == 0.0


@pytest.mark.parametrize("case", ["busy_at_return", "idle_at_return", "first"])
def test_occupancy_and_gap_in_the_three_cases(case):
    t = time.time()
    compile_guard.dispatched("dispatch_probe_case", 1, True, t, t, (), [Leaf(0.2)])
    if case == "busy_at_return":  # queued behind the first: its own time is from that stamp on
        compile_guard.dispatched("dispatch_probe_case", 1, False, t, t + 0.01, (), [Leaf(0.5)])
        assert compile_guard.drain(5)
        one, two = dispatches_of("dispatch_probe_case")
        assert two["device_s"] == pytest.approx(two["done"] - one["done"])
        assert two["done"] >= t + 0.5 - EARLY and two["gap_s"] == 0.0
        assert entry("dispatch_probe_case")["device_s"] == pytest.approx(two["done"] - t)
        return
    assert compile_guard.drain(5)
    one = dispatches_of("dispatch_probe_case")[0]
    time.sleep(0.3)  # the device has nothing to run
    end = time.time()
    compile_guard.dispatched("dispatch_probe_case", 2 if case == "first" else 1,
                             case == "first", end - 0.25, end, (), [Leaf(0.2)])
    assert compile_guard.drain(5)
    two = dispatches_of("dispatch_probe_case")[1]
    assert two["device_s"] == pytest.approx(two["done"] - end) and two["device_s"] >= 0.2 - EARLY
    p = entry("dispatch_probe_case")
    if case == "first":  # the host was compiling: the four `*_s` say that, not a gap
        assert two["gap_s"] == 0.0 and p["gap_s"] == 0.0
        assert p["first_device_s"] == pytest.approx(one["device_s"] + two["device_s"])
    else:
        assert two["gap_s"] == pytest.approx(end - one["done"]) and two["gap_s"] >= 0.3 - EARLY
        assert p["gap_s"] == p["gap_max_s"] == two["gap_s"]


def test_a_blocked_host_between_two_dispatches_is_gap_and_not_device_time():
    f, x = program("dispatch_probe_sleepy", rounds=2), jnp.ones((16, 16))
    f(x)[1].block_until_ready()
    assert compile_guard.drain(60)
    time.sleep(0.4)
    f(x)[1].block_until_ready()
    assert compile_guard.drain(60)
    p, (_, second) = entry("dispatch_probe_sleepy"), dispatches_of("dispatch_probe_sleepy")
    assert second["gap_s"] >= 0.4 - EARLY and p["gap_max_s"] == second["gap_s"]
    assert second["device_s"] < 0.2 and p["device_s"] < 0.4


def test_the_first_dispatch_s_compile_is_in_dispatch_s_and_not_in_first_device_s():
    def dispatch_probe_unrolled(x):
        for _ in range(150):  # long to trace, lower and compile
            x = jnp.tanh(x @ x) + 1.0
        return x

    f = scopes.remembering(jax.jit(dispatch_probe_unrolled))
    f(jnp.ones((8, 8))).block_until_ready()
    assert compile_guard.drain(60)
    p, (call,) = entry("dispatch_probe_unrolled"), dispatches_of("dispatch_probe_unrolled")
    brought_up = [r for r in compile_guard.records()
                  if r["program"] == "dispatch_probe_unrolled" and r["phase"] != "dispatch"]
    assert {r["phase"] for r in brought_up} == {"trace", "lower", "compile"}
    assert p["compiles"] == 1 and sum(p[k] for k in SECONDS) > 0.05
    # inside the call, so in its host seconds; the device's start at the call's return
    assert all(call["start"] <= r["start"] and r["end"] <= call["end"] for r in brought_up)
    assert p["dispatch_s"] == call["end"] - call["start"] >= sum(p[k] for k in SECONDS)
    assert p["first_device_s"] == p["device_s"] == call["done"] - call["end"]


def test_the_four_compile_seconds_are_what_they_are_without_the_dispatch_fields(monkeypatch):
    x, rows = jnp.ones((16, 16)), {}
    for on in (True, False):
        monkeypatch.setattr(compile_guard, "stamping", on)
        f = program(f"dispatch_probe_switch_{on}", rounds=2)
        f(x)
        first = {k: entry(f.name)[k] for k in SECONDS + ("traces", "compiles", "cache_hits")}
        for _ in range(4):
            f(x)[1].block_until_ready()
        assert compile_guard.drain(60)
        rows[on] = entry(f.name)
        assert {k: rows[on][k] for k in first} == first  # a dispatch adds to none of them
    assert set(rows[True]) == set(rows[False])
    assert (rows[True]["traces"], rows[True]["compiles"]) == (rows[False]["traces"],
                                                              rows[False]["compiles"]) == (1, 1)
    assert rows[True]["dispatches"] == 5 and rows[False]["dispatches"] == 0
    assert rows[False]["device_s"] == rows[False]["dispatch_s"] == 0.0
    assert not dispatches_of("dispatch_probe_switch_False")


def test_forget_empties_the_ledger_and_numbers_instances_anew():
    f, x = program("dispatch_probe_forgotten"), jnp.ones((8, 8))
    f(x), program("dispatch_probe_forgotten")(x)
    assert compile_guard.drain(60)
    assert entry("dispatch_probe_forgotten")["instances"] == 2
    compile_guard.forget()
    assert compile_guard.programs() == {} and compile_guard.records() == []
    f(x)  # an object the ledger has forgotten: numbered where it comes back, not first
    assert compile_guard.drain(60)
    (r,), p = dispatches_of("dispatch_probe_forgotten"), entry("dispatch_probe_forgotten")
    assert (r["instance"], r["first"]) == (0, False)
    assert (p["dispatches"], p["instances"], p["first_device_s"]) == (1, 1, 0.0)


def test_the_ring_stays_under_max_records_and_the_rows_count_on():
    t = time.time()
    for i in range(compile_guard.MAX_RECORDS + 40):
        compile_guard.dispatched("dispatch_probe_many", 1, i == 0, t, t, (), [Leaf(0.0)])
    assert compile_guard.drain(30)
    got = compile_guard.records()
    assert len(got) == compile_guard.MAX_RECORDS
    assert all(r["phase"] == "dispatch" and r["done"] is not None for r in got)
    p = entry("dispatch_probe_many")
    assert p["dispatches"] == compile_guard.MAX_RECORDS + 40 and p["unstamped"] == 0


def test_a_call_inside_another_program_s_trace_is_no_dispatch():
    inner, x = program("dispatch_probe_inner", rounds=1), jnp.ones((8, 8))
    outer = jax.jit(lambda x: inner(x)[1] * 2.0)
    assert float(outer(x)) == 128.0
    assert compile_guard.drain(60)
    assert entry("dispatch_probe_inner").get("dispatches", 0) == 0
    assert not dispatches_of("dispatch_probe_inner")


def test_nothing_raises_into_the_caller_and_the_lost_record_is_counted(monkeypatch):
    f, x = program("dispatch_probe_broken", rounds=1), jnp.ones((8, 8))
    before = compile_guard.listener_cost()

    def broken(*args):
        raise ValueError("the ledger is broken")

    monkeypatch.setattr(compile_guard, "_start_watcher", broken)
    assert float(f(x)[1]) == 64.0
    after = compile_guard.listener_cost()
    assert after["dispatch_errors"] - before["dispatch_errors"] == 1
    assert after["dispatches"] - before["dispatches"] == 1


def test_the_listeners_meter_the_dispatches_too():
    before = compile_guard.listener_cost()
    f, x = program("dispatch_probe_metered", rounds=1), jnp.ones((8, 8))
    for _ in range(10):
        f(x)
    assert compile_guard.drain(60)
    after = compile_guard.listener_cost()
    assert after["dispatches"] - before["dispatches"] == 10
    assert 0 < after["dispatch_seconds"] - before["dispatch_seconds"] < 1.0
    assert after["dispatch_errors"] == before["dispatch_errors"]


def test_log_compiles_says_what_the_listed_programs_dispatches_cost(capsys):
    f, x = program("dispatch_probe_logged"), jnp.ones((96, 96))
    f(x), f(x)
    compile_guard.log_compiles()  # drains: the second dispatch has its stamp by the line
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith(compile_guard.COMPILES_LINE_PREFIX)]
    assert set(json.loads(lines[0][len(compile_guard.COMPILES_LINE_PREFIX):])) == {
        "count", "cache_hits", "uncached", "seconds"}
    (line,) = [l for l in lines if "dispatch_probe_logged:" in l]
    p = entry("dispatch_probe_logged")
    assert line.endswith(f"2 dispatches: device {p['device_s']:.3g} s (first "
                         f"{p['first_device_s']:.3g} s), gap {p['gap_s']:.3g} s "
                         f"(max {p['gap_max_s']:.3g} s)")
    assert all("dispatches" not in l for l in lines if "dispatch_probe_logged" not in l)


def test_dispatches_from_many_threads_lose_no_update():
    """More threads than cores, a short switch interval: every dispatch is
    counted and stamped, none twice, while the stamping thread works."""
    workers, rounds = 16, 100
    barrier, interval = threading.Barrier(workers), sys.getswitchinterval()

    def work(k: int):
        barrier.wait(timeout=30)
        for i in range(rounds):
            t = time.time()
            compile_guard.dispatched(f"dispatch_probe_thread_{k % 4}", k, i == 0, t, t, (),
                                     [Leaf(0.0)])

    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,)) for k in range(workers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(interval)
    assert compile_guard.drain(60)
    for k in range(4):
        p = entry(f"dispatch_probe_thread_{k}")
        assert (p["dispatches"], p["instances"], p["unstamped"]) == (4 * rounds, 4, 0)
        assert p["device_s"] >= 0 and p["gap_s"] >= 0
    stamps = [r["done"] for r in compile_guard.records() if r["phase"] == "dispatch"]
    assert len(stamps) == compile_guard.MAX_RECORDS and None not in stamps


def run_script(body: str, timeout: float = 120):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(body)], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_with_the_switch_off_nothing_is_recorded_and_no_thread_is_started():
    done = run_script("""
        import threading, jax, jax.numpy as jnp
        from dalle_pytorch_tpu.obs import scopes
        from dalle_pytorch_tpu.utils import compile_guard
        compile_guard.stamping = False
        compile_guard.install_listener()
        def dispatch_probe_off(x):
            return x + 1.0
        f = scopes.remembering(jax.jit(dispatch_probe_off))
        for _ in range(3):
            f(jnp.ones(4)).block_until_ready()
        p = compile_guard.programs()["dispatch_probe_off"]
        assert p["compiles"] == 1 and p["dispatches"] == 0 and p["instances"] == 0, p
        assert all(r["phase"] != "dispatch" for r in compile_guard.records())
        assert compile_guard.listener_cost()["dispatches"] == 0
        assert compile_guard._watcher is None
        assert [t.name for t in threading.enumerate() if t.name == "dispatch-stamps"] == []
        compile_guard.stamping = True
        f(jnp.ones(4))
        assert [t.name for t in threading.enumerate() if t.name == "dispatch-stamps"] != []
        print("held")
    """)
    assert done.returncode == 0 and done.stdout.strip() == "held", done.stderr[-2000:]


def test_the_process_exits_within_a_second_of_main_while_a_stamp_is_pending():
    done = run_script("""
        import time, jax, jax.numpy as jnp
        from dalle_pytorch_tpu.obs import scopes
        from dalle_pytorch_tpu.utils import compile_guard

        class Never:
            nbytes = 4
            def is_ready(self):
                return False

        def dispatch_probe_exit(x):
            return x + 1.0
        f = scopes.remembering(jax.jit(dispatch_probe_exit))
        f(jnp.ones(4)).block_until_ready()
        assert compile_guard.drain(30)
        t = time.time()
        compile_guard.dispatched("dispatch_probe_exit", 1, False, t, t, (), [Never()])
        assert not compile_guard.drain(0.05)  # it is pending, and stays so
        print(f"main returns at {time.time()!r}", flush=True)
    """)
    ended = time.time()
    assert done.returncode == 0 and done.stderr.strip() == "", done.stderr[-2000:]
    returned = float(done.stdout.strip().rsplit(" ", 1)[1])
    assert ended - returned < 1.0
