"""CPU tests of the `dispatch_ledger` reader: its two forms over a hand-made
ledger, a ledger that has no dispatches (the parent's, with these benchmark
files laid over it) giving nothing and raising nothing, two traced rehearsals
that report the new metrics and say the `[dispatch_ledger]` line, and the
cells' cycles, on whose order the third metric's meaning rests.

Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`.
"""

from __future__ import annotations

import json
import types

import pytest

from benchmark import harness
from benchmark.readers import dispatch_ledger
from benchmark.tests.test_harness import last_line, run_cell

NAMES = ("prefill_device_s", "warmup_device_s", "sampled_batch_over_greedy")
FILES = {n: harness.load("metrics", n) for n in NAMES}
DECODE = ["pangu.decode.8k", "olmohybrid.decode.512", "kexaone.decode.16k",
          "deepseek32.decode.32k", "nemotron3.decode.8k", "zaya1.decode.8k"]
GENERATION = DECODE + ["paper64.generate"]


def row(**fields) -> dict:
    return dict({"traces": 1, "trace_s": 0.0, "lower_s": 0.0, "compiles": 0, "compile_s": 0.0,
                 "cache_hits": 0, "load_s": 0.0, "first_at": 1.0, "last_at": 2.0,
                 "dispatches": 0, "dispatch_s": 0.0, "device_s": 0.0, "first_device_s": 0.0,
                 "gap_s": 0.0, "gap_max_s": 0.0, "unstamped": 0, "instances": 0}, **fields)


def dispatch(program, instance, first, start, end, done, device_s, gap_s=0.0) -> dict:
    return {"program": program, "phase": "dispatch", "start": start, "end": end, "nested": False,
            "thread": 1, "instance": instance, "first": first, "done": done,
            "device_s": device_s, "gap_s": gap_s}


LEDGER = {
    "lm_extend": row(dispatches=4, dispatch_s=0.9, device_s=2.0, first_device_s=0.5, instances=1),
    "lm_place": row(dispatches=2, device_s=0.25, unstamped=1, instances=1),
    "lm_sample": row(trace_s=3.0, dispatches=5, dispatch_s=9.0, device_s=20.5,
                     first_device_s=8.5, gap_s=0.3, gap_max_s=0.2, instances=2),
    "lm_step": row(trace_s=5.0, compile_s=30.0),  # compiled, never dispatched through the wrapper
    "scope_table:lm_sample": row(trace_s=9.0),
}
RECORDS = [
    {"program": "lm_sample", "phase": "trace", "start": 10.0, "end": 13.0, "nested": False,
     "thread": 1},
    dispatch("lm_sample", 0, True, 10.0, 14.0, 18.0, 4.0),
    dispatch("lm_sample", 1, True, 18.0, 22.0, 26.5, 4.5),
    dispatch("lm_sample", 0, False, 27.0, 27.1, 31.1, 4.0, 0.1),
    dispatch("lm_sample", 1, False, 31.3, 31.3, 35.6, 4.3, 0.2),
    dispatch("lm_sample", 1, False, 35.6, 35.6, None, None, None),  # no stamp: not in a mean
    dispatch("lm_place", 0, True, 5.0, 5.5, 5.75, 0.25),
]


@pytest.fixture
def fake(monkeypatch):
    guard = types.SimpleNamespace(
        programs=lambda: {k: dict(v) for k, v in LEDGER.items()},
        records=lambda: [dict(r) for r in RECORDS],
        listener_cost=lambda: {"events": 12, "seconds": 1e-4, "dispatches": 11,
                               "dispatch_seconds": 2e-4, "dispatch_errors": 0},
        drain=lambda timeout: True,
    )
    monkeypatch.setattr(dispatch_ledger, "compile_guard", guard)
    return guard


@pytest.mark.parametrize("name,want", [
    ("prefill_device_s", 2.25),  # lm_extend + lm_place
    ("warmup_device_s", 8.5),  # the two instances' first dispatches
    ("sampled_batch_over_greedy", (4.5 + 4.3) / 2 / 4.0),
])
def test_the_metric_files_read_the_hand_made_ledger(fake, name, want):
    assert dispatch_ledger.read(FILES[name]["params"], {}) == pytest.approx(want)


def test_a_field_sum_takes_any_of_the_rows_fields(fake):
    got = dispatch_ledger.read({"program": "^lm_", "fields": ["dispatches", "unstamped"]}, {})
    assert got == 4 + 2 + 5 + 1  # `lm_step` was never dispatched: not among them


def test_says_the_dispatches_once_a_run(fake, capsys):
    ctx = {}
    for name in NAMES:
        dispatch_ledger.read(FILES[name]["params"], ctx)
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("[dispatch_ledger] ")]
    assert len(lines) == 1
    said = json.loads(lines[0][len("[dispatch_ledger] "):])
    assert [p["program"] for p in said["programs"]] == ["lm_extend", "lm_place", "lm_sample"]
    assert said["programs"][2]["first_device_s"] == 8.5
    assert said["dispatches"][0] == ["lm_sample", 0, True, 10.0, 14.0, 18.0, 4.0, 0.0]
    assert len(said["dispatches"]) == 6 and said["dispatches"][4][5:] == [None, None, None]
    assert said["device_s"] == pytest.approx(22.75) and said["unstamped"] == 1
    assert said["wall_s"] == pytest.approx(35.6 - 5.0)
    assert said["listener"]["dispatches"] == 11


@pytest.mark.parametrize("params", [
    {"program": "^no_such_program$", "fields": ["device_s"]},
    {"program": "^lm_step$", "fields": ["device_s"]},  # in the ledger, never dispatched
    {"program": "^lm_place$", "over": [1, 0]},  # one instance: nothing to compare
    {"program": "^lm_sample$", "over": [2, 0]},
])
def test_nothing_found_leaves_the_metric_out(fake, params):
    assert dispatch_ledger.read(params, {}) is None


def parents_row(**seconds) -> dict:  # a row as PR 35 to PR 48 keep it
    return dict({"traces": 1, "trace_s": 0.0, "lower_s": 0.0, "compiles": 0, "compile_s": 0.0,
                 "cache_hits": 0, "load_s": 0.0, "first_at": 1.0, "last_at": 2.0}, **seconds)


@pytest.mark.parametrize("guard", [
    None,
    types.SimpleNamespace(compile_count=lambda: 3),  # a guard older than its ledger
    types.SimpleNamespace(  # the parent: a ledger of compiles, no dispatch among its fields
        programs=lambda: {"lm_sample": parents_row(trace_s=3.0), "lm_prefill": parents_row()},
        records=lambda: [{"program": "lm_sample", "phase": "trace", "start": 1.0, "end": 4.0,
                          "nested": False, "thread": 1}],
        listener_cost=lambda: {"events": 12, "seconds": 1e-4}),
    types.SimpleNamespace(  # this ledger with its stamping switched off
        programs=lambda: {"lm_sample": row(trace_s=3.0)}, records=lambda: [],
        listener_cost=lambda: {}, drain=lambda timeout: True),
])
@pytest.mark.parametrize("name", NAMES)
def test_a_ledger_without_dispatches_reads_nothing_and_raises_nothing(monkeypatch, guard, name,
                                                                        capsys):
    monkeypatch.setattr(dispatch_ledger, "compile_guard", guard)
    assert dispatch_ledger.read(FILES[name]["params"], {}) is None
    assert "[dispatch_ledger]" not in capsys.readouterr().out


def test_the_files_say_what_benchmark_json_says_and_name_the_reader():
    with open(harness.ROOT.parent / "BENCHMARK.json") as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert list(declared)[-3:] == list(NAMES)  # appended, in this order
    for name, spec in FILES.items():
        assert spec["reader"] == "dispatch_ledger" and spec["layer"] == "model"
        for key in ("unit", "better", "source", "layer", "moves", "workloads"):
            assert spec[key] == declared[name][key]
    assert FILES["prefill_device_s"]["workloads"] == DECODE
    assert FILES["warmup_device_s"]["workloads"] == GENERATION
    assert FILES["sampled_batch_over_greedy"]["workloads"] == GENERATION
    assert FILES["warmup_device_s"]["params"]["program"] == FILES[
        "sampled_batch_over_greedy"]["params"]["program"]


@pytest.mark.parametrize("program,found", [
    ("lm_prefill", True), ("lm_extend", True), ("lm_place", True), ("lm_sample", False),
    ("scope_table:lm_prefill", False), ("lm_prefill_ref", False),
])
def test_the_prefill_regex_finds_the_prefill_programs_alone(program, found):
    import re

    assert bool(re.search(FILES["prefill_device_s"]["params"]["program"], program)) == found


@pytest.mark.parametrize("program,found", [
    ("lm_sample", True), ("sample_cached", True), ("sample_cached_batched", True),
    ("lm_prefill", False), ("scope_table:lm_sample", False), ("sample", False),
])
def test_the_sampler_regex_finds_the_samplers_alone(program, found):
    import re

    assert bool(re.search(FILES["warmup_device_s"]["params"]["program"], program)) == found


@pytest.mark.parametrize("cell", GENERATION)
def test_a_listed_cells_cycle_starts_greedy_and_goes_on_filtered(cell):
    """`sampled_batch_over_greedy` is instance 1 over instance 0: the loops warm
    the cycle's settings in its order, so `batches[0]` has to be the greedy one."""
    batches = harness.load("workloads", cell)["job"]["batches"]
    assert len(batches) == 2
    assert float(batches[0]["filter_thres"]) >= 1.0 > float(batches[1]["filter_thres"])


@pytest.mark.parametrize("cell,names", [
    ("_tiny.generate_lm", NAMES),
    ("_tiny.generate", NAMES[1:]),  # no prefill program: the sampler prefills inside
])
def test_a_traced_rehearsal_reports_the_cells_new_metrics_and_says_the_line(cell, names):
    assert harness.load("workloads", cell)["stands_for"] in FILES[names[-1]]["workloads"]
    p = run_cell(cell, trace=1, seconds=2, seed=3000000019)
    line = last_line(p)
    assert line["correct"] is True and set(names) <= set(line["metrics"])
    assert set(NAMES) - set(names) <= set(NAMES) - set(line["metrics"])
    assert line["metrics"]["warmup_device_s"]["value"] > 0
    assert 0.2 < line["metrics"]["sampled_batch_over_greedy"]["value"] < 5.0
    (said,) = [json.loads(l[len("[dispatch_ledger] "):]) for l in p.stdout.splitlines()
               if l.startswith("[dispatch_ledger] ")]
    rows = {r["program"]: r for r in said["programs"]}
    sampler = rows["lm_sample" if "lm_sample" in rows else "sample_cached"]
    assert sampler["instances"] == 2 and sampler["dispatches"] >= 3
    assert said["unstamped"] == 0 and said["listener"]["dispatch_errors"] == 0
    assert sum(1 for d in said["dispatches"] if d[2]) == len(rows) + 1  # the firsts
    for program, instance, first, start, end, done, device_s, gap_s in said["dispatches"]:
        assert start <= end <= done and device_s >= 0 and gap_s >= 0
    assert said["device_s"] <= said["wall_s"]
    # the compile ledger's line carries the dispatches of 10 ms or more beside the compiles
    (led,) = [json.loads(l[len("[compile_ledger] "):]) for l in p.stdout.splitlines()
              if l.startswith("[compile_ledger] ")]
    assert any(phase == "dispatch" for _, phase, *_ in led["timeline"])
