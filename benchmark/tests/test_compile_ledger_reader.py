"""CPU tests of the `compile_ledger` and `kernel_bodies` readers over a fake
ledger: what they sum, what they leave out, and that a program without a
ledger gives nothing and raises nothing.

Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`.
"""

from __future__ import annotations

import json
import types

import pytest

from benchmark import harness
from benchmark.readers import compile_ledger, kernel_bodies

PROGRAM = harness.load("metrics", "program_trace_lower_s")["params"]["program"]


def row(**seconds) -> dict:
    return dict({"traces": 1, "trace_s": 0.0, "lower_s": 0.0, "compiles": 0, "compile_s": 0.0,
                 "cache_hits": 0, "load_s": 0.0, "first_at": 1.0, "last_at": 2.0}, **seconds)


LEDGER = {
    "lm_sample": row(trace_s=3.0, lower_s=1.0, compile_s=41.2, compiles=2),
    "lm_prefill": row(trace_s=2.0, lower_s=0.5, load_s=0.31, cache_hits=1),
    "scope_table:lm_sample": row(trace_s=9.0, lower_s=9.0, load_s=9.0),  # after the window
    "<lambda>": row(trace_s=5.0, compile_s=7.0),  # the reference's
    "sample": row(trace_s=0.004),  # under 10 ms: not said
}


RECORDS = [
    {"program": "lm_prefill", "phase": "trace", "start": 10.0, "end": 12.0, "nested": False},
    {"program": "_where", "phase": "trace", "start": 10.5, "end": 11.5, "nested": True},
    {"program": "lm_prefill", "phase": "lower", "start": 12.0, "end": 12.005, "nested": False},
    {"program": "lm_prefill", "phase": "load", "start": 12.5, "end": 12.81, "nested": False},
    {"program": "iota", "phase": "compile", "start": 11.0, "end": 11.001, "nested": True},
]


@pytest.fixture
def fake(monkeypatch):
    guard = types.SimpleNamespace(
        programs=lambda: {k: dict(v) for k, v in LEDGER.items()},
        listener_cost=lambda: {"events": 12, "seconds": 1e-4},
        records=lambda: [dict(r, thread=1) for r in RECORDS],
    )
    monkeypatch.setattr(compile_ledger, "compile_guard", guard)
    return guard


@pytest.mark.parametrize("fields,want", [
    (["trace_s", "lower_s"], 6.5),
    (["compile_s"], 41.2),
    (["load_s"], 0.31),
    (["compiles", "cache_hits"], 3),
])
def test_sums_the_fields_of_the_programs_the_regex_finds(fake, fields, want, capsys):
    got = compile_ledger.read({"program": PROGRAM, "fields": fields}, {})
    assert got == pytest.approx(want)


def test_says_the_ledger_once_a_run(fake, capsys):
    ctx = {}
    for _ in range(3):
        compile_ledger.read({"program": PROGRAM, "fields": ["load_s"]}, ctx)
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("[compile_ledger] ")]
    assert len(lines) == 1
    said = json.loads(lines[0][len("[compile_ledger] "):])
    assert [(p["program"], p["matched"]) for p in said["programs"]] == [
        ("lm_sample", True), ("scope_table:lm_sample", False), ("<lambda>", False),
        ("lm_prefill", True)]
    assert said["names"] == 5 and said["listener"] == {"events": 12, "seconds": 1e-4}
    assert said["all_programs"]["trace_s"] == pytest.approx(19.004)
    # backend events always, traces and lowerings from 10 ms up and top level only
    assert said["records"] == 5 and said["timeline"] == [
        ["lm_prefill", "trace", 10.0, 12.0, False], ["lm_prefill", "load", 12.5, 12.81, False],
        ["iota", "compile", 11.0, 11.001, True]]


@pytest.mark.parametrize("name", ["step", "lm_step", "sample_cached", "sample_cached_batched",
                                  "lm_sample", "lm_prefill"])
def test_the_metric_files_regex_finds_the_windows_programs(name):
    import re

    assert re.search(PROGRAM, name)
    for other in (f"scope_table:{name}", f"{name}_ref", f"_{name}", "<lambda>", "init", "make"):
        assert not re.search(PROGRAM, other)


def test_a_regex_that_finds_nothing_leaves_the_metric_out(fake):
    assert compile_ledger.read({"program": "^no_such_program$", "fields": ["load_s"]}, {}) is None


@pytest.mark.parametrize("guard", [None, types.SimpleNamespace(compile_count=lambda: 3)])
def test_a_program_without_a_ledger_reads_nothing(monkeypatch, guard, capsys):
    monkeypatch.setattr(compile_ledger, "compile_guard", guard)
    assert compile_ledger.read({"program": PROGRAM, "fields": ["load_s"]}, {}) is None
    assert "[compile_ledger]" not in capsys.readouterr().out


def test_the_three_metric_files_share_one_regex_and_split_the_fields():
    files = {n: harness.load("metrics", n) for n in
             ("program_trace_lower_s", "program_compile_s", "program_cache_load_s")}
    assert {f["params"]["program"] for f in files.values()} == {PROGRAM}
    assert [f["params"]["fields"] for f in files.values()] == [
        ["trace_s", "lower_s"], ["compile_s"], ["load_s"]]
    with open(harness.ROOT.parent / "BENCHMARK.json") as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, spec in dict(files, flash_kernel_bodies=harness.load(
            "metrics", "flash_kernel_bodies")).items():
        assert spec["reader"] in ("compile_ledger", "kernel_bodies")
        for key in ("unit", "better", "source", "layer", "moves", "workloads"):
            assert spec[key] == declared[name][key]


def test_kernel_bodies_reads_the_programs_counter():
    from dalle_pytorch_tpu.ops import pallas_attention

    assert kernel_bodies.read({}, {}) == pallas_attention.kernel_bodies
