"""CPU tests of the harness, on the `_tiny` rehearsal files.

Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`. The
repo's tier-1 command does not collect this directory.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(cell, trace=0, seconds=1, seed=3, bench=BENCH, check=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    p = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        capture_output=True, text=True, env=env, cwd=str(bench.parent), timeout=600,
    )
    if check:
        assert p.returncode == 0, p.stderr[-3000:]
    return p


def last_line(p):
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,metric", [
    ("_tiny.train", "train_tokens_per_s"),
    ("_tiny.generate", "generate_tokens_per_s"),
])
def test_loop_end_to_end(cell, metric):
    line = last_line(run_cell(cell))
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", metric}
    assert line["metrics"][metric]["value"] > 0
    assert line["device"]["platform"] == "cpu"  # a rehearsal says where it ran


@pytest.mark.parametrize("cell", ["_tiny.train", "_tiny.generate"])
def test_traced_run_reports_layers_and_breakdown(cell):
    line = last_line(run_cell(cell, trace=1))
    assert set(line) == RESULT_KEYS | {"breakdown"}
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert line["device"]["busy_s"] <= line["device"]["window_s"] * 1.001
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(line["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("cell", ["flagship.train", "paper64.generate"])
def test_real_cell_fails_off_the_chip(cell):
    p = run_cell(cell, check=False)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout  # and prints no result


def test_unknown_cell_fails():
    assert run_cell("no.such.cell", check=False).returncode != 0


def test_files_dropped_in_are_found_without_a_code_edit(tmp_path):
    """A configuration, a cell and a per-layer metric (existing reader) are
    added as new files only."""
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("out", "cache", "__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cfg = json.loads((bench / "configs" / "_tiny.json").read_text())
    cfg["name"] = "_other"
    cfg["model"]["depth"] = 2
    (bench / "configs" / "_other.json").write_text(json.dumps(cfg))
    wl = json.loads((bench / "workloads" / "_tiny.train.json").read_text())
    wl["config"] = "_other"
    wl["stands_for"] = "_other.train"
    (bench / "workloads" / "_other.train.json").write_text(json.dumps(wl))
    (bench / "metrics" / "steps_done.json").write_text(json.dumps({
        "layer": "model", "unit": "count", "better": "higher", "source": "program_counter",
        "moves": "train_tokens_per_s", "workloads": ["_other.train"],
        "reader": "counter", "params": {"counter": "steps"},
    }))
    line = last_line(run_cell("_other.train", trace=1, bench=bench))
    assert line["metrics"]["steps_done"]["value"] > 0
    # metrics of other cells do not leak in, and nothing that was there changed
    assert "input_wait_pct" not in line["metrics"]
    for p, content in before.items():
        assert p.read_bytes() == content
