"""The two readers that attribute device time through the program's own
table (`readers/component_share.py`, `readers/scope_join.py`), on a small
recorded pair per cell cut from a traced chip run of PR 24
(`tests/record_named.py`): a slice of the device trace, and the part of the
program's table the slice needs. The pairs live under `data/named/`:
`test_reduce.py` takes every `data/*.json.gz` for a recording that a trace
alone can be read from, which these are not (they need the table).
"""

from __future__ import annotations

import gzip
import importlib
import json
from pathlib import Path

import pytest

from benchmark.tests.test_declarations import cell_metrics
from benchmark.trace import costs, reduce
from dalle_pytorch_tpu.obs import scopes

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data" / "named"
CELLS = {"flagship.train": "step", "paper64.generate": "sample_cached"}


def context(cell: str) -> dict:
    with gzip.open(DATA / f"{cell}.trace.json.gz", "rt") as f:
        trace = reduce.reduce(json.load(f))
    return {"trace": trace, "counters": {}, "shapes": {}, "costs": costs,
            "device": {"kind": "TPU v5 lite"}}


def table(cell: str) -> dict:
    return json.loads((DATA / f"{cell}.scopes.json").read_text())


def metric_files(cell: str, reader: str):
    return cell_metrics(cell, reader).items()


def read(spec: dict, ctx: dict):
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(spec["params"], ctx)


@pytest.fixture(autouse=True)
def clean():
    scopes.forget()
    yield
    scopes.forget()


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_components_of_one_program_sum_to_100(cell):
    scopes.keep_table(CELLS[cell], table(cell))
    ctx = context(cell)
    (name, spec), = metric_files(cell, "scope_join")
    assert read(spec, ctx) >= 98.0
    values = {name: read(spec, ctx) for name, spec in metric_files(cell, "component_share")}
    assert values and all(v is not None and 0 <= v <= 100 for v in values.values()), values
    program = next(iter(metric_files(cell, "component_share")))[1]["params"]["program"]
    every = {c: read({"reader": "component_share",
                      "params": {"program": program, "components": [c]}}, ctx)
             for c in scopes.COMPONENTS}
    assert sum(every.values()) == pytest.approx(100.0, abs=0.1)
    assert scopes.lowered == 0  # a recorded table is read, nothing is lowered


def test_the_kernel_share_is_the_signature_metrics_share():
    """`attn_kernel_pct.train` against the kernels `flash_train_step_pct`
    finds by their output signature: the same trace read the same way. Over
    the same denominator, because a slice this short cuts kernels at its
    edges, where `kernel_share` divides whole events by clipped busy time
    (on the whole trace the two metrics read 49.640 and 49.640, PERF.md)."""
    import re

    cell = "flagship.train"
    scopes.keep_table("step", table(cell))
    ctx = context(cell)
    by_name = read(json.loads((BENCH / "metrics" / "attn_kernel_pct.train.json").read_text()), ctx)
    spec = json.loads((BENCH / "metrics" / "flash_train_step_pct.json").read_text())
    ops = ctx["trace"]["ops"]
    found = sum(row["seconds"] for name, row in ops.items()
                if any(re.search(k, name) for k in spec["params"]["kernels"]))
    by_signature = 100.0 * found / sum(row["seconds"] for row in ops.values())
    assert read(spec, ctx) is not None
    assert by_name == pytest.approx(by_signature, abs=0.01)
    # and the three kernels are told apart by name alone
    for kernel in ("fwd_flash", "dq_flash", "dkv_flash"):
        assert any(name.startswith(f"%{kernel}.") for name in ops), kernel


def test_the_generate_cells_copies_have_no_owner():
    """XLA's two whole-cache copies per token step carry no `op_name`: they
    are `unscoped`, and that reading is the finding (PERF.md), not a gap."""
    cell = "paper64.generate"
    scopes.keep_table("sample_cached", table(cell))
    ctx = context(cell)
    unscoped = read(json.loads((BENCH / "metrics" / "unscoped_pct.gen.json").read_text()), ctx)
    # the recording is PR 24's, when a token step still copied the cache; the
    # metric that found those copies by shape read nothing from PR 28 on and
    # is retired (PR 46), so the test finds them itself
    whole_cache = {"reader": "kernel_share",
                   "params": {"kernels": [r"^%copy\.\d+ = bf16\[64,\d+,16,1281,64\]"]}}
    copies = read(whole_cache, ctx)
    assert unscoped >= copies > 20


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_table_of_another_program_reads_nothing(cell):
    other = next(c for c in CELLS if c != cell)
    scopes.keep_table(CELLS[cell], table(other))  # the right name, the wrong program
    ctx = context(cell)
    (name, spec), = metric_files(cell, "scope_join")
    assert read(spec, ctx) < 50.0  # the placed share itself is reported ...
    for name, spec in metric_files(cell, "component_share"):
        assert read(spec, ctx) is None, name  # ... and no component is


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_no_program_remembered_reads_nothing_and_does_not_raise(cell):
    ctx = context(cell)
    for reader in ("scope_join", "component_share"):
        for name, spec in metric_files(cell, reader):
            assert read(spec, ctx) is None, name
    ctx["trace"] = None  # an untraced run
    for name, spec in metric_files(cell, "component_share"):
        assert read(spec, ctx) is None
