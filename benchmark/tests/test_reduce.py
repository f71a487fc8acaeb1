"""The trace reduction, on hand-made events and on a small recorded trace."""

from __future__ import annotations

import gzip
import importlib
import json
from pathlib import Path

import pytest

from benchmark.trace import costs, reduce

DATA = Path(__file__).resolve().parent / "data"


def test_busy_is_the_union_and_gaps_go_to_the_host_span():
    events = {
        "devices": [{"name": "/device:TPU:0", "modules": [("jit_step(1)", 1.0, 4.0)], "ops": [
            ("%while.1 = ...", 1.0, 3.0),      # a loop ...
            ("%fusion.1 = ...", 1.0, 1.0),     # ... and its body, nested
            ("%fusion.2 = ...", 2.5, 1.5),
            ("%copy.3 = ...", 4.5, 0.5),       # after a gap of 0.5
        ]}],
        "spans": [("window", 0.0, 6.0), ("feed", 4.0, 0.4), ("wait", 5.0, 1.0)],
    }
    r = reduce.reduce(events)
    assert r["window_s"] == pytest.approx(6.0)
    assert r["busy_s"] == pytest.approx(3.5)  # [1, 4) + [4.5, 5)
    assert r["ops"]["%while.1 = ..."]["self_seconds"] == pytest.approx(0.5)
    assert dict(r["device_ops"])["fusion.#"] == pytest.approx(2.5)
    gaps = dict(r["idle_gaps"])
    assert gaps["feed"] == pytest.approx(0.4)        # of the gap [4, 4.5)
    assert gaps["wait"] == pytest.approx(1.0)        # the tail [5, 6)
    assert gaps["unattributed"] == pytest.approx(1.1)  # [0, 1) and 0.1 of [4, 4.5)
    assert r["modules"]["jit_step(1)"] == {"count": 1.0, "seconds": 4.0}


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        reduce.reduce({"devices": [{"name": "d", "ops": [], "modules": []}], "spans": []})


def test_unlisted_device_raises():
    assert costs.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        costs.peaks("cpu")


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json.gz")))
def test_recorded_trace(name):
    """A slice of a real trace of the cell (tests/record_trace.py): the
    reduction finds the device, the kernels by the names in the metric files,
    and no share passes 100%."""
    with gzip.open(DATA / name, "rt") as f:
        events = json.load(f)
    r = reduce.reduce(events)
    assert 0 < r["busy_s"] <= r["window_s"]
    cell = name.split(".trace")[0]
    ctx = {"trace": r, "counters": {}, "device": {"kind": "TPU v5 lite"}, "costs": costs,
           "shapes": json.loads((DATA / f"{cell}.shapes.json").read_text())}
    metrics = Path(__file__).resolve().parents[1] / "metrics"
    found = 0
    for path in metrics.glob("*.json"):
        spec = json.loads(path.read_text())
        if cell not in spec.get("workloads", []) or spec.get("source") != "device_trace":
            continue
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(spec["params"], ctx)
        if spec["unit"] == "%":  # a time per program is cut short by the slice
            assert value is not None and 0 < value <= 100, (path.name, value)
        found += bool(value)
    assert found
