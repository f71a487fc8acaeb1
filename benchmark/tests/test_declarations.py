"""The declarations agree: `BENCHMARK.json`'s `per_layer` and the files of
`benchmark/metrics/` say the same of every metric, a metric is ONE thing
measured (reader, `params`, `moves`) whose `workloads` are the cells that
report it, and a cell's shares name each component once, so they add up.

Reads the JSON files and nothing else: no import of the program or of JAX
(`benchmark/harness.py` imports neither until a run needs them), so it runs
in well under a second wherever it is collected.
`cell_metrics(cell)` is what the per-configuration tests ask for a cell's
metrics, now that a file's name no longer says which cells it serves.
"""

import json
from collections import Counter

import pytest

from benchmark import harness

CAP = 128  # the per-layer metrics `BENCHMARK.json` may hold

with open(harness.ROOT.parent / "BENCHMARK.json") as f:
    DECLARED = json.load(f)
CELLS = [w["name"] for w in DECLARED["workloads"]]
PER_LAYER = {m["name"]: m for m in DECLARED["per_layer"]}
FILES = harness.metric_files()
METRICS = {n: s for n, s in FILES.items() if s.get("kind") != "end_to_end"}


def cell_metrics(cell: str, reader: str = None) -> dict:
    """The per-layer metric files that list `cell` (a file without a list is
    tried in every cell), optionally those of one reader."""
    return {n: s for n, s in METRICS.items()
            if cell in s.get("workloads", CELLS) and reader in (None, s["reader"])}


def reports(metric: str) -> list:
    """The cells that report an end-to-end metric (all, where it lists none)."""
    entry = next(m for m in DECLARED["end_to_end"] if m["name"] == metric)
    return entry.get("workloads", CELLS)


def test_the_declared_names_are_the_files_and_fit_the_cap():
    assert len(DECLARED["per_layer"]) == len(PER_LAYER) <= CAP  # no name twice
    assert set(PER_LAYER) == set(METRICS)
    assert {m["name"] for m in DECLARED["end_to_end"]} <= set(FILES) - set(METRICS)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_an_entry_says_what_its_file_says(name):
    entry, spec = PER_LAYER[name], METRICS[name]
    for key in ("unit", "better", "layer", "moves"):
        assert entry[key] == spec[key], key
    # `device_trace+program_table` in a file is `device_trace` to the driver
    assert entry["source"] == spec["source"].split("+")[0]
    assert entry["workloads"] == spec.get("workloads", CELLS)
    assert entry["workloads"] and set(entry["workloads"]) <= set(CELLS)
    assert len(set(entry["workloads"])) == len(entry["workloads"])
    # every cell of the list reports the end-to-end metric this one moves
    assert set(entry["workloads"]) <= set(reports(entry["moves"])), entry["moves"]


@pytest.mark.parametrize("name", sorted(METRICS))
def test_no_other_file_measures_the_same_thing(name):
    """One (reader, `params`, `moves`) is one metric: a copy that differs by a
    cell's suffix alone belongs in the first one's `workloads`."""
    key = lambda s: (s["reader"], json.dumps(s.get("params", {}), sort_keys=True), s["moves"])
    same = [n for n, s in METRICS.items() if key(s) == key(METRICS[name])]
    assert same == [name]


@pytest.mark.parametrize("cell", CELLS)
def test_a_cells_shares_name_each_component_once(cell):
    """Phases aside (`remat_pct`, `mtp_pct`: every component in one phase),
    so that the shares of a cell can add up to 100."""
    shares = {n: s["params"]["components"] for n, s in cell_metrics(cell, "component_share").items()
              if "phase" not in s["params"]}
    named = Counter(c for group in shares.values() for c in group)
    assert not {c: [n for n, group in shares.items() if c in group]
                for c, k in named.items() if k > 1}
    # and one join says how much of the window those shares are shares of
    assert len(cell_metrics(cell, "scope_join")) == (1 if shares else 0)
    assert cell_metrics(cell)  # a cell without a per-layer metric is unguarded
