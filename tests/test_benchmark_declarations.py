"""The benchmark's declarations, held by the suite that guards every PR:
`benchmark/tests/test_declarations.py`'s tests, which read the JSON files and
nothing else (tier-1 does not collect `benchmark/tests`). Beside them, what a
PR that ADDS a cell leaves behind: it may edit no file that is there, so where
an existing file already measures the thing it adds a copy that lists the new
cell alone, LABELLED `params.copy_of`, for the next `benchmark` PR to fold into
the original's list; a label has to be true."""

from benchmark.tests.test_declarations import *  # noqa: F401,F403
from benchmark.tests.test_declarations import METRICS

COPIES = sorted(n for n, s in METRICS.items() if "copy_of" in s.get("params", {}))


def test_a_labelled_copy_is_its_original_for_cells_the_original_does_not_list():
    for name in COPIES:
        spec = METRICS[name]
        params = dict(spec["params"])
        original = METRICS[params.pop("copy_of")]
        assert "copy_of" not in original.get("params", {}), name
        for key in ("reader", "moves", "unit", "better", "layer", "source"):
            assert spec[key] == original[key], (name, key)
        assert params == original.get("params", {}), name
        assert spec["workloads"] and not set(spec["workloads"]) & set(original["workloads"]), name
