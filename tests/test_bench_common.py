"""bench_common harness: a bench measures the chip or it fails.

The contract these tests hold: ONE JSON line on stdout however the
workload ends, and a NON-ZERO exit on every failure — no accelerator, a
crash, a hang. Nothing retries on the CPU, and a profile that fails is a
failed bench, not a cue to try the next one."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

#: the generated parents stand in for a machine with a chip: the probe
#: child would (rightly) report this sandbox's CPU and end the run
FAKE_CHIP = (
    "import bench_common\n"
    "bench_common.probe_device = lambda timeout=0: "
    "{'platform': 'tpu', 'device_kind': 'TPU v5 lite', 'n_devices': 1}\n"
)

GOOD_CHILD = (
    "import json, os\n"
    "print(json.dumps({'metric': 'm', 'value': 1, 'unit': 'u', 'ok': True,"
    " 'vs_baseline': 1.0, 'mode': os.environ.get('FAKE_MODE')}))\n"
)


def run_parent(tmp_path, script_body, parent_body, fake_chip=True):
    """Run a tiny parent that calls run_guarded on a fake child script.
    Returns (exit code, the one JSON line)."""
    child = tmp_path / "fake_bench.py"
    child.write_text(script_body)
    parent = tmp_path / "parent.py"
    parent.write_text(
        "import sys\n"
        f"sys.path.insert(0, {str(REPO)!r})\n"
        f"CHILD = {str(child)!r}\n"
        + (FAKE_CHIP if fake_chip else "")
        + "from bench_common import run_guarded\n" + parent_body
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(parent)], capture_output=True, text=True,
        timeout=180, env=env,
    )
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, f"expected ONE JSON line, got: {lines} / {proc.stderr}"
    return proc.returncode, json.loads(lines[0])


class TestRunGuarded:
    def test_success_forwards_the_childs_line_with_its_profile(self, tmp_path):
        rc, result = run_parent(
            tmp_path, GOOD_CHILD,
            "run_guarded('m', 'u', CHILD, child_timeout=60,\n"
            "    profile=('fast', {'FAKE_MODE': 'fast'}))\n",
        )
        assert rc == 0 and result["ok"] is True
        assert result["profile"] == "fast" and result["mode"] == "fast"
        assert "attempts" not in result

    def test_failed_profile_is_a_failed_bench_not_a_fall_through(self, tmp_path):
        marker = tmp_path / "runs"
        script = (
            "import sys\n"
            f"open({str(marker)!r}, 'a').write('x')\n"
            "sys.stderr.write('Mosaic failed to compile the kernel')\n"
            "sys.exit(1)\n"
        )
        rc, result = run_parent(
            tmp_path, script,
            "run_guarded('m', 'u', CHILD, child_timeout=60,\n"
            "    profile=('flash', {'FAKE_MODE': 'flash'}),\n"
            "    oom_ladder=[{'BENCH_ACCUM': '2'}])\n",
        )
        assert rc != 0
        assert result["ok"] is False and result["value"] == 0
        assert "Mosaic failed" in result["error"]
        assert marker.read_text() == "x"  # one attempt: not an OOM, no retry

    def test_oom_ladder_keeps_the_batch_and_notes_the_attempts(self, tmp_path):
        # child OOMs unless BENCH_ACCUM >= 2
        script = (
            "import json, os, sys\n"
            "if int(os.environ.get('BENCH_ACCUM', '1')) < 2:\n"
            "    sys.stderr.write('RESOURCE_EXHAUSTED: out of memory')\n"
            "    sys.exit(1)\n"
            "print(json.dumps({'metric': 'm', 'value': 2, 'unit': 'u',"
            " 'ok': True, 'vs_baseline': 1.0}))\n"
        )
        rc, result = run_parent(
            tmp_path, script,
            "def mb(env):\n"
            "    b = int(env.get('BENCH_BATCH', '16'))\n"
            "    a = int(env.get('BENCH_ACCUM', '1'))\n"
            "    return b // a if a > 0 and b % a == 0 else None\n"
            "run_guarded('m', 'u', CHILD, child_timeout=60,\n"
            "    oom_ladder=[{'BENCH_ACCUM': '2'}, {'BENCH_ACCUM': '4'}],\n"
            "    microbatch_of=mb)\n",
        )
        assert rc == 0
        assert result["ok"] is True and result["value"] == 2
        assert result["attempts"] == 2

    def test_hung_child_is_a_failure_line_and_nonzero_exit(self, tmp_path):
        rc, result = run_parent(
            tmp_path, "import time; time.sleep(60)\n",
            "run_guarded('m', 'u', CHILD, child_timeout=2)\n",
        )
        assert rc != 0
        assert result["ok"] is False and "timed out" in result["error"]

    def test_no_accelerator_fails_without_running_the_child(self, tmp_path):
        """The REAL probe, on this sandbox's CPU: the bench refuses — it
        does not shrink the workload and report a CPU number."""
        marker = tmp_path / "ran"
        script = f"open({str(marker)!r}, 'w').write('x')\n" + GOOD_CHILD
        rc, result = run_parent(
            tmp_path, script,
            "run_guarded('m', 'u', CHILD, child_timeout=60)\n",
            fake_chip=False,
        )
        assert rc != 0
        assert result["ok"] is False and "no accelerator" in result["error"]
        assert not marker.exists()


def test_bench_parents_run_one_named_profile():
    """bench.py's configurations are a table to pick from (BENCH_PROFILE),
    not a ladder to fall down: importing it as a parent stays off jax."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import bench, bench_common\n"
        "assert bench.DEFAULT_PROFILE in bench.PROFILES\n"
        "assert 'jax' not in sys.modules\n" % str(REPO)
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
