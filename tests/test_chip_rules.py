"""The rules that keep a chip run honest, checked on the CPU:

  * ONE helper places jax's persistent compile cache, and the environment
    (`JAX_COMPILATION_CACHE_DIR`) outranks every path in code;
  * parents that start chip-needing children never import jax;
  * `chip_smoke.py` fails on anything but the chip, and says so.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(code, env=None, **kw):
    """A fresh interpreter (the rules are about process-wide state)."""
    full = dict(os.environ, JAX_PLATFORMS="cpu")
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(env or {})
    return subprocess.run(
        [sys.executable, "-c", f"import sys; sys.path.insert(0, {str(REPO)!r})\n" + code],
        capture_output=True, text=True, timeout=300, env=full, **kw,
    )


COMPILE_ONE = (
    "import jax, jax.numpy as jnp\n"
    "jax.jit(lambda x: jnp.sin(x) @ x + 3)(jnp.ones((16, 16))).block_until_ready()\n"
)


class TestXlaCachePlacement:
    def test_unset_means_the_fixed_in_checkout_path(self, monkeypatch):
        from dalle_pytorch_tpu.utils.compile_cache import XLA_CACHE_ENV, xla_cache_dir

        monkeypatch.delenv(XLA_CACHE_ENV, raising=False)
        assert xla_cache_dir() == REPO / ".jax_cache"
        assert xla_cache_dir(default="/x/y") == Path("/x/y")
        monkeypatch.setenv(XLA_CACHE_ENV, "/from/env")
        assert xla_cache_dir() == xla_cache_dir(default="/x/y") == Path("/from/env")

    def test_environment_outranks_the_helper(self, tmp_path):
        env_dir = tmp_path / "from_env"
        proc = _run(
            "from dalle_pytorch_tpu.utils.compile_cache import enable_xla_cache\n"
            "import jax\n"
            "print(enable_xla_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n" + COMPILE_ONE,
            env={"JAX_COMPILATION_CACHE_DIR": str(env_dir)},
        )
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.split() == [str(env_dir), str(env_dir)]
        assert any(env_dir.iterdir()), "the run cached nothing where the env said"

    def test_compile_cache_install_leaves_the_environments_directory(self, tmp_path):
        """`serve.py --compile_cache D` with the variable set: XLA entries
        go where the environment says, D keeps only AOT artefacts (no
        D/xla), and uninstall() does not clear the environment's choice."""
        env_dir, d = tmp_path / "from_env", tmp_path / "D"
        proc = _run(
            "from dalle_pytorch_tpu.utils.compile_cache import CompileCache\n"
            "import jax\n"
            f"cache = CompileCache({str(d)!r}).install()\n" + COMPILE_ONE +
            "CompileCache.uninstall()\n"
            "print(jax.config.jax_compilation_cache_dir)\n",
            env={"JAX_COMPILATION_CACHE_DIR": str(env_dir)},
        )
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.strip() == str(env_dir)
        assert any(env_dir.iterdir())
        assert (d / "aot").is_dir() and not (d / "xla").exists()

    def test_compile_cache_install_defaults_to_its_own_xla_dir(self, tmp_path):
        d = tmp_path / "D"
        proc = _run(
            "from dalle_pytorch_tpu.utils.compile_cache import CompileCache\n"
            "import jax\n"
            f"CompileCache({str(d)!r}).install()\n" + COMPILE_ONE +
            "print(jax.config.jax_compilation_cache_dir)\n"
            "CompileCache.uninstall()\n"
            "print(jax.config.jax_compilation_cache_dir)\n",
        )
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.split() == [str(d / "xla"), "None"]
        assert any((d / "xla").iterdir())

    def test_second_run_hits_and_the_receipt_says_so(self, tmp_path):
        """jax 0.9.0: a persistent-cache hit still fires the
        backend-compile event, so hits are counted separately and
        `uncached` is what actually paid XLA time."""
        code = (
            "from dalle_pytorch_tpu.utils.compile_cache import enable_xla_cache\n"
            "from dalle_pytorch_tpu.utils.compile_guard import log_compiles\n"
            "enable_xla_cache()\n" + COMPILE_ONE + "log_compiles()\n"
        )
        receipts = []
        for _ in range(2):
            proc = _run(code, env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
            assert proc.returncode == 0, proc.stderr
            line = [l for l in proc.stdout.splitlines() if l.startswith("[compiles] ")]
            receipts.append(json.loads(line[0][len("[compiles] "):]))
        cold, warm = receipts
        assert cold["uncached"] == cold["count"] > 0 and cold["cache_hits"] == 0
        assert warm["uncached"] == 0 and warm["cache_hits"] == warm["count"] == cold["count"]


class TestParentsStayOffJax:
    """A process that holds the chip starves the child it spawns, so the
    parents of chip-needing children do not even import jax."""

    def test_serve_supervise_parent(self):
        proc = _run(
            "import subprocess\n"
            "import serve\n"
            "seen = []\n"
            "class FakePopen:\n"
            "    def __init__(self, *a, **k):\n"
            "        seen.append('jax' in sys.modules)\n"
            "        raise KeyboardInterrupt\n"
            "subprocess.Popen = FakePopen\n"
            "try:\n"
            "    serve.main(['--dalle_path', 'x.npz', '--supervise', '--port', '8123',\n"
            "                '--engine', 'continuous', '--mesh', 'dp=1,tp=2'])\n"
            "except KeyboardInterrupt:\n"
            "    pass\n"
            "print(seen)\n"
        )
        assert proc.stdout.strip().splitlines()[-1] == "[False]", proc.stdout + proc.stderr

    def test_launch_parent(self):
        proc = _run(
            "import subprocess\n"
            "import launch\n"
            "seen = []\n"
            "class FakePopen:\n"
            "    def __init__(self, *a, **k):\n"
            "        seen.append('jax' in sys.modules)\n"
            "    def wait(self):\n"
            "        return 0\n"
            "    def poll(self):\n"
            "        return 0\n"
            "subprocess.Popen = FakePopen\n"
            "sys.argv = ['launch.py', 'train_dalle.py']\n"
            "rc = launch.main()\n"
            "print(rc, seen)\n"
        )
        assert proc.stdout.strip().splitlines()[-1] == "0 [False]", proc.stdout + proc.stderr

    @pytest.mark.parametrize("module", [
        "chip_smoke", "dalle_pytorch_tpu.serving.supervisor",
        "dalle_pytorch_tpu.serving.router", "dalle_pytorch_tpu.training.metrics",
    ])
    def test_import_does_not_pull_jax(self, module):
        proc = _run(f"import {module}\nprint('jax' in sys.modules)\n")
        assert proc.stdout.strip() == "False", proc.stderr

    def test_lazy_package_exports_still_resolve(self):
        import dalle_pytorch_tpu as dt
        from dalle_pytorch_tpu.serving import ContinuousEngine, parse_mesh_shape
        from dalle_pytorch_tpu.training import TrainState
        from dalle_pytorch_tpu.utils import param_count

        assert dt.DALLE.__name__ == "DALLE" and "DALLE" in dir(dt)
        assert callable(parse_mesh_shape) and callable(param_count)
        assert ContinuousEngine.__name__ == "ContinuousEngine"
        assert TrainState.__name__ == "TrainState"
        with pytest.raises(AttributeError):
            dt.no_such_name


class TestChipSmokeOffTheChip:
    def test_cpu_run_fails_and_never_reports_ok(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, str(REPO / "chip_smoke.py")],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert proc.returncode != 0
        assert '"ok": true' not in proc.stdout
        last = json.loads(proc.stdout.strip().splitlines()[-1])
        assert last["ok"] is False and last["phase"] == "preflight"
        assert "cpu" in last["error"]

    def test_alone_in_a_directory_it_fails(self, tmp_path):
        """Without the program beside it there is nothing to prove."""
        (tmp_path / "chip_smoke.py").write_text((REPO / "chip_smoke.py").read_text())
        proc = subprocess.run(
            [sys.executable, str(tmp_path / "chip_smoke.py")],
            capture_output=True, text=True, timeout=300, cwd=tmp_path,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert proc.returncode != 0 and '"ok": true' not in proc.stdout

    def test_device_check_refuses_other_platforms_and_counts(self):
        sys.path.insert(0, str(REPO))
        import chip_smoke

        smoke = chip_smoke.Smoke(chip_smoke.FLAGSHIP, chips=1, rehearse=False)
        ok = '[device] {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}\n'
        assert smoke.check_device("p", "noise\n" + ok)["kind"] == "TPU v5 lite"
        for bad in (
            '[device] {"platform": "cpu", "kind": "cpu", "count": 1}\n',
            '[device] {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}\n',
            "no device line at all\n",
        ):
            with pytest.raises(chip_smoke.SmokeFailure):
                smoke.check_device("p", bad)

    def test_a_failed_phase_is_not_carried_past(self, monkeypatch, capsys, tmp_path):
        """Kill one phase (here: the trainer finds no VAE checkpoint) and
        the run ends non-zero with ok false, naming the phase."""
        sys.path.insert(0, str(REPO))
        import chip_smoke

        monkeypatch.setattr(chip_smoke, "OUT", tmp_path / "out")
        monkeypatch.setattr(chip_smoke.shutil, "rmtree", lambda *a, **k: None)

        def boom(self):
            self.device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
            raise chip_smoke.SmokeFailure("train_dalle", "exit 1: no such file")

        monkeypatch.setattr(chip_smoke.Smoke, "one_chip", boom)
        assert chip_smoke.main([]) == 1
        last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert last["ok"] is False and last["phase"] == "train_dalle"
