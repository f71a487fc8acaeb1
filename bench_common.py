"""Parent/child harness of the bench entry points.

One process per chip: the bench parent never touches a JAX backend. It
asks a probe child which device there is, then runs the measurement in a
second child (`script --child`) after the probe has exited:

  parent (no jax)
    |- probe child: tiny matmul -> platform / device kind / device count
    `- measurement child: the real workload, under a time limit

A bench measures the chip or it fails: no accelerator, a crash, a hang or
an OOM that the accumulation ladder cannot absorb all end in ONE
structured-failure JSON line and a NON-ZERO exit. Nothing retries on the
CPU and nothing swaps the configuration for one that happens to run.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

PROBE_CODE = (
    "import json, time, jax\n"
    "import jax.numpy as jnp\n"
    "t0 = time.perf_counter()\n"
    "x = jnp.ones((256, 256))\n"
    "y = float((x @ x).sum())\n"
    "d = jax.devices()[0]\n"
    "print(json.dumps({'platform': d.platform, 'device_kind': d.device_kind,\n"
    "                  'n_devices': jax.device_count(),\n"
    "                  'probe_s': round(time.perf_counter() - t0, 2),\n"
    "                  'matmul': y}))\n"
)


def _last_json_line(text: str):
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def probe_device(timeout: float = 180.0):
    """Tiny matmul in a subprocess: the device info dict, or None when
    JAX cannot start (or hangs) here. One attempt, on whatever platform
    the environment selects (`JAX_PLATFORMS`)."""
    try:
        proc = subprocess.run(
            [sys.executable, "-c", PROBE_CODE],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            timeout=timeout,
        )
    except subprocess.TimeoutExpired:
        return None
    if proc.returncode != 0:
        return None
    return _last_json_line(proc.stdout)


def fail(metric: str, unit: str, error: str):
    """One failure line, then a non-zero exit — a failed bench must not
    look like a finished one to whatever reads its exit code."""
    print(
        json.dumps(
            {
                "metric": metric,
                "value": 0,
                "unit": unit,
                "vs_baseline": 0.0,
                "ok": False,
                "error": error,
            }
        ),
        flush=True,
    )
    raise SystemExit(1)


_OOM_SIGNATURES = (
    "RESOURCE_EXHAUSTED",
    "Allocation type: HLO temp",
    "out of memory",
    "OOM",
)


def _looks_like_oom(text: str) -> bool:
    return any(sig in text for sig in _OOM_SIGNATURES)


def run_guarded(
    metric: str,
    unit: str,
    script: str,
    child_timeout: float = 1800.0,
    oom_ladder: list[dict] | None = None,
    microbatch_of=None,
    profile: "tuple[str, dict] | None" = None,
) -> dict:
    """Probe, then run `script --child` and forward its JSON line.

    Returns the successful result dict (already printed). Every failure
    path prints one structured-failure line and exits non-zero (`fail`):
    no device, a device that is not an accelerator, an invalid env, a
    child that crashed, hung or printed no JSON.

    `profile` is ONE named configuration, `(name, env-defaults)`: applied
    with setdefault (explicit user env wins) and stamped on the record.
    A profile that fails is a failed bench — there is no next one.

    `oom_ladder` is a list of env-override dicts tried in order whenever
    the child dies with an OOM signature: each rung keeps the global batch
    (the metric stays comparable) and shrinks the live microbatch through
    gradient accumulation; the record notes the attempts it took.
    `child_timeout` is the TOTAL budget across all rungs.
    `microbatch_of(env) -> int | None` reports the live microbatch implied
    by an env dict; rungs that are invalid (None) or do not shrink it below
    the last attempt that ran are skipped.
    """
    info = probe_device()
    if info is None:
        fail(metric, unit, "device probe failed: JAX could not start here "
             "(or hung) on the platform the environment selects")
    if info.get("platform") == "cpu":
        fail(metric, unit, "no accelerator: JAX found only the CPU "
             f"({info}); a bench measures the chip or fails")

    base_env = dict(os.environ)
    prof_name, prof_env = profile or ("", {})
    for k, v in prof_env.items():
        base_env.setdefault(k, v)
    if microbatch_of is not None and microbatch_of(base_env) is None:
        fail(metric, unit, "invalid bench env: the configured batch/accum "
             "combination is not divisible (check BENCH_BATCH / BENCH_ACCUM)")

    deadline = time.monotonic() + child_timeout
    last_error = ""
    last_mb = None
    n_run = 0
    for overrides in [{}] + list(oom_ladder or []):
        env = dict(base_env)
        env.update(overrides)
        mb = None
        if microbatch_of is not None:
            mb = microbatch_of(env)
            if mb is None or (last_mb is not None and mb >= last_mb):
                continue
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            break
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(script), "--child"],
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                timeout=remaining,
                env=env,
            )
        except subprocess.TimeoutExpired:
            n_run += 1
            last_error = f"child timed out after {remaining:.0f}s"
            break
        n_run += 1
        last_mb = mb

        result = _last_json_line(proc.stdout)
        if proc.returncode == 0 and result is not None:
            if n_run > 1:
                result["attempts"] = n_run
            if prof_name:
                result["profile"] = prof_name
            print(json.dumps(result), flush=True)
            return result

        err_text = proc.stderr or proc.stdout or ""
        last_error = "\n".join(err_text.splitlines()[-12:])
        if not _looks_like_oom(err_text):
            break  # only an OOM is worth a smaller microbatch

    fail(
        metric,
        unit,
        f"bench child failed after {n_run} attempt(s) within "
        f"{child_timeout:.0f}s, no JSON produced: {last_error}",
    )
