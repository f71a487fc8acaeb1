"""What every loop shares: the run's context, the device check, the tracer,
the checks behind `correct`, and the one result line.

A loop (`benchmark/loops/<kind>.py`) gets a `Run`, builds and warms the
program, calls `run.window_opens()`, measures, frees the program, compares
with the reference through `run.check(...)` and returns its end-to-end
values. `run.py` prints the result line from what the `Run` then holds.
"""

from __future__ import annotations

import contextlib
import glob
import importlib
import json
import shutil
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent  # benchmark/
OUT = ROOT / "out"
CACHE = ROOT / "cache" / "jax"  # fixed: the path is part of the cache's key


def use_checkout_cache() -> None:
    """Keep JAX's persistent compilation cache in the checkout, at a fixed
    path, uncapped: only files of the checkout outlast a run, the path is part
    of the cache's key, and the chip machine's own directory is capped at 192
    MiB, which one flagship program set overflows (PERF.md, PR 21). Set before
    the program is imported; its `enable_xla_cache()` honours the variable."""
    import os

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    CACHE.mkdir(parents=True, exist_ok=True)
    from dalle_pytorch_tpu.utils.compile_cache import enable_xla_cache

    enable_xla_cache()


def load(kind: str, name: str) -> dict:
    """benchmark/<kind>/<name>.json, found by name and never listed in code."""
    path = ROOT / kind / f"{name}.json"
    if not path.is_file():
        known = sorted(p.stem for p in (ROOT / kind).glob("*.json"))
        raise SystemExit(f"no {kind} file for {name!r} (have: {known})")
    with open(path) as f:
        return json.load(f)


def say(tag: str, **fields) -> None:
    """An earlier line of the output: `[tag] {json}`."""
    print(f"[{tag}] " + json.dumps(fields, default=float), flush=True)


class Run:
    """One run of one cell."""

    def __init__(self, cell: str, seed: int, seconds: float, trace: bool, t0: float):
        self.cell, self.seed, self.seconds, self.trace = cell, int(seed), seconds, trace
        self.t0 = t0
        self.workload = load("workloads", cell)
        self.config = load("configs", self.workload["config"])
        self.rehearsal = bool(self.workload.get("rehearsal"))
        # the cell whose metric files apply (a rehearsal file stands for one)
        self.metrics_cell = self.workload.get("stands_for", cell)
        self.record: dict = {"cell": cell, "seed": self.seed, "seconds": seconds}
        self.counters: dict = {}  # what `program_counter` readers read
        self.shapes: dict = {}  # what the cost functions are given
        self.checks: list = []
        self.attempted = self.failed = 0
        self.setup_s = None
        self.reduced = None  # the trace's reduction, when traced
        self.keep_trace = False
        self.device = None
        self._compiles_at_open = None

    # ------------------------------------------------------------ device

    def claim_device(self) -> None:
        """Name the device, or fail: a benchmark run needs the chips its cell
        asks for, and only a rehearsal file may run anywhere else."""
        import jax

        devices = jax.devices()
        d = devices[0]
        self.device = {"platform": d.platform, "kind": d.device_kind,
                       "count": len(devices)}
        chips = int(self.workload.get("chips", 1))
        if not self.rehearsal and (d.platform != "tpu" or len(devices) < chips):
            sys.stderr.write(
                f"benchmark: cell {self.cell} needs {chips} tpu chip(s), "
                f"found {self.device}\n"
            )
            raise SystemExit(2)
        say("device", **self.device)

    def memory_peak(self) -> int:
        import jax

        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()
        ]
        return int(max(peaks))

    # ------------------------------------------------------------ window

    def window_opens(self) -> float:
        """Set-up ends here: everything before the first measured unit."""
        from dalle_pytorch_tpu.utils import compile_guard

        now = time.perf_counter()
        self.setup_s = now - self.t0
        self._compiles_at_open = compile_guard.compile_count()
        say("setup", setup_s=self.setup_s, **compile_tally())
        return now

    def window_closes(self) -> None:
        from dalle_pytorch_tpu.utils import compile_guard

        n = compile_guard.compile_count() - self._compiles_at_open
        self.counters["compiles_in_window"] = n
        self.device["memory_peak_bytes"] = self.memory_peak()
        self.check("compiles_in_window", n, 0)

    # ------------------------------------------------------------ checks

    def check(self, name: str, value, limit, ok=None) -> bool:
        """One number compared beside its limit; `correct` is all of them."""
        value = float(value)
        if ok is None:
            ok = value == value and value <= float(limit)
        self.checks.append({"name": name, "value": value, "limit": float(limit),
                            "ok": bool(ok)})
        say("check", name=name, value=value, limit=limit, ok=bool(ok))
        return bool(ok)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c["ok"] for c in self.checks)

    def limit(self, name: str) -> float:
        return float(self.workload["check"]["limits"][name])


def compile_tally() -> dict:
    from dalle_pytorch_tpu.utils import compile_guard

    count, hits = compile_guard.compile_count(), compile_guard.cache_hit_count()
    return {"compiles": count, "cache_hits": hits, "uncached": max(0, count - hits),
            "compile_s": compile_guard.compile_seconds()}


# ------------------------------------------------------------------ tracing


def span(name: str, **kw):
    """A host span on the profiler's clock (free when no trace is on)."""
    import jax

    return jax.profiler.TraceAnnotation("bench:" + name, **kw)


class Tracer:
    """Start and stop the profiler around part of the window, then reduce."""

    def __init__(self, run: Run):
        self.run = run
        self.dir = OUT / f"trace-{run.cell}-{run.seed}"

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def stop(self) -> None:
        import jax

        from benchmark.trace import reduce

        jax.profiler.stop_trace()
        files = glob.glob(str(self.dir / "plugins" / "profile" / "*" / "*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        t = time.perf_counter()
        events = reduce.load_xplane(files[0], self.run.device["platform"])
        self.run.reduced = reduce.reduce(events)
        say("trace", file=files[0], reduce_s=time.perf_counter() - t,
            busy_s=self.run.reduced["busy_s"], window_s=self.run.reduced["window_s"])
        if not self.run.keep_trace:
            shutil.rmtree(self.dir, ignore_errors=True)

    def in_background(self, after_s: float, seconds: float) -> threading.Thread:
        """Trace `seconds` of the window from `after_s` on, from a thread of
        its own: for loops whose main thread is inside one long dispatch or
        asleep until the next arrival. Join it before the window closes."""

        def traced():
            time.sleep(after_s)
            with self.window():
                time.sleep(seconds)

        thread = threading.Thread(target=traced, name="bench-trace")
        thread.start()
        return thread

    @contextlib.contextmanager
    def window(self):
        """Trace the body, inside one `bench:window` span."""
        self.start()
        try:
            with span("window"):
                yield
        finally:
            self.stop()


# ------------------------------------------------------------------ metrics


def metric_files() -> dict:
    out = {}
    for path in sorted((ROOT / "metrics").glob("*.json")):
        with open(path) as f:
            out[path.stem] = json.load(f)
    return out


def per_layer_metrics(run: Run) -> dict:
    """Every per-layer metric file that lists this cell (or lists none), read
    by the reader it names; a reader that finds nothing returns None and the
    metric is left out."""
    from benchmark.trace import costs

    out = {}
    context = {"trace": run.reduced, "counters": run.counters, "shapes": run.shapes,
               "device": run.device, "costs": costs}
    for name, spec in metric_files().items():
        if spec.get("kind") == "end_to_end":
            continue
        cells = spec.get("workloads")
        if cells is not None and run.metrics_cell not in cells:
            continue
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(spec.get("params", {}), context)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def end_to_end_metrics(run: Run, values: dict) -> dict:
    files = metric_files()
    out = {"setup_s": {"value": run.setup_s, "unit": files["setup_s"]["unit"]}}
    for name, value in values.items():
        out[name] = {"value": float(value), "unit": files[name]["unit"]}
    return out


def result_line(run: Run, values: dict) -> dict:
    line = {"correct": run.correct, "attempted": run.attempted, "failed": run.failed}
    if run.trace:
        line["metrics"] = per_layer_metrics(run)
        r = run.reduced
        run.device["busy_s"], run.device["window_s"] = r["busy_s"], r["window_s"]
        line["breakdown"] = {"device_ops": r["device_ops"][:10],
                             "idle_gaps": r["idle_gaps"][:10]}
    else:
        line["metrics"] = end_to_end_metrics(run, values)
    line["device"] = run.device
    return line


def write_record(run: Run, line: dict, values: dict) -> None:
    OUT.mkdir(parents=True, exist_ok=True)
    run.record.update(
        line=line, end_to_end=values, checks=run.checks, counters=run.counters,
        shapes=run.shapes, reduced=run.reduced, compiles=compile_tally(),
    )
    suffix = "-trace" if run.trace else ""
    with open(OUT / f"{run.cell}-{run.seed}{suffix}.json", "w") as f:
        json.dump(run.record, f, default=float)
