"""Device telemetry and self-diagnosis for the serving engines.

PR 5's span pipeline answers "where did THIS request's wall time go";
this module answers the capacity question underneath it — "is the device
healthy and well-utilized" — with four cooperating pieces:

  * `ProgramCostTable` — per-program XLA cost/memory accounting. During
    engine warmup every program in the compiled ladder (prefill, chunk,
    release, pixel decode, the micro sampler rungs, the paged variants)
    is AOT-lowered and `compiled.cost_analysis()` + `memory_analysis()`
    are captured: FLOPs, bytes accessed, argument/temp/output HBM.
    Combined with measured dispatch wall time (EMA) this yields live
    model-FLOPs-utilization and achieved-bandwidth gauges per program
    (`dalle_serving_mfu{program=}`, `dalle_serving_hbm_gbps{program=}`)
    — FLOPs and bytes over the peaks of the device it runs on
    (`utils/flops.py:lookup_peaks`); a host-clock estimate, not the
    trace's (ROADMAP D7; no cell serves, so not measured on the chip).
    Capture costs ONE extra backend compile per program at warmup
    (JAX's AOT path does not share the jit dispatch cache — measured),
    which is why it is opt-in via `engine.cost_table`. Mesh-sharded
    engines pass their device labels at capture: where jax exposes
    per-partition analysis the row gains a per-device block
    (`GET /debug/programs?per_shard=1`,
    `dalle_serving_mfu{program=,device=}`); the global row is the
    documented fallback everywhere else.

  * `EngineVitals` — a background sampler thread snapshotting queue
    depth, slots/blocks active, prefix-cache occupancy, the age of the
    dispatch currently in flight, and `device.memory_stats()` (when the
    backend provides it; per-device across the engine's mesh for the
    sharded engine, rolled up into one payload + a
    `dalle_serving_hbm_bytes{device=}` gauge per shard) into a bounded
    ring, exported as
    `GET /debug/vitals` JSON time-series plus `/metrics` gauges. The
    device seam (`_device_memory_stats`) is an overridable hook so tests
    stub it. Zero-overhead-when-off is a counter-gated contract like the
    tracer's: a disabled `EngineVitals` never starts its thread and
    `samples_taken` stays 0; engines talk to `NULL_VITALS` (shared no-op
    singleton) unless a real instance is bound.

  * `StallWatchdog` — runs on the sampler's tick. Three detectors: a
    dispatch whose in-flight age exceeds an EMA-based multiple of that
    program's typical wall time; a queue head older than its budget; and
    zero decode progress (chunk index frozen) with slots active. A
    detection emits one structured `stall` JSONL event carrying the full
    engine-state dump (`/debug/state`: slot table, page tables +
    refcounts, queue summary, in-flight trace IDs) and a worker-thread
    Python stack capture, bumps `dalle_serving_stalls_total{reason=}`,
    and marks /healthz degraded. A cooldown per reason keeps a long
    stall from flooding the log.

  * `SLOTracker` — declarative latency targets (serve.py
    `--slo_ttft_ms` / `--slo_request_ms`) with rolling-window burn rate
    computed from the EXISTING stage/latency histograms: each tick diffs
    cumulative bucket counts, so no per-request bookkeeping is added to
    the hot path. Burn rate = observed violation fraction / allowed
    error budget; > 1 means the budget is burning and /healthz reports
    `"status": "degraded"` (still 200 — a router should shed load, not
    pull the replica).

Everything here reads host-side state only (allocator counts, numpy page
tables, monotonic clocks); nothing in the sampler path can trigger an
XLA compile — pinned, like the tracer, by a serve-cycle-under-
`assert_no_recompiles` test with all of it enabled.
"""

from __future__ import annotations

import sys
import threading
import time
import traceback
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

from dalle_pytorch_tpu.utils import compile_guard

from dalle_pytorch_tpu.utils.flops import lookup_peaks


def extract_cost(compiled) -> Dict[str, float]:
    """`compiled.cost_analysis()` as one flat dict, across jax versions
    (older jax returns `[dict]`)."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost or {})


_MEMORY_FIELDS = (
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "temp_size_in_bytes",
    "alias_size_in_bytes",
    "generated_code_size_in_bytes",
)


def _memory_fields(mem) -> Dict[str, int]:
    out = {}
    for field in _MEMORY_FIELDS:
        v = getattr(mem, field, None)
        if v is not None:
            out[field] = int(v)
    return out


def extract_memory(compiled) -> Dict[str, int]:
    """`compiled.memory_analysis()` HBM footprint fields as a plain dict
    (empty when the backend doesn't implement it). A per-shard list
    (some jax versions report one entry per partition) collapses to its
    first entry here — `extract_memory_per_device` keeps the split."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {}
    if mem is None:
        return {}
    if isinstance(mem, (list, tuple)):
        mem = mem[0] if mem else None
        if mem is None:
            return {}
    return _memory_fields(mem)


def extract_cost_per_device(compiled) -> Optional[List[Dict[str, float]]]:
    """Per-partition cost dicts when jax exposes them — a
    `cost_analysis()` returning MULTIPLE entries is read as one entry
    per mesh device. The common shape (one global entry for the whole
    partitioned program) returns None and callers fall back to the
    global row; that fallback IS the contract, not an error."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return None
    if (
        isinstance(cost, (list, tuple)) and len(cost) > 1
        and all(isinstance(c, dict) for c in cost)
    ):
        return [dict(c) for c in cost]
    return None


def extract_memory_per_device(compiled) -> Optional[List[Dict[str, int]]]:
    """Per-partition memory dicts where `memory_analysis()` reports one
    entry per device; None (fall back to the global row) otherwise."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return None
    if isinstance(mem, (list, tuple)) and len(mem) > 1:
        return [_memory_fields(m) for m in mem]
    return None


def thread_stacks(name_contains: str = "batcher") -> Dict[str, List[str]]:
    """Python stacks of live threads whose name matches, via
    `sys._current_frames()` — the watchdog's answer to "WHERE is the
    worker stuck". Host-side introspection only; safe on any thread."""
    frames = sys._current_frames()
    out: Dict[str, List[str]] = {}
    for t in threading.enumerate():
        if name_contains not in t.name:
            continue
        frame = frames.get(t.ident)
        if frame is not None:
            out[t.name] = [
                line.rstrip("\n")
                for line in traceback.format_stack(frame)
            ]
    return out


class _ProgramRow:
    """Static compile-time cost of one warmed program plus its measured
    dispatch-wall EMA."""

    __slots__ = (
        "name", "flops", "bytes_accessed", "memory", "wall_ema_s",
        "last_wall_s", "dispatches", "synced", "per_shard",
    )

    def __init__(self, name: str, flops: float, bytes_accessed: float,
                 memory: Dict[str, int]):
        self.name = name
        self.flops = float(flops)
        self.bytes_accessed = float(bytes_accessed)
        self.memory = memory
        #: device label -> {"flops", "bytes_accessed", "memory"} when jax
        #: exposed per-partition analysis at capture; None = global only
        self.per_shard: Optional[Dict[str, Dict]] = None
        self.wall_ema_s: Optional[float] = None
        self.last_wall_s: Optional[float] = None
        self.dispatches = 0
        #: False until a wall measurement that includes a device sync
        #: lands — MFU from an async dispatch's host-side wall would be
        #: fiction, so gauges only export once this is True
        self.synced = False


# tracelint: threads
class ProgramCostTable:
    """Compile-time cost registry + live MFU/bandwidth accounting.

    `capture(name, lower_fn)` AOT-compiles the program (one extra backend
    compile — warmup-time only; engines gate it on `_warmup`) and stores
    FLOPs / bytes-accessed / HBM footprint. `record_wall(name, seconds,
    synced=True)` feeds measured dispatch wall time into an EMA and, when
    a registry is attached, updates `dalle_serving_mfu{program=}` and
    `dalle_serving_hbm_gbps{program=}` — per-dispatch model-FLOPs-
    utilization and achieved bandwidth against the configured roofline.

    Wall times are only trusted for MFU when the measurement brackets a
    device sync (the chunk boundary's fused `device_get`, the micro
    sampler's `np.asarray`, the pixel decode's host copy); a pure
    dispatch wall (async prefill) keeps the row's static cost visible
    without exporting a bogus utilization number.
    """

    def __init__(
        self,
        peak_flops: Optional[float] = None,
        hbm_bps: Optional[float] = None,
        registry=None,
        ema_alpha: float = 0.2,
    ):
        if peak_flops is None or hbm_bps is None:
            # the roofline of the device this process runs on; a device
            # with no published peak (the CPU) has no utilization to
            # report — rows and the bandwidth gauge still export
            import jax

            listed = lookup_peaks(jax.devices()[0].device_kind) or (None, None)
            peak_flops = listed[0] if peak_flops is None else peak_flops
            hbm_bps = listed[1] if hbm_bps is None else hbm_bps
        self.peak_flops = None if peak_flops is None else float(peak_flops)
        self.hbm_bps = None if hbm_bps is None else float(hbm_bps)
        self.ema_alpha = float(ema_alpha)
        self._rows: Dict[str, _ProgramRow] = {}
        self._errors: Dict[str, str] = {}
        self._lock = threading.Lock()
        self._m_mfu = self._m_bw = None
        if registry is not None:
            self._m_mfu = registry.gauge_family(
                "dalle_serving_mfu",
                "model-FLOPs-utilization of the most recent synced "
                "dispatches per compiled program (EMA wall vs roofline "
                "peak)",
                label_name="program",
            )
            self._m_bw = registry.gauge_family(
                "dalle_serving_hbm_gbps",
                "achieved HBM bandwidth (bytes accessed / EMA wall) per "
                "compiled program, GB/s",
                label_name="program",
            )

    # ------------------------------------------------------------ capture

    def has(self, name: str) -> bool:
        with self._lock:
            return name in self._rows

    def add(self, name: str, compiled, devices=None) -> None:
        """Register one already-compiled program's cost analysis.

        `devices` (the engine's mesh device labels, in mesh order) opts
        into per-shard attribution: where jax exposes per-partition
        cost/memory analysis (`extract_cost_per_device`), each device
        gets its own row — `GET /debug/programs?per_shard=1` and
        `dalle_serving_mfu{program=,device=}`. Everywhere else the
        global row stands alone, exactly as before."""
        cost = extract_cost(compiled)
        row = _ProgramRow(
            name,
            flops=float(cost.get("flops", 0.0)),
            bytes_accessed=float(cost.get("bytes accessed", 0.0)),
            memory=extract_memory(compiled),
        )
        if devices:
            per_cost = extract_cost_per_device(compiled)
            if per_cost is not None and len(per_cost) == len(devices):
                per_mem = extract_memory_per_device(compiled)
                if per_mem is None or len(per_mem) != len(devices):
                    per_mem = [{}] * len(devices)
                row.per_shard = {
                    str(dev): {
                        "flops": float(c.get("flops", 0.0)),
                        "bytes_accessed": float(
                            c.get("bytes accessed", 0.0)
                        ),
                        "memory": m,
                    }
                    for dev, c, m in zip(devices, per_cost, per_mem)
                }
                # with per-partition entries the program-level row is
                # their SUM (extract_cost's first entry would understate
                # the collective dispatch by ~1/num_devices)
                row.flops = sum(c["flops"] for c in row.per_shard.values())
                row.bytes_accessed = sum(
                    c["bytes_accessed"] for c in row.per_shard.values()
                )
        with self._lock:
            self._rows[name] = row
            self._errors.pop(name, None)

    def capture(self, name: str, lower_fn: Callable,
                devices=None) -> bool:
        """AOT-lower + compile via `lower_fn() -> jax.stages.Lowered` and
        record the program's cost. Failures are recorded, never raised —
        a backend without cost analysis must not break warmup."""
        if self.has(name):
            return True
        try:
            lowered = lower_fn()
            if lowered is None:  # eager-fallback sampler: nothing to lower
                return False
            self.add(name, lowered.compile(), devices=devices)
            return True
        except Exception as exc:
            with self._lock:
                self._errors[name] = repr(exc)
            return False

    def record_error(self, name: str, exc: BaseException) -> None:
        """Record a capture failure from a caller that did its own
        lower/compile (the engines' shared AOT ladder pre-compiles once
        and feeds both this table and the compile-cache export)."""
        with self._lock:
            self._errors[name] = repr(exc)

    # ---------------------------------------------------------- live wall

    def _mfu(self, flops: float, wall_s: float) -> Optional[float]:
        if self.peak_flops is None:
            return None
        return min(1.0, flops / (wall_s * self.peak_flops))

    def record_wall(self, name: str, seconds: float,
                    synced: bool = True) -> None:
        with self._lock:
            row = self._rows.get(name)
            if row is None:
                return
            row.dispatches += 1
            row.last_wall_s = float(seconds)
            row.wall_ema_s = (
                float(seconds) if row.wall_ema_s is None
                else (1 - self.ema_alpha) * row.wall_ema_s
                + self.ema_alpha * float(seconds)
            )
            row.synced = row.synced or bool(synced)
            export = row.synced and row.wall_ema_s > 0
            mfu = bw = None
            shard_stats = []
            if export:
                mfu = self._mfu(row.flops, row.wall_ema_s)
                bw = row.bytes_accessed / row.wall_ema_s / 1e9
                if row.per_shard:
                    # the dispatch is collective — every shard shares the
                    # program wall; per-device MFU divides each shard's
                    # OWN flops by it, so a lopsided partition shows up
                    # as one hot device, not a fleet average
                    shard_stats = [
                        (
                            dev,
                            self._mfu(c["flops"], row.wall_ema_s),
                            c["bytes_accessed"] / row.wall_ema_s / 1e9,
                        )
                        for dev, c in row.per_shard.items()
                    ]
        if export:
            if self._m_mfu is not None and mfu is not None:
                self._m_mfu.labels(name).set(mfu)
                for dev, s_mfu, _ in shard_stats:
                    self._m_mfu.labels_extra(name, device=dev).set(s_mfu)
            if self._m_bw is not None:
                self._m_bw.labels(name).set(bw)
                for dev, _, s_bw in shard_stats:
                    self._m_bw.labels_extra(name, device=dev).set(s_bw)

    def mfu(self, name: str) -> Optional[float]:
        with self._lock:
            row = self._rows.get(name)
        if row is None or not row.synced or not row.wall_ema_s:
            return None
        return self._mfu(row.flops, row.wall_ema_s)

    # ------------------------------------------------------------- export

    def rows(self, per_shard: bool = False) -> List[Dict]:
        """JSON-ready rows for `GET /debug/programs`. `per_shard=True`
        adds a per-mesh-device block to programs whose capture exposed
        per-partition analysis (the `?per_shard=1` query); programs with
        only the global row render unchanged — the documented fallback."""
        with self._lock:
            rows = list(self._rows.values())
            errors = dict(self._errors)
        out = []
        for r in rows:
            ai = r.flops / r.bytes_accessed if r.bytes_accessed else None
            row = {
                "program": r.name,
                "flops": r.flops,
                "bytes_accessed": r.bytes_accessed,
                "arithmetic_intensity": round(ai, 2) if ai else None,
                "memory": r.memory,
                "dispatches": r.dispatches,
            }
            live = r.wall_ema_s is not None
            if live:
                row["wall_ema_ms"] = round(r.wall_ema_s * 1e3, 3)
                row["wall_includes_sync"] = r.synced
                if r.synced and r.wall_ema_s > 0:
                    # significant figures, not decimal places: a toy CPU
                    # engine's honest MFU is ~1e-7 and must not render 0
                    mfu = self._mfu(r.flops, r.wall_ema_s)
                    if mfu is not None:
                        row["mfu"] = float(f"{mfu:.4g}")
                    row["hbm_gbps"] = float(
                        f"{r.bytes_accessed / r.wall_ema_s / 1e9:.4g}"
                    )
            if per_shard and r.per_shard:
                shards = []
                for dev, c in r.per_shard.items():
                    shard = {
                        "device": dev,
                        "flops": c["flops"],
                        "bytes_accessed": c["bytes_accessed"],
                        "memory": c["memory"],
                    }
                    if live and r.synced and r.wall_ema_s > 0:
                        s_mfu = self._mfu(c["flops"], r.wall_ema_s)
                        if s_mfu is not None:
                            shard["mfu"] = float(f"{s_mfu:.4g}")
                        shard["hbm_gbps"] = float(
                            f"{c['bytes_accessed'] / r.wall_ema_s / 1e9:.4g}"
                        )
                    shards.append(shard)
                row["per_shard"] = shards
            out.append(row)
        for name, err in errors.items():
            out.append({"program": name, "error": err})
        return out

    def detail(self, per_shard: bool = False) -> Dict:
        return {
            "peak_flops": self.peak_flops,
            "hbm_bps": self.hbm_bps,
            "programs": self.rows(per_shard=per_shard),
        }


class _NullVitals:
    """Shared no-op stand-in engines hold by default: dispatch-clock calls
    in the hot path cost one attribute lookup and nothing else, and no
    object is ever allocated (the tracer's NULL_TRACE pattern)."""

    __slots__ = ()
    enabled = False
    samples_taken = 0

    def __bool__(self) -> bool:
        return False

    def dispatch_begin(self, name: str) -> None:
        pass

    def dispatch_end(self, name: str, seconds: float) -> None:
        pass


NULL_VITALS = _NullVitals()


# tracelint: threads
class StallWatchdog:
    """Stall detectors evaluated on the vitals tick (host state only).

    `check(snapshot)` returns the list of stall records it fired this
    tick (for tests and for the caller to log); state needed across ticks
    (per-reason cooldowns, progress tracking) lives here so the sampler
    stays stateless about stalls.
    """

    #: detector names — the `reason` label on dalle_serving_stalls_total
    DISPATCH_STUCK = "dispatch_stuck"
    QUEUE_HEAD_STALE = "queue_head_stale"
    NO_PROGRESS = "no_progress"

    def __init__(
        self,
        dispatch_mult: float = 8.0,
        dispatch_min_s: float = 1.0,
        queue_age_budget_s: Optional[float] = None,
        no_progress_ticks: int = 3,
        cooldown_s: float = 30.0,
        first_dispatch_budget_s: float = 600.0,
        registry=None,
        log=None,
        state_dump_fn: Optional[Callable[[], Dict]] = None,
    ):
        self.dispatch_mult = float(dispatch_mult)
        self.dispatch_min_s = float(dispatch_min_s)
        self.queue_age_budget_s = queue_age_budget_s
        self.no_progress_ticks = int(no_progress_ticks)
        self.cooldown_s = float(cooldown_s)
        # a program's first dispatch may legitimately be compiling, so
        # it gets this LARGE fixed budget instead of the EMA-based one —
        # large, not unlimited: a deadlocked first dispatch must still
        # eventually fire (nothing else would catch it: no-progress is
        # suppressed while a dispatch is in flight)
        self.first_dispatch_budget_s = float(first_dispatch_budget_s)
        self.log = log
        self.state_dump_fn = state_dump_fn
        # guards recent/_last_fired: _fire runs on the sampler thread
        # while /healthz and /debug/vitals handlers read them (deque/dict
        # iteration during mutation raises RuntimeError)
        self._lock = threading.Lock()
        self._m_stalls = None
        if registry is not None:
            self._m_stalls = registry.counter_family(
                "dalle_serving_stalls_total",
                "watchdog stall detections by reason",
                label_name="reason",
            )
        self._last_fired: Dict[str, float] = {}
        self._progress_mark = None  # (chunk_index, consecutive stuck ticks)
        self.stalls_fired = 0
        #: most recent stall summaries (reason + detail, no dump), newest
        #: last — /debug/vitals and the degraded healthz read these
        self.recent: deque = deque(maxlen=16)

    def last_stall_age_s(self) -> Optional[float]:
        with self._lock:
            if not self._last_fired:
                return None
            return time.monotonic() - max(self._last_fired.values())

    def recent_stalls(self) -> List[Dict]:
        """Snapshot of the recent-stall ring for exporters (the sampler
        thread appends concurrently)."""
        with self._lock:
            return list(self.recent)

    # ------------------------------------------------------------- checks

    def _fire(self, reason: str, now: float, **detail) -> Optional[Dict]:
        record = {"reason": reason, **detail}
        with self._lock:
            last = self._last_fired.get(reason)
            if last is not None and now - last < self.cooldown_s:
                return None
            self._last_fired[reason] = now
            self.stalls_fired += 1
            self.recent.append({"ts": round(time.time(), 3), **record})
        if self._m_stalls is not None:
            self._m_stalls.labels(reason).inc()
        if self.log is not None:
            dump = None
            if self.state_dump_fn is not None:
                try:
                    dump = self.state_dump_fn()
                except Exception as exc:  # the dump must not kill the tick
                    dump = {"error": repr(exc)}
            extra = {}
            if not (isinstance(dump, dict) and "worker_stacks" in dump):
                # the server's state_dump already captures worker stacks;
                # only fall back to our own capture when the dump didn't
                # (standalone watchdogs, custom dump fns) — one
                # sys._current_frames pass per stall, not two, under ONE
                # schema key wherever the stacks land
                extra["worker_stacks"] = thread_stacks("batcher")
            self.log.event("stall", **record, state=dump, **extra)
        return record

    def check(self, snapshot: Dict, wall_ema: Dict[str, float]) -> List[Dict]:
        """Evaluate every detector against one vitals snapshot. `wall_ema`
        maps program name -> typical dispatch wall (the EMA the dispatch
        clock keeps), the baseline for "this dispatch is taking too long".
        """
        now = time.monotonic()
        fired = []

        inflight = snapshot.get("dispatch_inflight")
        if inflight is not None:
            name, age = inflight["program"], inflight["age_s"]
            if inflight.get("first"):
                # may be paying a legitimate XLA compile (--no_warmup
                # cold start): a large fixed budget, not the EMA one
                ema = None
                budget = self.first_dispatch_budget_s
            else:
                ema = wall_ema.get(name)
                budget = max(
                    self.dispatch_min_s,
                    self.dispatch_mult * ema if ema else 0.0,
                )
            if age > budget:
                rec = self._fire(
                    self.DISPATCH_STUCK, now, program=name,
                    age_s=round(age, 3), budget_s=round(budget, 3),
                    wall_ema_s=round(ema, 4) if ema else None,
                )
                if rec:
                    fired.append(rec)

        head_age = snapshot.get("queue_head_age_s")
        if (
            self.queue_age_budget_s is not None
            and head_age is not None
            and head_age > self.queue_age_budget_s
        ):
            rec = self._fire(
                self.QUEUE_HEAD_STALE, now,
                head_age_s=round(head_age, 3),
                budget_s=self.queue_age_budget_s,
                queue_depth_rows=snapshot.get("queue_depth_rows"),
            )
            if rec:
                fired.append(rec)

        # zero decode progress with slots active and NO dispatch in
        # flight: the worker is wedged somewhere host-side (the stuck-
        # dispatch detector owns the in-flight case)
        chunk_index = snapshot.get("chunk_index")
        slots = snapshot.get("slots_active") or 0
        if chunk_index is not None and slots > 0 and inflight is None:
            mark, stuck = self._progress_mark or (None, 0)
            stuck = stuck + 1 if mark == chunk_index else 0
            self._progress_mark = (chunk_index, stuck)
            if stuck >= self.no_progress_ticks:
                rec = self._fire(
                    self.NO_PROGRESS, now, chunk_index=chunk_index,
                    slots_active=slots, ticks=stuck,
                )
                if rec:
                    fired.append(rec)
        else:
            self._progress_mark = (chunk_index, 0)
        return fired


class SLOTarget:
    """One declarative latency objective over an existing histogram."""

    __slots__ = ("name", "threshold_s", "objective", "histogram")

    def __init__(self, name: str, threshold_s: float, histogram: str,
                 objective: float = 0.99):
        assert 0.0 < objective < 1.0
        self.name = name
        self.threshold_s = float(threshold_s)
        self.objective = float(objective)
        self.histogram = histogram  # registry metric name to read

    def describe(self) -> Dict:
        return {
            "slo": self.name,
            "threshold_ms": round(self.threshold_s * 1e3, 1),
            "objective": self.objective,
            "histogram": self.histogram,
        }


# tracelint: threads
class SLOTracker:
    """Rolling-window SLO burn rate from cumulative histogram buckets.

    Each `update()` diffs the target histogram's bucket counts against
    the previous tick and classifies the delta as compliant (buckets
    whose bound <= threshold) or violating — bucket-granular and
    CONSERVATIVE: a threshold that falls between bounds counts its
    straddling bucket as violating, so a misaligned target over-alerts
    rather than silently never alerting (stated in `status()`). It keeps
    a deque of per-tick deltas spanning `window_s`. Burn rate is
    the window's violation fraction over the allowed error budget
    (1 - objective): 1.0 means exactly on budget, above it the budget is
    burning and /healthz degrades.
    """

    def __init__(self, targets: Sequence[SLOTarget], registry,
                 window_s: float = 300.0):
        self.targets = list(targets)
        self.registry = registry
        self.window_s = float(window_s)
        self._m_burn = registry.gauge_family(
            "dalle_slo_burn_rate",
            "rolling-window error-budget burn rate per SLO (>1 = budget "
            "burning; /healthz degrades)",
            label_name="slo",
        )
        self._prev: Dict[str, tuple] = {}  # slo -> (counts, total)
        self._window: Dict[str, deque] = {
            t.name: deque() for t in self.targets
        }
        self._burn: Dict[str, float] = {t.name: 0.0 for t in self.targets}
        # update() runs on the sampler thread; status()/burning() on
        # /healthz handler threads — the window deques need the lock
        # (iteration during append raises RuntimeError)
        self._lock = threading.Lock()

    @staticmethod
    def _split(buckets, counts, threshold_s):
        """(ok, total) of a bucket snapshot: compliant = observations in
        buckets whose bound <= threshold (provably <= threshold). A
        threshold between bounds leaves its straddling bucket ambiguous —
        counted VIOLATING, so off-bucket thresholds fail conservative
        (burn over-reports) instead of silently never alerting; align
        thresholds with bucket bounds for exact accounting."""
        ok = 0
        for bound, n in zip(buckets, counts):
            if bound > threshold_s:
                break
            ok += n
        return ok, sum(counts)

    def update(self, now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        burns = {}
        for t in self.targets:
            hist = self.registry.get(t.histogram)
            if hist is None or not hasattr(hist, "bucket_counts"):
                continue
            buckets, counts, total, _ = hist.bucket_counts()
            ok, _ = self._split(buckets, counts, t.threshold_s)
            with self._lock:
                prev_ok, prev_total = self._prev.get(t.name, (0, 0))
                d_total = total - prev_total
                d_viol = (total - ok) - (prev_total - prev_ok)
                self._prev[t.name] = (ok, total)
                win = self._window[t.name]
                if d_total > 0:
                    win.append((now, max(d_viol, 0), d_total))
                while win and now - win[0][0] > self.window_s:
                    win.popleft()
                viol = sum(v for _, v, _ in win)
                seen = sum(n for _, _, n in win)
                burn = (
                    (viol / seen) / (1.0 - t.objective) if seen else 0.0
                )
                self._burn[t.name] = burn
            burns[t.name] = burn
        for name, burn in burns.items():  # gauges have their own locks
            self._m_burn.labels(name).set(burn)

    def burning(self) -> List[str]:
        with self._lock:
            return [name for name, b in self._burn.items() if b > 1.0]

    def max_burn(self) -> float:
        """Worst burn rate across every tracked SLO — the scalar the
        batcher's preemption-aware shed consults (0.0 with no targets
        or no observations yet)."""
        with self._lock:
            return max(self._burn.values(), default=0.0)

    def status(self) -> List[Dict]:
        out = []
        for t in self.targets:
            with self._lock:
                win_viol = sum(v for _, v, _ in self._window[t.name])
                win_seen = sum(n for _, _, n in self._window[t.name])
                burn = self._burn[t.name]
            out.append({
                **t.describe(),
                "window_s": self.window_s,
                "burn_rate": round(burn, 3),
                "window_violations": win_viol,
                "window_observations": win_seen,
                "granularity": "histogram buckets (off-bound thresholds "
                               "count the straddling bucket as violating)",
            })
        return out


class EngineVitals:
    """Bounded-ring vitals sampler + dispatch clock for one serving stack.

    Construction is cheap and inert; `bind(engine, batcher, ...)` wires
    the host-state sources and `start()` launches the daemon sampler
    thread (no-ops when `enabled=False` — the counter-gated
    zero-allocation path). Engines call `dispatch_begin/dispatch_end`
    around every device dispatch; both are plain attribute stores, and
    `dispatch_end` feeds the per-program wall EMA the watchdog's
    stuck-dispatch budget derives from.
    """

    def __init__(
        self,
        enabled: bool = True,
        interval_s: float = 1.0,
        max_samples: int = 512,
        registry=None,
        log=None,
        watchdog: Optional[StallWatchdog] = None,
        slo: Optional[SLOTracker] = None,
    ):
        self.enabled = bool(enabled)
        self.interval_s = float(interval_s)
        self._ring: deque = deque(maxlen=int(max_samples))
        self._lock = threading.Lock()
        #: vitals snapshots actually allocated — the counter-gated
        #: zero-overhead-when-off contract, like Tracer.spans_created
        self.samples_taken = 0
        self.registry = registry
        self.log = log
        self.watchdog = watchdog
        self.slo = slo
        self._engine = None
        self._batcher = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # dispatch clock: written by the engine thread, read (torn reads
        # tolerated — monotonic floats) by the sampler thread
        self._inflight_name: Optional[str] = None
        self._inflight_t0 = 0.0
        self._inflight_first = False
        self._inflight_c0 = 0
        self._wall_ema: Dict[str, float] = {}
        #: programs that have completed >= 1 dispatch since this sampler
        #: bound: a program's FIRST dispatch may be paying an unbounded,
        #: legitimate XLA compile (--no_warmup, a lazily-built program),
        #: so the stuck detector exempts it; whether its wall seeds the
        #: EMA is decided by whether a compile ACTUALLY landed (the
        #: compile_guard counter delta), so warmed servers get their
        #: baseline from dispatch one
        self._seen_programs: set = set()
        if self.enabled:
            try:  # compile-delta attribution needs the jax.monitoring
                compile_guard.install_listener()  # listener; optional —
            except Exception:  # without jax, deltas just stay 0
                pass
        self._m_inflight_age = self._m_head_age = self._m_mem = None
        self._m_hbm = None
        if self.enabled and registry is not None:
            # per-shard HBM gauge family: a mesh-sharded engine has one
            # device PER SHARD, and "the device is full" is useless until
            # it names which one — label by device so dashboards and the
            # watchdog postmortem identify the sick shard
            self._m_hbm = registry.gauge_family(
                "dalle_serving_hbm_bytes",
                "device memory_stats() bytes_in_use per mesh device "
                "(one series per shard; absent when the backend doesn't "
                "report memory stats)",
                label_name="device",
            )
            self._m_inflight_age = registry.gauge(
                "dalle_serving_dispatch_inflight_age_seconds",
                "age of the engine dispatch currently in flight (0 when "
                "idle)",
            )
            self._m_head_age = registry.gauge(
                "dalle_serving_queue_head_age_seconds",
                "age of the oldest queued request (0 when the queue is "
                "empty)",
            )
            self._m_mem = registry.gauge(
                "dalle_serving_device_bytes_in_use",
                "device.memory_stats() bytes_in_use (0 when the backend "
                "doesn't report it)",
            )

    # ------------------------------------------------------ dispatch clock

    def dispatch_begin(self, name: str) -> None:
        self._inflight_first = name not in self._seen_programs
        self._inflight_c0 = compile_guard.compile_count()
        self._inflight_t0 = time.monotonic()
        self._inflight_name = name

    def dispatch_end(self, name: str, seconds: float) -> None:
        self._inflight_name = None
        self._seen_programs.add(name)
        if compile_guard.compile_count() > self._inflight_c0:
            # a backend compile landed during this dispatch (--no_warmup
            # cold start, lazy program): the wall is compile latency, and
            # folding it in would inflate the watchdog's stuck budget by
            # dispatch_mult * compile_s — blinding it to real stalls.
            # (Attribution is process-wide, like compile_guard itself: a
            # concurrent compile elsewhere costs one skipped sample.)
            return
        # under the lock: the sampler thread snapshots this dict per tick
        # while engine dispatch threads land EMA updates here
        with self._lock:
            ema = self._wall_ema.get(name)
            self._wall_ema[name] = (
                seconds if ema is None else 0.8 * ema + 0.2 * seconds
            )

    def inflight(self) -> Optional[Dict]:
        name = self._inflight_name
        if name is None:
            return None
        return {
            "program": name,
            "age_s": time.monotonic() - self._inflight_t0,
            # True while the program's FIRST dispatch is in flight — it
            # may be compiling, so the stuck detector exempts it
            "first": self._inflight_first,
        }

    # ------------------------------------------------------------ lifecycle

    def bind(self, engine=None, batcher=None, log=None,
             state_dump_fn=None) -> "EngineVitals":
        self._engine = engine
        self._batcher = batcher
        if log is not None:
            self.log = log
        if self.watchdog is not None:
            if log is not None and self.watchdog.log is None:
                self.watchdog.log = log
            if state_dump_fn is not None:
                self.watchdog.state_dump_fn = state_dump_fn
        if engine is not None and getattr(engine, "vitals", None) is not None:
            engine.vitals = self if self.enabled else NULL_VITALS
        return self

    def start(self) -> "EngineVitals":
        if not self.enabled or self._thread is not None:
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="dalle-vitals", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.tick()
            except Exception:  # a bad source must not kill the sampler
                pass

    # ------------------------------------------------------------ sampling

    def _device_memory_stats(self) -> Optional[Dict]:
        """Overridable device seam (the profiler-hook pattern): returns
        `jax.devices()[0].memory_stats()` or None when the backend (CPU)
        doesn't provide it. Tests stub this — no real device touch."""
        try:
            import jax

            return jax.devices()[0].memory_stats()
        except Exception:
            return None

    def _device_memory_stats_all(self) -> Dict[str, Dict]:
        """Overridable per-shard seam: `memory_stats()` for EVERY device
        of the engine's mesh, keyed 'platform:id'. PR 7's sampler read
        one process-local device; a mesh-sharded engine has one device
        per shard, and a lopsided shard (bad partition rule, leaked
        buffer) is invisible in a single-device read.

        Without a mesh this routes through the legacy single-device seam
        (`_device_memory_stats`) — ONE query per tick, and tests that
        stub the legacy seam keep their no-real-device-touch contract on
        every backend, not just ones whose memory_stats is empty."""
        mesh = getattr(self._engine, "mesh", None)
        if mesh is None:
            stats = self._device_memory_stats()
            return {"device:0": stats} if stats else {}
        out: Dict[str, Dict] = {}
        try:
            for d in mesh.devices.flat:
                stats = d.memory_stats()
                if stats:
                    out[f"{d.platform}:{d.id}"] = stats
        except Exception:
            return out
        return out

    def sample(self) -> Dict:
        """One vitals snapshot from host state (never dispatches)."""
        snap: Dict = {"ts": round(time.time(), 3)}
        batcher = self._batcher
        if batcher is not None:
            snap["queue_depth_rows"] = batcher.queue_depth_rows
            head_age = getattr(batcher, "head_age_s", None)
            if head_age is not None:
                snap["queue_head_age_s"] = head_age()
            class_depths = getattr(batcher, "class_depths", None)
            if class_depths is not None:
                # per-priority-class queue split: under overload the
                # headline depth hides WHICH class is backing up
                snap["queue_depth_by_class"] = class_depths()
            alloc = getattr(batcher, "allocator", None)
            if alloc is not None:
                snap["slots_active"] = alloc.n_active
        engine = self._engine
        if engine is not None:
            chunk_index = getattr(engine, "chunk_index", None)
            if chunk_index is not None:
                snap["chunk_index"] = int(chunk_index)
            kv = getattr(engine, "kv", None)
            if kv is not None:
                snap["blocks_active"] = kv.blocks_active
                snap["blocks_free"] = kv.blocks_free
                snap["prefix_entries"] = len(kv.cache)
        snap["dispatch_inflight"] = self.inflight()
        snap["compile_count"] = compile_guard.compile_count()
        per_dev = self._device_memory_stats_all()
        if per_dev:
            snap["memory_stats_per_device"] = {
                dev: {
                    k: int(v) for k, v in stats.items()
                    if isinstance(v, (int, float))
                }
                for dev, stats in per_dev.items()
            }
            snap["bytes_in_use_total"] = sum(
                s.get("bytes_in_use", 0)
                for s in snap["memory_stats_per_device"].values()
            )
            # the legacy single-device block is the FIRST device's stats
            # — derived, not re-queried (one memory_stats pass per device
            # per tick, not two for device 0)
            snap["memory_stats"] = next(
                iter(snap["memory_stats_per_device"].values())
            )
        return snap

    def tick(self) -> Dict:
        """Sample once, run the watchdog and SLO updates, update gauges.
        Public so tests drive deterministic ticks without the thread."""
        snap = self.sample()
        with self._lock:
            self._ring.append(snap)
            self.samples_taken += 1
            # snapshot the EMA table while no dispatch thread is mid-update
            # (dispatch_end mutates it under this lock)
            wall_ema = dict(self._wall_ema)
        if self._m_inflight_age is not None:
            inflight = snap.get("dispatch_inflight")
            self._m_inflight_age.set(inflight["age_s"] if inflight else 0.0)
        if self._m_head_age is not None:
            self._m_head_age.set(snap.get("queue_head_age_s") or 0.0)
        if self._m_mem is not None:
            self._m_mem.set(
                (snap.get("memory_stats") or {}).get("bytes_in_use", 0)
            )
        if self._m_hbm is not None:
            for dev, stats in (
                snap.get("memory_stats_per_device") or {}
            ).items():
                self._m_hbm.labels(dev).set(stats.get("bytes_in_use", 0))
        if self.watchdog is not None:
            self.watchdog.check(snap, wall_ema)
        if self.slo is not None:
            # tracelint: disable=TL013 -- SLOTracker.update() is a method call, not a dict mutation; the tracker guards its windows with its own lock (review-hardening round, PR 7)
            self.slo.update()
        return snap

    # ------------------------------------------------------------- export

    def recent(self, n: Optional[int] = None) -> List[Dict]:
        with self._lock:
            samples = list(self._ring)
        return samples if n is None else samples[-n:]

    def reset_window(self) -> None:
        """Drop ring contents (bench: measure only the open-loop window)."""
        with self._lock:
            self._ring.clear()

    def window_summary(self) -> Dict:
        """mean/peak aggregates over the current ring — the bench's
        `vitals` block and a quick /debug/vitals headline."""
        samples = self.recent()
        out: Dict = {"samples": len(samples)}
        for key in ("slots_active", "blocks_active", "queue_depth_rows"):
            vals = [s[key] for s in samples if key in s]
            if vals:
                out[key] = {
                    "mean": round(sum(vals) / len(vals), 2),
                    "peak": max(vals),
                }
        return out

    def detail(self, n: Optional[int] = None) -> Dict:
        """JSON payload for `GET /debug/vitals`."""
        with self._lock:  # ticked by the sampler thread under this lock
            samples_taken = self.samples_taken
        out = {
            "enabled": self.enabled,
            "interval_s": self.interval_s,
            "samples_taken": samples_taken,
            "summary": self.window_summary(),
            "samples": self.recent(n),
        }
        mesh_detail = getattr(self._engine, "mesh_detail", None)
        if mesh_detail is not None:
            # sharded engine: one rolled-up payload names every shard —
            # axis geometry + live per-device buffer bytes — next to the
            # per-device memory_stats the samples carry
            out["mesh"] = mesh_detail()
        if self.watchdog is not None:
            out["stalls"] = self.watchdog.recent_stalls()
        if self.slo is not None:
            out["slo"] = self.slo.status()
        return out

    # ------------------------------------------------------------- health

    def degraded_reasons(self, window_s: float = 60.0) -> List[str]:
        """Why /healthz should report `degraded` (empty = fully ok):
        a watchdog stall within `window_s`, or an SLO burning."""
        reasons = []
        if self.watchdog is not None:
            age = self.watchdog.last_stall_age_s()
            if age is not None and age < window_s:
                stalls = self.watchdog.recent_stalls()
                last = stalls[-1] if stalls else {}
                reasons.append(
                    f"stall:{last.get('reason', 'unknown')}"
                )
        if self.slo is not None:
            reasons.extend(f"slo_burn:{name}" for name in self.slo.burning())
        return reasons
