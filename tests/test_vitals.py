"""Engine vitals: program cost table, vitals sampler, stall watchdog,
SLO burn rate, degraded /healthz, and the /debug endpoints.

The acceptance path (TestRealEngineVitals) pins the tentpole contract: a
warm continuous engine served over HTTP with vitals + watchdog + SLO
tracking all enabled compiles ZERO new programs while the sampler ticks
(`assert_no_recompiles`), and `/debug/programs` reports non-empty
FLOPs/bytes/HBM rows for every warmed program. The zero-overhead
contract mirrors the tracer's: a disabled `EngineVitals` allocates no
samples whatever traffic flows (`samples_taken` counter gate). All other
tests stub the device seams (no real `memory_stats`, no profiler init) —
watchdog/SLO logic is synthetic and deterministic via explicit `tick()`/
`check()` calls, never thread timing.
"""

import io
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from dalle_pytorch_tpu.obs import (
    EngineVitals,
    NULL_VITALS,
    ProgramCostTable,
    SLOTarget,
    SLOTracker,
    StallWatchdog,
    StructuredLog,
    Tracer,
)
from dalle_pytorch_tpu.obs.vitals import extract_cost, extract_memory
from dalle_pytorch_tpu.serving.batcher import ContinuousBatcher
from dalle_pytorch_tpu.serving.server import ServingServer
from dalle_pytorch_tpu.training.metrics import MetricsRegistry

from test_continuous import FakeContinuousEngine, IMG_SEQ, _build, spec
from test_serving_e2e import FakeServingEngine, _get, _post


# ------------------------------------------------------- program cost table


class FakeCompiled:
    """Stand-in for jax.stages.Compiled: the two analysis surfaces."""

    class _Mem:
        argument_size_in_bytes = 1024
        output_size_in_bytes = 256
        temp_size_in_bytes = 64
        alias_size_in_bytes = 0
        generated_code_size_in_bytes = 12

    def __init__(self, flops=2.0e9, nbytes=1.0e7, as_list=True):
        self._cost = {"flops": flops, "bytes accessed": nbytes}
        self._as_list = as_list

    def cost_analysis(self):
        return [self._cost] if self._as_list else self._cost

    def memory_analysis(self):
        return self._Mem()


class TestProgramCostTable:
    def test_extract_helpers_handle_both_jax_shapes(self):
        flat = extract_cost(FakeCompiled(as_list=False))
        wrapped = extract_cost(FakeCompiled(as_list=True))
        assert flat == wrapped and flat["flops"] == 2.0e9
        mem = extract_memory(FakeCompiled())
        assert mem["argument_size_in_bytes"] == 1024
        assert mem["temp_size_in_bytes"] == 64

    def test_rows_and_mfu_from_synced_wall(self):
        reg = MetricsRegistry()
        table = ProgramCostTable(
            peak_flops=1e12, hbm_bps=1e11, registry=reg
        )
        table.add("chunk", FakeCompiled(flops=1e9, nbytes=1e8))
        # unsynced wall: watchdog baseline only, no MFU exported
        table.record_wall("chunk", 0.010, synced=False)
        assert table.mfu("chunk") is None
        (row,) = table.rows()
        assert row["wall_includes_sync"] is False and "mfu" not in row
        # synced wall: EMA folds in, MFU = flops / (wall * peak)
        table.record_wall("chunk", 0.010, synced=True)
        mfu = table.mfu("chunk")
        assert mfu == pytest.approx(1e9 / (0.010 * 1e12), rel=1e-6)
        (row,) = table.rows()
        assert row["mfu"] == pytest.approx(mfu, rel=1e-3)
        assert row["hbm_gbps"] == pytest.approx(1e8 / 0.010 / 1e9, rel=1e-3)
        assert row["memory"]["argument_size_in_bytes"] == 1024
        # gauges landed with the program label
        out = reg.render()
        assert 'dalle_serving_mfu{program="chunk"}' in out
        assert 'dalle_serving_hbm_gbps{program="chunk"}' in out

    def test_mfu_clamped_and_unknown_program_ignored(self):
        table = ProgramCostTable(peak_flops=1.0)  # absurd peak -> clamp
        table.add("p", FakeCompiled(flops=1e9, nbytes=1.0))
        table.record_wall("p", 0.001)
        assert table.mfu("p") == 1.0
        table.record_wall("never_captured", 0.5)  # must not raise
        assert table.mfu("never_captured") is None

    def test_unlisted_device_exports_no_mfu(self):
        """Peaks resolve from the device kind; the CPU is not in the table,
        so the default table reports cost rows and bandwidth but never a
        utilization against some other chip's peak."""
        reg = MetricsRegistry()
        table = ProgramCostTable(registry=reg)
        assert table.peak_flops is None and table.hbm_bps is None
        table.add("chunk", FakeCompiled(flops=1e9, nbytes=1e8))
        table.record_wall("chunk", 0.010, synced=True)
        assert table.mfu("chunk") is None
        (row,) = table.rows()
        assert "mfu" not in row and row["hbm_gbps"] == pytest.approx(10.0)
        out = reg.render()
        assert "dalle_serving_mfu{" not in out
        assert 'dalle_serving_hbm_gbps{program="chunk"}' in out

    def test_capture_records_errors_instead_of_raising(self):
        table = ProgramCostTable()

        def bad_lower():
            raise RuntimeError("no backend")

        assert table.capture("broken", bad_lower) is False
        (row,) = table.rows()
        assert row["program"] == "broken" and "no backend" in row["error"]
        # eager-fallback samplers lower to None: skipped, not an error
        assert table.capture("eager", lambda: None) is False
        assert not table.has("eager")


class PerShardCompiled:
    """Compiled stand-in whose cost_analysis reports one entry per
    partition — the 'where jax exposes per-shard data' arm."""

    def __init__(self, per_dev):
        self._per = per_dev

    def cost_analysis(self):
        return [dict(c) for c in self._per]

    def memory_analysis(self):
        return None


class TestPerShardCostRows:
    PER_DEV = [
        {"flops": 1e9, "bytes accessed": 1e8},
        {"flops": 3e9, "bytes accessed": 3e8},
    ]

    def _table(self, reg=None):
        table = ProgramCostTable(
            peak_flops=1e12, hbm_bps=1e11, registry=reg
        )
        table.add(
            "chunk", PerShardCompiled(self.PER_DEV),
            devices=["cpu:0", "cpu:1"],
        )
        return table

    def test_per_shard_rows_and_global_sum(self):
        table = self._table()
        (row,) = table.rows(per_shard=True)
        # the global row is the SUM of the partitions, not entry 0
        assert row["flops"] == 4e9 and row["bytes_accessed"] == 4e8
        shards = {s["device"]: s for s in row["per_shard"]}
        assert shards["cpu:0"]["flops"] == 1e9
        assert shards["cpu:1"]["flops"] == 3e9
        # default rows() view is unchanged (no per_shard key)
        (plain,) = table.rows()
        assert "per_shard" not in plain

    def test_per_shard_mfu_gauges_and_row_values(self):
        reg = MetricsRegistry()
        table = self._table(reg)
        table.record_wall("chunk", 0.010, synced=True)
        (row,) = table.rows(per_shard=True)
        shards = {s["device"]: s for s in row["per_shard"]}
        # per-device MFU divides each shard's OWN flops by the shared
        # collective wall — the lopsided shard reads 3x the other
        assert shards["cpu:1"]["mfu"] == pytest.approx(
            3e9 / (0.010 * 1e12), rel=1e-3
        )
        assert shards["cpu:1"]["mfu"] == pytest.approx(
            3 * shards["cpu:0"]["mfu"], rel=1e-3
        )
        out = reg.render()
        assert 'dalle_serving_mfu{program="chunk"}' in out
        assert 'dalle_serving_mfu{program="chunk",device="cpu:0"}' in out
        assert 'dalle_serving_hbm_gbps{program="chunk",device="cpu:1"}' in out

    def test_global_only_analysis_falls_back(self):
        """The common jax shape (one entry for the whole partitioned
        program) keeps the global row alone even with devices passed."""
        table = ProgramCostTable()
        table.add(
            "prefill", FakeCompiled(flops=5e9), devices=["cpu:0", "cpu:1"]
        )
        (row,) = table.rows(per_shard=True)
        assert "per_shard" not in row and row["flops"] == 5e9

    def test_debug_programs_per_shard_query(self):
        """GET /debug/programs?per_shard=1 surfaces the block; the plain
        endpoint stays global-only."""
        eng = FakeServingEngine()
        eng.cost_table = self._table(eng.registry)
        server = ServingServer(eng, port=0, max_delay_ms=5).start()
        try:
            status, body = _get(server.port, "/debug/programs")
            assert status == 200
            (row,) = json.loads(body)["programs"]
            assert "per_shard" not in row
            status, body = _get(server.port, "/debug/programs?per_shard=1")
            assert status == 200
            (row,) = json.loads(body)["programs"]
            assert [s["device"] for s in row["per_shard"]] == [
                "cpu:0", "cpu:1",
            ]
        finally:
            server.shutdown()


# ----------------------------------------------------------------- SLO burn


class TestSLOTracker:
    def _tracker(self, threshold_s=0.25, objective=0.9, window_s=60.0):
        reg = MetricsRegistry()
        hist = reg.histogram("lat_seconds", "test latency")
        slo = SLOTracker(
            [SLOTarget("lat", threshold_s, histogram="lat_seconds",
                       objective=objective)],
            registry=reg, window_s=window_s,
        )
        return reg, hist, slo

    def test_burn_zero_when_compliant(self):
        reg, hist, slo = self._tracker()
        for _ in range(10):
            hist.observe(0.01)
        slo.update()
        assert slo.burning() == []
        (st,) = slo.status()
        assert st["burn_rate"] == 0.0 and st["window_observations"] == 10

    def test_burn_exceeds_one_on_violations(self):
        reg, hist, slo = self._tracker(threshold_s=0.25, objective=0.9)
        for _ in range(8):
            hist.observe(0.01)
        hist.observe(5.0)
        hist.observe(5.0)  # 2/10 violating vs 10% budget -> burn 2.0
        slo.update()
        assert slo.burning() == ["lat"]
        (st,) = slo.status()
        assert st["burn_rate"] == pytest.approx(2.0)
        assert st["window_violations"] == 2
        out = reg.render()
        assert 'dalle_slo_burn_rate{slo="lat"} 2' in out

    def test_rolling_window_forgets_old_violations(self):
        reg, hist, slo = self._tracker(window_s=60.0)
        hist.observe(5.0)
        slo.update(now=0.0)
        assert slo.burning() == ["lat"]
        # a window later: only fresh compliant traffic counts
        for _ in range(10):
            hist.observe(0.01)
        slo.update(now=100.0)
        assert slo.burning() == []

    def test_off_bucket_threshold_fails_conservative(self):
        """A threshold between bucket bounds counts the straddling bucket
        as violating — the SLO over-alerts rather than going silently
        blind (an observation at 0.4s against a 0.3s target IS a
        violation the optimistic rounding would have hidden)."""
        reg, hist, slo = self._tracker(threshold_s=0.3, objective=0.9)
        for _ in range(9):
            hist.observe(0.01)
        hist.observe(0.4)  # lands in the (0.25, 0.5] bucket
        slo.update()
        (st,) = slo.status()
        assert st["window_violations"] == 1
        assert slo.burning() == ["lat"]

    def test_missing_histogram_is_harmless(self):
        reg = MetricsRegistry()
        slo = SLOTracker(
            [SLOTarget("ghost", 0.1, histogram="never_registered")],
            registry=reg,
        )
        slo.update()
        assert slo.burning() == []


# ------------------------------------------------------------ stall watchdog


class TestStallWatchdog:
    def _watchdog(self, log_buf=None, **kw):
        kw.setdefault("dispatch_mult", 4.0)
        kw.setdefault("dispatch_min_s", 0.05)
        kw.setdefault("queue_age_budget_s", 1.0)
        kw.setdefault("no_progress_ticks", 2)
        reg = MetricsRegistry()
        log = StructuredLog(stream=log_buf) if log_buf is not None else None
        wd = StallWatchdog(
            registry=reg, log=log,
            state_dump_fn=lambda: {"slot_table": [0, 1]},
            **kw,
        )
        return reg, wd

    def test_silent_on_healthy_cycle(self):
        _, wd = self._watchdog()
        healthy = {
            "dispatch_inflight": {"program": "chunk", "age_s": 0.01},
            "queue_head_age_s": 0.2,
            "chunk_index": 7,
            "slots_active": 2,
        }
        for i in range(5):
            healthy = dict(healthy, chunk_index=7 + i)  # decode progresses
            assert wd.check(healthy, {"chunk": 0.02}) == []
        assert wd.stalls_fired == 0

    def test_fires_on_stuck_dispatch_with_state_dump(self):
        buf = io.StringIO()
        _, wd = self._watchdog(log_buf=buf)
        stuck = {"dispatch_inflight": {"program": "chunk", "age_s": 2.0}}
        (fired,) = wd.check(stuck, {"chunk": 0.02})  # budget = 4 * 0.02
        assert fired["reason"] == StallWatchdog.DISPATCH_STUCK
        assert fired["program"] == "chunk" and fired["age_s"] == 2.0
        rec = json.loads(buf.getvalue())
        assert rec["event"] == "stall"
        assert rec["reason"] == "dispatch_stuck"
        assert rec["state"] == {"slot_table": [0, 1]}
        # the custom dump carries no stacks, so the watchdog's fallback
        # capture rides the event under the SAME schema key the server
        # dump uses
        assert "worker_stacks" in rec
        assert wd.last_stall_age_s() < 1.0

    def test_first_dispatch_gets_compile_budget_not_ema_budget(self):
        """A program's first dispatch may be paying a legitimate XLA
        compile (--no_warmup cold start): no false stall within the large
        fixed budget — but the budget is BOUNDED, so a deadlocked first
        dispatch still eventually fires (nothing else would catch it)."""
        _, wd = self._watchdog()
        compiling = {
            "dispatch_inflight": {
                "program": "generate:8", "age_s": 45.0, "first": True,
            },
        }
        assert wd.check(compiling, {}) == []
        assert wd.stalls_fired == 0
        # the same age on a non-first dispatch IS a stall
        stuck = dict(compiling)
        stuck["dispatch_inflight"] = dict(
            compiling["dispatch_inflight"], first=False
        )
        assert wd.check(stuck, {})[0]["reason"] == wd.DISPATCH_STUCK
        # past the first-dispatch budget, even a "compiling" dispatch is
        # declared stuck
        _, wd2 = self._watchdog(first_dispatch_budget_s=10.0)
        (fired,) = wd2.check(compiling, {})
        assert fired["reason"] == wd2.DISPATCH_STUCK
        assert fired["budget_s"] == 10.0

    def test_serve_rejects_slo_without_vitals(self):
        """serve.py fails loudly on --no_vitals + --slo_*: the sampler
        drives burn updates, so the combination would silently export a
        dead burn gauge."""
        import sys
        from pathlib import Path

        sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
        import serve

        with pytest.raises(SystemExit):
            serve.parse_args(
                ["--dalle_path", "x", "--no_vitals", "--slo_ttft_ms", "500"]
            )
        args = serve.parse_args(["--dalle_path", "x", "--slo_ttft_ms", "500"])
        assert args.slo_ttft_ms == 500.0

    def test_cooldown_suppresses_repeat_firing(self):
        _, wd = self._watchdog(cooldown_s=60.0)
        stuck = {"dispatch_inflight": {"program": "chunk", "age_s": 2.0}}
        assert len(wd.check(stuck, {"chunk": 0.02})) == 1
        assert wd.check(stuck, {"chunk": 0.02}) == []
        assert wd.stalls_fired == 1

    def test_fires_on_stale_queue_head(self):
        reg, wd = self._watchdog(queue_age_budget_s=0.5)
        (fired,) = wd.check(
            {"queue_head_age_s": 3.0, "queue_depth_rows": 9}, {}
        )
        assert fired["reason"] == StallWatchdog.QUEUE_HEAD_STALE
        assert fired["queue_depth_rows"] == 9
        fam = reg.get("dalle_serving_stalls_total")
        assert fam.labels("queue_head_stale").value == 1

    def test_fires_on_frozen_decode_progress(self):
        _, wd = self._watchdog(no_progress_ticks=2)
        frozen = {"chunk_index": 5, "slots_active": 3}
        assert wd.check(frozen, {}) == []  # tick 1: baseline
        assert wd.check(frozen, {}) == []  # tick 2: 1 stuck tick
        (fired,) = wd.check(frozen, {})  # tick 3: threshold
        assert fired["reason"] == StallWatchdog.NO_PROGRESS
        assert fired["slots_active"] == 3

    def test_progress_resets_the_frozen_counter(self):
        _, wd = self._watchdog(no_progress_ticks=2)
        wd.check({"chunk_index": 5, "slots_active": 1}, {})
        wd.check({"chunk_index": 5, "slots_active": 1}, {})
        wd.check({"chunk_index": 6, "slots_active": 1}, {})  # progressed
        wd.check({"chunk_index": 6, "slots_active": 1}, {})
        assert wd.check({"chunk_index": 6, "slots_active": 1}, {}) != []
        assert wd.stalls_fired == 1


# --------------------------------------------------------- sampler (fakes)


class StubVitals(EngineVitals):
    """Device seam stubbed per the tier-1 contract: no real
    jax.devices()/memory_stats touch from the sampler."""

    def _device_memory_stats(self):
        return {"bytes_in_use": 12345, "peak_bytes_in_use": 23456}


class TestEngineVitalsSampler:
    def test_snapshot_fields_from_fake_stack(self):
        reg = MetricsRegistry()
        eng = FakeContinuousEngine()
        b = ContinuousBatcher(eng, registry=eng.registry)
        try:
            vit = StubVitals(interval_s=60.0, registry=reg)
            vit.bind(engine=eng, batcher=b)
            snap = vit.tick()
            assert snap["queue_depth_rows"] == 0
            assert snap["slots_active"] == 0
            assert snap["queue_head_age_s"] is None
            assert snap["memory_stats"]["bytes_in_use"] == 12345
            assert snap["dispatch_inflight"] is None
            assert "compile_count" in snap
            assert vit.samples_taken == 1
            assert vit.recent() == [snap]
            # the memory gauge follows the stubbed device stats
            assert reg.get(
                "dalle_serving_device_bytes_in_use"
            ).value == 12345
        finally:
            b.shutdown()

    def test_dispatch_clock_tracks_inflight_and_ema(self):
        vit = StubVitals(interval_s=60.0)
        assert vit.inflight() is None
        vit.dispatch_begin("chunk")
        time.sleep(0.01)
        inflight = vit.inflight()
        assert inflight["program"] == "chunk"
        assert inflight["age_s"] >= 0.01
        # a program's FIRST post-bind dispatch is stuck-exempt (it may
        # be compiling) but on a warmed server no compile lands, so its
        # wall DOES seed the EMA — the second dispatch has a baseline
        assert inflight["first"] is True
        vit.dispatch_end("chunk", 0.03)
        assert vit.inflight() is None
        assert vit._wall_ema["chunk"] == pytest.approx(0.03)
        vit.dispatch_begin("chunk")
        assert vit.inflight()["first"] is False
        vit.dispatch_end("chunk", 0.03)
        assert vit._wall_ema["chunk"] == pytest.approx(0.03)

    def test_compiling_dispatch_never_seeds_the_ema(self, monkeypatch):
        """A dispatch during which a backend compile landed (--no_warmup
        cold start) must not fold its ~compile-length wall into the EMA
        the watchdog's stuck budget multiplies."""
        from dalle_pytorch_tpu.utils import compile_guard

        vit = StubVitals(interval_s=60.0)
        vit.dispatch_begin("chunk")
        monkeypatch.setattr(  # a compile lands mid-dispatch
            compile_guard, "_compile_count",
            compile_guard.compile_count() + 1,
        )
        vit.dispatch_end("chunk", 60.0)
        assert "chunk" not in vit._wall_ema
        # the next (warm) dispatch seeds the honest baseline
        vit.dispatch_begin("chunk")
        vit.dispatch_end("chunk", 0.02)
        assert vit._wall_ema["chunk"] == pytest.approx(0.02)

    def test_window_summary_means_and_peaks(self):
        vit = StubVitals(interval_s=60.0)
        eng = FakeContinuousEngine()
        b = ContinuousBatcher(eng, registry=eng.registry)
        try:
            vit.bind(engine=eng, batcher=b)
            vit.tick()
            b.allocator.alloc()  # 2 live slots for the second sample
            b.allocator.alloc()
            vit.tick()
            summary = vit.window_summary()
            assert summary["samples"] == 2
            assert summary["slots_active"] == {"mean": 1.0, "peak": 2}
            vit.reset_window()
            assert vit.window_summary()["samples"] == 0
            assert vit.samples_taken == 2  # the gate counter never resets
        finally:
            b.shutdown()

    def test_disabled_vitals_zero_allocations_under_traffic(self):
        """The acceptance gate: a vitals-off server serves traffic with
        ZERO sampler allocations — counter-gated, like the tracer."""
        eng = FakeServingEngine()
        vit = EngineVitals(enabled=False, registry=eng.registry)
        server = ServingServer(
            eng, port=0, max_delay_ms=5, vitals=vit,
        ).start()
        try:
            for i in range(3):
                status, _ = _post(server.port, {"prompt": f"req {i}"})
                assert status == 200
            assert vit.samples_taken == 0
            assert vit.recent() == []
            assert vit.start() is vit  # start() on disabled = no thread
            assert vit._thread is None
            # the engine keeps the null clock: nothing bound
            assert eng.registry.get(
                "dalle_serving_dispatch_inflight_age_seconds"
            ) is None
        finally:
            server.shutdown()

    def test_null_vitals_singleton_is_inert(self):
        assert not NULL_VITALS
        NULL_VITALS.dispatch_begin("x")
        NULL_VITALS.dispatch_end("x", 1.0)
        assert NULL_VITALS.samples_taken == 0


class ShardStubVitals(StubVitals):
    """Per-shard seam stubbed: a fake 2-device mesh's memory stats (the
    PR 7 follow-on — one process used to sample only device 0)."""

    def _device_memory_stats_all(self):
        return {
            "tpu:0": {"bytes_in_use": 1000, "peak_bytes_in_use": 1500},
            "tpu:1": {"bytes_in_use": 3000, "peak_bytes_in_use": 3500},
        }


class TestPerShardVitals:
    def test_per_device_rollup_and_gauge_family(self):
        """One snapshot carries EVERY shard's memory stats plus their
        total, and the dalle_serving_hbm_bytes{device=} family exports
        one series per shard — the sick one is nameable."""
        reg = MetricsRegistry()
        eng = FakeContinuousEngine()
        b = ContinuousBatcher(eng, registry=eng.registry)
        try:
            vit = ShardStubVitals(interval_s=60.0, registry=reg)
            vit.bind(engine=eng, batcher=b)
            snap = vit.tick()
            per_dev = snap["memory_stats_per_device"]
            assert per_dev["tpu:0"]["bytes_in_use"] == 1000
            assert per_dev["tpu:1"]["bytes_in_use"] == 3000
            assert snap["bytes_in_use_total"] == 4000
            fam = reg.get("dalle_serving_hbm_bytes")
            by_dev = {label: child.value for label, child in fam.items()}
            assert by_dev == {"tpu:0": 1000, "tpu:1": 3000}
        finally:
            vit.stop()
            b.shutdown(drain=False)

    def test_vitals_detail_carries_mesh_block(self):
        """An engine exposing mesh_detail() (the sharded engine) gets its
        rollup into the /debug/vitals payload."""
        reg = MetricsRegistry()
        eng = FakeContinuousEngine()
        eng.mesh_detail = lambda: {
            "axes": {"tp": 2}, "devices": 2,
            "per_device_state_bytes": {"tpu:0": 7, "tpu:1": 7},
        }
        b = ContinuousBatcher(eng, registry=eng.registry)
        try:
            vit = ShardStubVitals(interval_s=60.0, registry=reg)
            vit.bind(engine=eng, batcher=b)
            vit.tick()
            detail = vit.detail()
            assert detail["mesh"]["axes"] == {"tp": 2}
            assert detail["mesh"]["per_device_state_bytes"]["tpu:1"] == 7
        finally:
            vit.stop()
            b.shutdown(drain=False)

    def test_mesh_devices_prefers_engine_mesh(self):
        """The per-shard seam reads the ENGINE's mesh devices when one is
        bound, not every process-visible device."""

        class _Dev:
            def __init__(self, i):
                self.platform, self.id = "tpu", i

            def memory_stats(self):
                return {"bytes_in_use": 10 * (self.id + 1)}

        class _Mesh:
            class devices:
                flat = [_Dev(0), _Dev(1)]

        eng = FakeContinuousEngine()
        eng.mesh = _Mesh()
        vit = EngineVitals(enabled=True, interval_s=60.0)
        vit.bind(engine=eng)
        try:
            stats = vit._device_memory_stats_all()
            assert stats == {
                "tpu:0": {"bytes_in_use": 10},
                "tpu:1": {"bytes_in_use": 20},
            }
        finally:
            vit.stop()


# -------------------------------------------------- /debug + health (HTTP)


class TestDebugEndpoints:
    def test_trace_id_exact_lookup_and_404(self):
        server = ServingServer(
            FakeServingEngine(), port=0, max_delay_ms=5,
            tracer=Tracer(max_traces=4),
        ).start()
        try:
            status, payload = _post(server.port, {"prompt": "find me"})
            assert status == 200
            tid = payload["trace_id"]
            status, body = _get(
                server.port, f"/debug/traces?trace_id={tid}"
            )
            assert status == 200
            events = json.loads(body)["traceEvents"]
            assert events and all(
                e["args"]["trace_id"] == tid
                for e in events if e["ph"] == "X"
            )
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(server.port, "/debug/traces?trace_id=deadbeef")
            assert e.value.code == 404
            # eviction: flood the 4-trace ring, the old ID 404s
            for i in range(5):
                _post(server.port, {"prompt": f"flood {i}"})
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(server.port, f"/debug/traces?trace_id={tid}")
            assert e.value.code == 404
        finally:
            server.shutdown()

    def test_debug_vitals_and_programs_endpoints(self):
        eng = FakeServingEngine()
        vit = StubVitals(interval_s=60.0, registry=eng.registry)
        server = ServingServer(
            eng, port=0, max_delay_ms=5, vitals=vit,
        ).start()
        try:
            vit.tick()  # deterministic: don't wait for the thread
            status, body = _get(server.port, "/debug/vitals?n=1")
            assert status == 200
            payload = json.loads(body)
            assert payload["enabled"] is True
            assert len(payload["samples"]) == 1
            assert payload["samples"][0]["memory_stats"]["bytes_in_use"] == 12345
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(server.port, "/debug/vitals?n=0")
            assert e.value.code == 400
            # no cost table attached: explicit note, not a 500
            status, body = _get(server.port, "/debug/programs")
            assert status == 200
            payload = json.loads(body)
            assert payload["programs"] == [] and "note" in payload
        finally:
            server.shutdown()

    def test_debug_state_renders_midflight_dump(self):
        """/debug/state while the worker is parked inside a chunk: the
        dump shows the in-flight slot with its trace ID and the queued
        request behind it — a consistent postmortem view mid-stall."""
        gate = threading.Event()
        eng = FakeContinuousEngine(block_event=gate)
        b = ContinuousBatcher(eng, registry=eng.registry)
        tr = Tracer()
        try:
            t1 = tr.start_trace()
            first = b.submit([spec(0)], trace=t1)
            assert eng.chunk_entered.wait(10.0)  # worker provably parked
            queued = b.submit([spec(1)], trace=tr.start_trace())
            summary = b.state_summary()
            assert summary["queue_requests"] == 1
            assert summary["queue_head_age_s"] is not None
            assert summary["slots_active"] == 1
            (slot_info,) = summary["slots_inflight"].values()
            assert slot_info["trace_id"] == t1.trace_id
            assert slot_info["rows"] == 1
        finally:
            gate.set()
            first.future.result(timeout=10)
            queued.future.result(timeout=10)
            b.shutdown()

    def test_request_log_carries_admission_context(self):
        """Satellite: every request log line records the load it was
        admitted under (queue_depth_rows / slots_active at submit)."""
        from dalle_pytorch_tpu.data.tokenizer import ByteTokenizer

        _, cont = _build(max_batch=2, chunk_tokens=4, prefill_batch=2)
        cont.tokenizer = ByteTokenizer()
        cont.warmup()
        buf = io.StringIO()
        server = ServingServer(
            cont, port=0, request_timeout_s=60,
            log=StructuredLog(stream=buf),
        ).start()
        try:
            status, payload = _post(server.port, {"prompt": "ctx", "seed": 3})
            assert status == 200
            (rec,) = [
                json.loads(line) for line in buf.getvalue().splitlines()
                if json.loads(line).get("event") == "request"
            ]
            assert rec["trace_id"] == payload["trace_id"]
            assert rec["queue_depth_rows"] == 0
            assert rec["slots_active"] == 0  # sampled at submit time
        finally:
            server.shutdown()

    def test_healthz_degraded_tier(self):
        """Between ok and 503: a recent watchdog stall (or burning SLO)
        turns /healthz into 200 + status=degraded with reasons; hard
        failures still 503."""
        eng = FakeServingEngine()
        wd = StallWatchdog(dispatch_min_s=0.01, cooldown_s=600)
        vit = StubVitals(
            interval_s=60.0, registry=eng.registry, watchdog=wd,
        )
        server = ServingServer(
            eng, port=0, max_delay_ms=5, vitals=vit,
        ).start()
        try:
            status, body = _get(server.port, "/healthz")
            assert status == 200
            assert json.loads(body)["status"] == "ok"
            # synthetic stall -> degraded, still HTTP 200
            wd.check(
                {"dispatch_inflight": {"program": "chunk", "age_s": 9.9}},
                {},
            )
            status, body = _get(server.port, "/healthz")
            health = json.loads(body)
            assert status == 200
            assert health["status"] == "degraded"
            assert health["degraded_reasons"] == ["stall:dispatch_stuck"]
        finally:
            server.shutdown()


# ------------------------------------- acceptance: real engine, everything on


@pytest.fixture(scope="module")
def vital_server():
    """Warm toy continuous engine + cost table + sampler + watchdog + SLO
    behind one HTTP server (the PR's full stack, device seams stubbed)."""
    from dalle_pytorch_tpu.data.tokenizer import ByteTokenizer

    _, cont = _build(max_batch=2, chunk_tokens=4, prefill_batch=2)
    cont.tokenizer = ByteTokenizer()
    # explicit roofline: the CPU these tests run on has no published peak,
    # and a table that resolves none exports no MFU (pinned below)
    cont.cost_table = ProgramCostTable(
        peak_flops=197e12, hbm_bps=819e9, registry=cont.registry
    )
    cont.warmup()
    slo = SLOTracker(
        [
            SLOTarget("ttft", 30.0, histogram="dalle_serving_ttft_seconds"),
            SLOTarget(
                "request", 60.0,
                histogram="dalle_serving_request_latency_seconds",
            ),
        ],
        registry=cont.registry,
    )
    vitals = StubVitals(
        interval_s=0.05, registry=cont.registry,
        watchdog=StallWatchdog(
            registry=cont.registry, dispatch_min_s=30.0,
            queue_age_budget_s=30.0,
        ),
        slo=slo,
    )
    server = ServingServer(
        cont, port=0, request_timeout_s=60,
        tracer=Tracer(max_traces=16), vitals=vitals,
    ).start()
    try:
        yield server, cont, vitals
    finally:
        server.shutdown()


class TestRealEngineVitals:
    def test_warm_serve_cycle_zero_compiles_with_everything_on(
        self, vital_server
    ):
        """The acceptance pin: vitals sampling, watchdog checks, SLO burn
        updates, and MFU accounting all run DURING a served request on a
        warm engine — and nothing compiles."""
        from dalle_pytorch_tpu.utils.compile_guard import assert_no_recompiles

        server, cont, vitals = vital_server
        _post(server.port, {"prompt": "warm", "seed": 1})
        before = vitals.samples_taken
        with assert_no_recompiles():
            status, payload = _post(
                server.port, {"prompt": "steady", "seed": 2}
            )
            deadline = time.monotonic() + 5.0
            while vitals.samples_taken == before:  # sampler ticked inside
                assert time.monotonic() < deadline, "sampler never ticked"
                time.sleep(0.02)
        assert status == 200 and payload["trace_id"]
        assert vitals.watchdog.stalls_fired == 0  # healthy cycle: silent

    def test_debug_programs_rows_for_every_warmed_program(self, vital_server):
        server, cont, _ = vital_server
        status, body = _get(server.port, "/debug/programs")
        assert status == 200
        payload = json.loads(body)
        rows = {r["program"]: r for r in payload["programs"]}
        # the continuous ladder (toy engine has no VAE -> no pixel decode)
        assert {"prefill", "chunk", "release"} <= set(rows)
        for name in ("prefill", "chunk", "release"):
            row = rows[name]
            assert "error" not in row
            assert row["bytes_accessed"] > 0
            assert row["memory"]["argument_size_in_bytes"] > 0
        assert rows["chunk"]["flops"] > 0 and rows["prefill"]["flops"] > 0
        assert payload["peak_flops"] > 0 and payload["hbm_bps"] > 0

    def test_live_mfu_exported_after_traffic(self, vital_server):
        server, cont, _ = vital_server
        _post(server.port, {"prompt": "mfu", "seed": 5})
        assert cont.cost_table.mfu("chunk") is not None
        _, metrics = _get(server.port, "/metrics")
        assert 'dalle_serving_mfu{program="chunk"}' in metrics
        assert 'dalle_serving_hbm_gbps{program="chunk"}' in metrics

    def test_vitals_and_state_reflect_served_traffic(self, vital_server):
        server, cont, vitals = vital_server
        _post(server.port, {"prompt": "vitals", "seed": 7})
        status, body = _get(server.port, "/debug/vitals?n=8")
        assert status == 200
        payload = json.loads(body)
        assert payload["samples"]
        assert payload["stalls"] == []
        assert {s["slo"] for s in payload["slo"]} == {"ttft", "request"}
        assert all(s["burn_rate"] == 0.0 for s in payload["slo"])
        status, body = _get(server.port, "/debug/state")
        assert status == 200
        dump = json.loads(body)
        assert dump["engine"]["engine"] == "ContinuousEngine"
        assert dump["engine"]["chunk_index"] >= IMG_SEQ // 4
        assert dump["batcher"]["slots_active"] == 0  # idle between tests
        assert "worker_stacks" in dump
        # healthz shows the SLO status block alongside ok
        status, body = _get(server.port, "/healthz")
        health = json.loads(body)
        assert health["status"] == "ok" and "slo" in health
