"""QoS serving under overload: priority admission, preemption, recovery.

The load-bearing contracts, in order of consequence:

  * PREEMPTION IS LATENCY, NEVER CORRECTNESS — a request suspended at a
    chunk boundary and resumed later returns tokens BIT-IDENTICAL to the
    un-preempted run, because decode RNG is (seed, image-position)-keyed
    and the re-admitted row restarts at position 0 (the same determinism
    decode-composition invariance pins in tests/test_continuous.py).
  * the weighted-fair scheduler BOUNDS starvation — a saturating
    low-class flood cannot push the high/normal classes' admission share
    below their weight ratio, and the low class itself is never starved
    outright.
  * RECOVERY LEAKS NOTHING — a dispatch failure mid-wave (injected
    deterministically via `serving/faults.py`) rebuilds engine state,
    leaves the block pool / prefix cache / slot allocator consistent
    (`PagedKVManager.leak_check`), and the suspended requests' bounded
    retry still produces bit-identical tokens.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dalle_pytorch_tpu.models.dalle import DALLE
from dalle_pytorch_tpu.obs.tracing import Tracer
from dalle_pytorch_tpu.serving.batcher import (
    ContinuousBatcher,
    QueueFullError,
    RequestCancelled,
    RequestTimeout,
)
from dalle_pytorch_tpu.serving.engine import (
    ContinuousEngine,
    PagedContinuousEngine,
    SampleSpec,
)
from dalle_pytorch_tpu.serving.faults import FaultInjector, InjectedFault
from dalle_pytorch_tpu.serving.paging import PagedKVManager
from dalle_pytorch_tpu.serving.qos import (
    ShedError,
    TenantQuotaError,
    WeightedFairQueue,
    priority_class,
)
from dalle_pytorch_tpu.serving.server import ServingServer
from dalle_pytorch_tpu.training.metrics import MetricsRegistry

from test_continuous import FakeContinuousEngine

TEXT_SEQ = 8
FMAP = 4
IMG_SEQ = FMAP * FMAP


# ------------------------------------------------------ weighted-fair queue


class _R:
    """Minimal request double for scheduler unit tests."""

    def __init__(self, name, priority="normal", tenant="", rows=1):
        self.name = name
        self.klass = priority_class(priority)
        self.tenant = tenant
        self.pending_rows = rows
        self.enqueued_at = time.monotonic()

    def __repr__(self):
        return f"_R({self.name})"


class TestWeightedFairQueue:
    def test_single_class_single_tenant_is_fifo(self):
        q = WeightedFairQueue()
        reqs = [_R(i) for i in range(5)]
        for r in reqs:
            q.push(r)
        assert [q.pop().name for _ in range(5)] == [0, 1, 2, 3, 4]

    def test_class_shares_follow_weights(self):
        """Backlogged high vs low: admissions split ~8:1 (the default
        weights), so low is throttled but NEVER starved — the stride
        scheduler's bound, pinned as 'at most 9 pops between low pops'."""
        q = WeightedFairQueue()
        for i in range(100):
            q.push(_R(f"h{i}", "high"))
            q.push(_R(f"l{i}", "low"))
        popped = [q.pop().name for _ in range(90)]
        lows = [i for i, n in enumerate(popped) if n.startswith("l")]
        assert 8 <= len(lows) <= 12, popped
        gaps = np.diff([-1] + lows)
        assert gaps.max() <= 9, "low class starved past the weight bound"

    def test_tenant_fairness_within_class(self):
        """One tenant flooding a class cannot starve another tenant in
        the same class: service alternates while both are backlogged."""
        q = WeightedFairQueue()
        for i in range(20):
            q.push(_R(f"a{i}", "low", tenant="a"))
        for i in range(3):
            q.push(_R(f"b{i}", "low", tenant="b"))
        popped = [q.pop().name for _ in range(6)]
        assert popped[0][0] == "a"  # a was first in, ties break stably
        # b's three requests all surface within the first six pops
        assert sum(1 for n in popped if n.startswith("b")) == 3

    def test_push_front_resumes_next_in_its_queue(self):
        q = WeightedFairQueue()
        a, b, c = _R("a"), _R("b"), _R("c")
        q.push(a)
        q.push(b)
        q.push_front(c)
        assert q.pop() is c

    def test_uncharged_pop_keeps_shares(self):
        q = WeightedFairQueue()
        q.push(_R("x", "low"))
        before = list(q._class_served)
        q.pop(charge=False)  # cancelled/expired: consumed nothing
        assert q._class_served == before

    def test_idle_class_banks_no_credit(self):
        """Reactivation clamp: a class that sat idle while another was
        served re-enters at the CURRENT minimum ratio — a low burst after
        a long high-only period gets its fair share, not a priority
        inversion worth the whole idle span."""
        q = WeightedFairQueue()
        for i in range(100):
            q.push(_R(f"h{i}", "high"))
        for _ in range(50):  # high-only service: high banks ratio 6.25
            q.pop()
        for i in range(10):  # low reactivates from empty
            q.push(_R(f"l{i}", "low"))
        popped = [q.pop().name for _ in range(18)]
        lows = sum(1 for n in popped if n.startswith("l"))
        assert lows <= 3, (
            f"stale credit let low run ahead of high: {popped}"
        )
        assert popped[0].startswith("h"), "tie must break to the better class"

    def test_rows_accounting(self):
        q = WeightedFairQueue()
        q.push(_R("a", "high", tenant="t", rows=2))
        q.push(_R("b", "low", tenant="t", rows=3))
        q.push(_R("c", "normal", rows=1))
        assert q.rows == 6
        assert q.tenant_rows("t") == 5
        assert q.class_depths() == {"high": 2, "normal": 1, "low": 3}
        assert q.rows_at_or_better(priority_class("high")) == 2
        assert q.rows_at_or_better(priority_class("normal")) == 3
        assert q.rows_at_or_better(priority_class("low")) == 6
        assert q.oldest_enqueued_at() is not None
        q.pop()
        q.pop()
        q.pop()
        assert q.rows == 0 and q.tenant_rows("t") == 0


# --------------------------------------------------- fake-engine QoS policy


class TestTenantWeights:
    """Per-tenant weighted shares (ROADMAP §5 follow-on): fairness within
    a class is proportional to configured weights, not equal."""

    def test_weights_split_service_proportionally(self):
        q = WeightedFairQueue(tenant_weights={"a": 4.0, "b": 1.0})
        for i in range(30):
            q.push(_R(f"a{i}", tenant="a"))
            q.push(_R(f"b{i}", tenant="b"))
        served = {"a": 0, "b": 0}
        for _ in range(25):
            served[q.pop().tenant] += 1
        # stride scheduling over rows_served/weight: a backlogged 4:1
        # pair splits admissions exactly 4:1
        assert served == {"a": 20, "b": 5}

    def test_unlisted_tenants_weigh_one(self):
        q = WeightedFairQueue(tenant_weights={"vip": 2.0})
        for i in range(20):
            q.push(_R(f"v{i}", tenant="vip"))
            q.push(_R(f"p{i}", tenant="pleb"))
        served = {"vip": 0, "pleb": 0}
        for _ in range(12):
            served[q.pop().tenant] += 1
        assert served == {"vip": 8, "pleb": 4}

    def test_idle_weighted_tenant_banks_no_credit(self):
        """The reactivation clamp scales by weight: a weight-4 tenant
        that sat idle re-enters at the current minimum RATIO (not raw
        rows), so it gets its 4:1 share from now on — not a catch-up
        burst for the idle period."""
        q = WeightedFairQueue(tenant_weights={"a": 4.0})
        for i in range(30):
            q.push(_R(f"b{i}", tenant="b"))
        for _ in range(20):
            q.pop()
        for i in range(30):
            q.push(_R(f"a{i}", tenant="a"))
        wins = {"a": 0, "b": 0}
        for _ in range(10):
            wins[q.pop().tenant] += 1
        assert wins == {"a": 8, "b": 2}

    def test_nonpositive_weight_rejected(self):
        with pytest.raises(AssertionError):
            WeightedFairQueue(tenant_weights={"a": 0.0})


class StepEngine(FakeContinuousEngine):
    """FakeContinuousEngine whose chunk boundary advances only when the
    test releases a permit — deterministic stepping for policy tests."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.step_sem = threading.Semaphore(0)

    def step_chunk(self):
        self.chunk_entered.set()
        assert self.step_sem.acquire(timeout=10), "no permit released"
        return super().step_chunk()


def _step(eng, n=1):
    """Release n chunk boundaries; returns once the worker is parked at
    the NEXT boundary entry (all admission/retire/reap/preempt work of
    the released boundaries is then complete)."""
    for _ in range(n):
        eng.chunk_entered.clear()
        eng.step_sem.release()
        assert eng.chunk_entered.wait(10)


def _until(eng, cond, max_steps=64):
    """Step boundaries until `cond()` holds (worker must be parked at a
    chunk entry, i.e. after a chunk_entered wait) — absorbs the race
    between test submissions and the worker's admission waves."""
    for _ in range(max_steps):
        if cond():
            return
        _step(eng)
    assert cond(), "condition never reached within the step budget"


def _finish(eng, reqs, timeout=20.0):
    """Drain: keep releasing boundaries until every request resolved.
    Permit-release + poll rather than `_step`: after the LAST retirement
    the worker parks idle in cond.wait and never re-enters a chunk, so
    waiting on chunk entry would hang exactly at the finish line."""
    deadline = time.monotonic() + timeout
    while not all(r.future.done() for r in reqs):
        assert time.monotonic() < deadline, "requests never finished"
        eng.step_sem.release()
        time.sleep(0.002)


def spec(seed, text=None):
    ids = np.zeros(TEXT_SEQ, np.int32) if text is None else text
    return SampleSpec(ids, seed=seed)


class TestPriorityPolicy:
    def test_high_overtakes_queued_low(self):
        """Slots full of low, queue holds more low, then a high arrives:
        the high's first token lands before every QUEUED low's."""
        eng = FakeContinuousEngine(chunk=2)
        b = ContinuousBatcher(eng, registry=eng.registry)
        running = [b.submit([spec(i)], priority="low") for i in range(4)]
        queued = [b.submit([spec(10 + i)], priority="low") for i in range(4)]
        high = b.submit([spec(99)], priority="high")
        for r in running + queued + [high]:
            r.future.result(timeout=10)
        assert high.first_token_at is not None
        assert all(
            high.first_token_at <= q.first_token_at for q in queued
        ), "queued low-class requests beat the high-class arrival"
        b.shutdown()

    def test_low_flood_cannot_starve_normal(self):
        """Starvation bound via trace timestamps: under a saturating
        low-class flood from one tenant, a normal-class request's queue
        time stays below the flood's slowest request."""
        eng = FakeContinuousEngine(chunk=4)
        b = ContinuousBatcher(eng, registry=eng.registry)
        tr = Tracer()
        flood = [
            b.submit(
                [spec(i)], priority="low", tenant="flooder",
                trace=tr.start_trace(),
            )
            for i in range(16)
        ]
        normal = b.submit(
            [spec(50)], priority="normal", trace=tr.start_trace()
        )
        for r in flood + [normal]:
            r.future.result(timeout=10)
            r.trace.finish()
        normal_queue = normal.trace.stage_seconds().get("queue", 0.0)
        flood_queues = [
            r.trace.stage_seconds().get("queue", 0.0) for r in flood
        ]
        assert normal_queue <= max(flood_queues), (
            "normal class waited longer than the whole low flood"
        )
        b.shutdown()

    def test_preempts_youngest_low_for_high(self):
        eng = StepEngine(chunk=1)  # 8 boundaries per image: slow decode
        b = ContinuousBatcher(eng, registry=eng.registry)
        tr = Tracer()
        lows = [
            b.submit([spec(i)], priority="low", trace=tr.start_trace())
            for i in range(4)
        ]
        assert eng.chunk_entered.wait(10)  # worker parked at a boundary
        _until(eng, lambda: b.allocator.n_active == 4)  # all four admitted
        high = b.submit([spec(9)], priority="high")
        _step(eng, 2)  # boundary 1: preempt fires; boundary 2: high admits
        assert lows[3].preemptions == 1, "victim must be the youngest low"
        assert all(lows[i].preemptions == 0 for i in range(3))
        fam = eng.registry.get("dalle_serving_preemptions_total")
        assert dict(fam.items())["priority"].value == 1
        # run everything to completion: resumed low re-prefills and ends
        _finish(eng, lows + [high])
        for r in lows + [high]:
            toks, _ = r.future.result(timeout=10)
        assert high.first_token_at <= lows[3].first_token_at or (
            lows[3].first_token_at is not None
        )
        fam = eng.registry.get("dalle_serving_resumptions_total")
        assert dict(fam.items())["priority"].value == 1
        # the preempted span landed in the victim's trace
        lows[3].trace.finish()
        assert "preempted" in lows[3].trace.stage_seconds()
        b.shutdown()

    def test_reserve_slots_hold_room_for_high(self):
        eng = StepEngine(chunk=1)
        b = ContinuousBatcher(eng, registry=eng.registry, reserve_slots=1)
        lows = [b.submit([spec(i)], priority="low") for i in range(4)]
        assert eng.chunk_entered.wait(10)
        _until(eng, lambda: b.allocator.n_active == 3)
        # only 3 of 4 slots go to the low class; one stays reserved
        _step(eng, 2)
        assert b.allocator.n_active == 3
        high = b.submit([spec(9)], priority="high")
        _until(eng, lambda: b.allocator.n_active == 4)  # reserve used
        _finish(eng, lows + [high])
        for r in lows + [high]:
            r.future.result(timeout=10)
        b.shutdown()

    def test_reserve_makes_wide_low_request_unadmittable_at_submit(self):
        """A non-high request wider than max_batch minus the reserve can
        NEVER admit — it must be rejected at submit, not queued to
        head-of-line-block its class forever."""
        eng = StepEngine(chunk=1)
        b = ContinuousBatcher(eng, registry=eng.registry, reserve_slots=1)
        with pytest.raises(QueueFullError, match="exceeds max batch"):
            b.submit([spec(i) for i in range(4)], priority="low")
        # the high class may still use the full slot set
        high = b.submit([spec(i) for i in range(4)], priority="high")
        _finish(eng, [high])
        high.future.result(timeout=10)
        b.shutdown()

    def test_preemption_churn_free_despite_stale_low_credit(self):
        """The finding-3 livelock setup: high banks heavy scheduler
        credit first, then a preempted low is re-queued — the clamp must
        keep the blocked high as the scheduler's pick, so the victim is
        preempted ONCE, not re-admitted and re-evicted every boundary."""
        eng = StepEngine(chunk=1)
        b = ContinuousBatcher(eng, registry=eng.registry)
        # bank high-class service credit
        warm = [b.submit([spec(i)], priority="high") for i in range(12)]
        _finish(eng, warm)
        lows = [b.submit([spec(50 + i)], priority="low") for i in range(4)]
        assert eng.chunk_entered.wait(10)
        _until(eng, lambda: b.allocator.n_active == 4)
        high = b.submit([spec(99)], priority="high")
        _until(eng, lambda: high.first_token_at is not None, max_steps=16)
        _finish(eng, lows + [high])
        assert sum(r.preemptions for r in lows) == 1, (
            "preempt/re-admit churn: victim evicted more than once"
        )
        b.shutdown()

    def test_cancel_mid_decode_releases_slot(self):
        eng = StepEngine(chunk=1)
        b = ContinuousBatcher(eng, registry=eng.registry)
        req = b.submit([spec(0)])
        assert eng.chunk_entered.wait(10)
        _until(eng, lambda: b.allocator.n_active == 1)  # admitted, decoding
        req.cancel()
        _finish(eng, [req])  # reaped at the next chunk boundary
        with pytest.raises(RequestCancelled):
            req.future.result(timeout=10)
        assert b.allocator.n_active == 0
        assert eng.registry.get("dalle_serving_cancelled_total").value == 1
        b.shutdown()

    def test_timeout_mid_decode_releases_slot(self):
        eng = StepEngine(chunk=1)
        b = ContinuousBatcher(eng, registry=eng.registry)
        req = b.submit([spec(0)], timeout_s=0.3)
        assert eng.chunk_entered.wait(10)
        _until(eng, lambda: b.allocator.n_active == 1)
        time.sleep(0.35)  # deadline passes while the row decodes
        _finish(eng, [req])
        with pytest.raises(RequestTimeout):
            req.future.result(timeout=10)
        assert b.allocator.n_active == 0
        assert eng.registry.get("dalle_serving_timeouts_total").value == 1
        b.shutdown()


class FailNthChunkEngine(FakeContinuousEngine):
    def __init__(self, fail_calls, **kw):
        super().__init__(**kw)
        self.fail_calls = set(fail_calls)
        self.chunk_calls = 0

    def step_chunk(self):
        self.chunk_calls += 1
        if self.chunk_calls in self.fail_calls:
            raise RuntimeError(f"injected chunk failure #{self.chunk_calls}")
        return super().step_chunk()


class TestDispatchRetry:
    def test_transient_failure_retries_to_completion(self):
        eng = FailNthChunkEngine({1}, chunk=4)
        b = ContinuousBatcher(eng, registry=eng.registry)
        req = b.submit([spec(7)])
        toks, _ = req.future.result(timeout=10)
        assert int(toks[0, 0]) == 7
        assert req.dispatch_retries == 1
        assert (
            eng.registry.get("dalle_serving_dispatch_retries_total").value
            == 1
        )
        fam = eng.registry.get("dalle_serving_resumptions_total")
        assert dict(fam.items())["dispatch_retry"].value == 1
        b.shutdown()

    def test_retry_budget_is_one(self):
        """A persistently failing engine costs each request exactly two
        dispatch attempts (original + the one bounded retry)."""
        eng = FakeContinuousEngine(fail_chunks=True)
        b = ContinuousBatcher(eng, registry=eng.registry)
        req = b.submit([spec(0)])
        with pytest.raises(RuntimeError, match="XLA fell over"):
            req.future.result(timeout=10)
        assert req.dispatch_retries == 1
        b.shutdown()


class TestShedQuotaRetryAfter:
    def _loaded_batcher(self, **kw):
        """Batcher with 4 rows decoding (worker parked in a chunk) so
        submissions stay queued."""
        eng = StepEngine(chunk=1)
        b = ContinuousBatcher(eng, registry=eng.registry, **kw)
        # distinct tenants so the background fill can't trip a per-tenant
        # quota while racing the worker's admission waves
        running = [
            b.submit([spec(i)], priority="low", tenant=f"bg{i}")
            for i in range(4)
        ]
        assert eng.chunk_entered.wait(10)
        _until(eng, lambda: b.allocator.n_active == 4)
        return eng, b, running

    def test_tenant_quota_429(self):
        eng, b, running = self._loaded_batcher(tenant_quota_rows=2)
        b.submit([spec(10)], tenant="t")
        b.submit([spec(11)], tenant="t")
        with pytest.raises(TenantQuotaError) as e:
            b.submit([spec(12)], tenant="t")
        assert e.value.retry_after_s >= 1.0
        b.submit([spec(13)], tenant="other")  # other tenants unaffected
        fam = eng.registry.get("dalle_serving_shed_total")
        assert dict(fam.items())["quota"].value == 1
        self._drain(eng, b, running)

    def test_deadline_shed_503(self):
        eng, b, running = self._loaded_batcher(deadline_shed=True)
        b._chunk_ema = 0.5  # measured basis: 8 chunks/image -> 4s/image
        with pytest.raises(ShedError) as e:
            b.submit([spec(10)], timeout_s=2.0)  # unmeetable
        assert e.value.reason == "deadline"
        assert 1.0 <= e.value.retry_after_s <= 60.0
        b.submit([spec(11)], timeout_s=120.0)  # meetable: admitted
        fam = eng.registry.get("dalle_serving_shed_total")
        assert dict(fam.items())["deadline"].value == 1
        self._drain(eng, b, running)

    def test_shed_disabled_admits(self):
        eng, b, running = self._loaded_batcher(deadline_shed=False)
        b._chunk_ema = 0.5
        b.submit([spec(10)], timeout_s=2.0)  # no shed model: queued
        self._drain(eng, b, running)

    def test_queue_full_retry_after_and_class_horizon(self):
        eng, b, running = self._loaded_batcher(max_queue_rows=4)
        b._chunk_ema = 0.1
        for i in range(4):
            b.submit([spec(20 + i)], priority="low")
        with pytest.raises(QueueFullError) as e:
            b.submit([spec(30)], priority="low")
        assert e.value.retry_after_s >= 1.0
        # the class horizon: high sees past the low flood's queue rows
        b.submit([spec(31)], priority="high")
        self._drain(eng, b, running)

    def _drain(self, eng, b, running):
        _finish(eng, running)
        b.shutdown(drain=False)


class TestSLOBurnAware:
    """Preemption-aware SLO burn (ROADMAP §5 follow-on): the batcher's
    `slo_burn` hook (wired to SLOTracker.max_burn by ServingServer)
    tightens admission and changes the preemption victim policy while
    the error budget burns."""

    def test_burn_tightens_deadline_shed_deterministically(self):
        eng = FakeContinuousEngine(chunk=4)
        b = ContinuousBatcher(eng, registry=eng.registry)
        burn = {"v": 0.0}
        b.slo_burn = lambda: burn["v"]
        # settle one request so the worker idles, then pin the cost
        # model: image time = 2 chunks x 1.0s EMA = 2.0s, empty backlog
        b.submit([spec(1)], timeout_s=30.0).future.result(timeout=10)
        b._chunk_ema = 1.0
        # burn <= 1: est completion 2.0s fits a 4s timeout -> admit is
        # exactly the burn-blind behavior
        burn["v"] = 0.5
        b._chunk_ema = 1.0
        b.submit([spec(2)], timeout_s=4.0).future.result(timeout=10)
        # burn 4x: admission budget tightens to 4s/4 = 1s < 2s -> shed,
        # attributed to the burn (the request WOULD fit its raw timeout)
        burn["v"] = 4.0
        b._chunk_ema = 1.0
        with pytest.raises(ShedError) as e:
            b.submit([spec(3)], timeout_s=4.0)
        assert e.value.reason == "slo_burn"
        assert e.value.retry_after_s >= 1.0
        fam = eng.registry.get("dalle_serving_shed_total")
        assert dict(fam.items())["slo_burn"].value == 1
        # a deadline-impossible request stays reason=deadline even while
        # burning (the burn did not cause that rejection)
        with pytest.raises(ShedError) as e:
            b.submit([spec(4)], timeout_s=1.0)
        assert e.value.reason == "deadline"
        # a broken burn source must not break admission
        b.slo_burn = lambda: (_ for _ in ()).throw(RuntimeError("boom"))
        b._chunk_ema = 1.0
        b.submit([spec(5)], timeout_s=4.0).future.result(timeout=10)
        b.shutdown()

    def test_burn_prefers_cheapest_redo_victim(self):
        """Victim selection under burn: evict the lower-class request
        with the LEAST decode progress (cheapest redo) instead of the
        youngest. Setup makes the two policies disagree: an OLDER
        single-row request has less total progress than a YOUNGER
        two-row one."""
        eng = StepEngine(chunk=2)
        eng.image_seq_len = 32  # long decode: nothing completes mid-test
        b = ContinuousBatcher(eng, registry=eng.registry)
        b.slo_burn = lambda: 2.0
        old = b.submit([spec(1)], priority="low")
        assert eng.chunk_entered.wait(10)
        _until(eng, lambda: b.allocator.n_active == 1)
        young = b.submit([spec(2), spec(3)], priority="low")
        _until(eng, lambda: b.allocator.n_active == 3)
        _step(eng, 3)
        # precondition: the policies disagree — the older request's one
        # row has less summed progress than the younger's two rows
        def progress(req):
            return sum(
                int(eng.pos[s])
                for s, (r, _) in b._inflight.items() if r is req
            )

        assert progress(old) < progress(young), (
            f"setup broken: old={progress(old)} young={progress(young)}"
        )
        assert old.admitted_seq < young.admitted_seq
        high = b.submit([spec(9), spec(10)], priority="high")
        _step(eng, 2)  # boundary 1: preempt; boundary 2: high admits
        assert old.preemptions == 1, (
            "burning: the cheapest-redo victim (least progress) must go"
        )
        assert young.preemptions == 0
        _finish(eng, [old, young, high])
        for r in (old, young, high):
            r.future.result(timeout=10)
        b.shutdown()


# ------------------------------------------- real engines: bit-identity


@pytest.fixture(scope="module")
def toy():
    model = DALLE(
        dim=32, depth=2, heads=2, dim_head=8,
        num_image_tokens=32, image_fmap_size=FMAP,
        num_text_tokens=64, text_seq_len=TEXT_SEQ,
        shift_tokens=True, rotary_emb=True,
    )
    text = jnp.zeros((1, TEXT_SEQ), jnp.int32)
    toks = jnp.zeros((1, IMG_SEQ), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(42), text, toks)
    return model, params


def _prompt(fill):
    ids = np.zeros(TEXT_SEQ, np.int32)
    ids[:4] = fill
    return ids


def _make_engine(toy, paged, prefix_entries=8):
    model, params = toy
    cls = PagedContinuousEngine if paged else ContinuousEngine
    kw = dict(page_size=8, prefix_entries=prefix_entries) if paged else {}
    return cls(
        model=model, variables=params, max_batch=2, chunk_tokens=2,
        prefill_batch=2, registry=MetricsRegistry(), **kw,
    )


def _wait_first_token(req, timeout=30.0):
    deadline = time.monotonic() + timeout
    while req.first_token_at is None:
        assert time.monotonic() < deadline, "request never produced a token"
        time.sleep(0.002)


class TestPreemptResumeBitIdentity:
    @pytest.mark.parametrize("paged", [False, True], ids=["slot", "paged"])
    def test_preempted_run_matches_unpreempted(self, toy, paged):
        """The acceptance pin: fill both slots with low, let them decode,
        then submit a high — the youngest low is preempted (slot released
        mid-decode) and later resumed from scratch; its final tokens must
        equal the un-preempted reference run bit for bit, and the
        preemption snapshot must be a prefix of them."""
        eng = _make_engine(toy, paged)
        b = ContinuousBatcher(eng, registry=eng.registry)
        victim_spec = spec(1234, _prompt((5, 6, 7, 8)))
        # reference: the same spec served without interference
        ref_toks, _ = b.submit([victim_spec]).future.result(timeout=120)

        other = b.submit([spec(5, _prompt((1, 1, 2, 2)))], priority="low")
        victim = b.submit([victim_spec], priority="low")
        _wait_first_token(victim)  # decoding, tokens exist
        high = b.submit([spec(9, _prompt((3, 3, 4, 4)))], priority="high")
        h_toks, _ = high.future.result(timeout=120)
        v_toks, _ = victim.future.result(timeout=120)
        other.future.result(timeout=120)

        assert victim.preemptions == 1, "high had no free slot: must preempt"
        assert high.preemptions == 0
        np.testing.assert_array_equal(v_toks, ref_toks)
        snap = victim.preempt_snapshots[0]
        assert len(snap) >= 1
        np.testing.assert_array_equal(v_toks[0][: len(snap)], snap)
        fam = eng.registry.get("dalle_serving_resumptions_total")
        assert dict(fam.items())["priority"].value == 1
        if paged:
            # the resume admitted through the prefix cache (near-zero
            # re-prefill — the PR 6 wiring this layer exists to use)
            assert victim.prefix_hit is True
            assert eng.kv.leak_check() == []
        b.shutdown()


# ------------------------------------------- real engines: fault injection


class TestFaultInjectedRecovery:
    def test_midwave_prefill_failure_leaves_pool_consistent(self, toy):
        """Injected failure on the first prefill wave: the donated-state
        rebuild resets pool/cache/tables, the batcher's bounded retry
        re-admits both requests, tokens still match the reference, and
        the page pool audits clean with admissions still working.
        Prefix caching is disabled so the reference runs don't register
        the prompts — a repeat admission must run a REAL prefill wave
        for the injected prefill fault to have a dispatch to hit."""
        eng = _make_engine(toy, paged=True, prefix_entries=0)
        b = ContinuousBatcher(eng, registry=eng.registry)
        specs = [spec(11, _prompt((9, 9, 1, 1))), spec(22, _prompt((9, 9, 2, 2)))]
        refs = [
            b.submit([s]).future.result(timeout=120)[0] for s in specs
        ]
        eng.faults = FaultInjector().fail_nth("prefill", 1)
        reqs = [b.submit([s], priority="low") for s in specs]
        outs = [r.future.result(timeout=120)[0] for r in reqs]
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        assert eng.faults.fired and eng.faults.fired[0]["program"] == "prefill"
        assert (
            eng.registry.get("dalle_serving_dispatch_retries_total").value
            == len([r for r in reqs if r.dispatch_retries])
        )
        assert eng.kv.leak_check() == [], "failed wave leaked pages/refs"
        # the pool still admits after the rebuild
        again = b.submit([spec(33, _prompt((7, 7, 7, 7)))])
        again.future.result(timeout=120)
        assert eng.kv.leak_check() == []
        b.shutdown()

    def test_chunk_failure_midflight_recovers_bit_identical(self, toy):
        eng = _make_engine(toy, paged=True)
        b = ContinuousBatcher(eng, registry=eng.registry)
        s = spec(77, _prompt((2, 4, 6, 8)))
        ref, _ = b.submit([s]).future.result(timeout=120)
        eng.faults = FaultInjector().fail_nth("chunk", 2)
        req = b.submit([s])
        out, _ = req.future.result(timeout=120)
        np.testing.assert_array_equal(out, ref)
        assert req.dispatch_retries == 1
        assert eng.kv.leak_check() == []
        b.shutdown()

    def test_exhausted_retry_fails_clean(self, toy):
        eng = _make_engine(toy, paged=True)
        b = ContinuousBatcher(eng, registry=eng.registry)
        eng.faults = FaultInjector().fail_nth("prefill", 1).fail_nth(
            "prefill", 2
        )
        req = b.submit([spec(5, _prompt((1, 2, 3, 4)))])
        with pytest.raises(InjectedFault):
            req.future.result(timeout=120)
        assert req.dispatch_retries == 1
        assert eng.kv.leak_check() == []
        # rules exhausted: the engine serves again
        ok = b.submit([spec(6, _prompt((4, 3, 2, 1)))])
        ok.future.result(timeout=120)
        assert eng.kv.leak_check() == []
        b.shutdown()

    def test_stall_rule_delays_but_completes(self, toy):
        eng = _make_engine(toy, paged=False)
        b = ContinuousBatcher(eng, registry=eng.registry)
        eng.faults = FaultInjector().stall_nth("chunk", 1, seconds=0.05)
        req = b.submit([spec(3, _prompt((6, 6, 6, 6)))])
        req.future.result(timeout=120)
        assert eng.faults.fired[0]["kind"] == "stall"
        b.shutdown()


class TestLeakCheck:
    def _kv(self):
        return PagedKVManager(
            n_rows=2, page_size=4, max_positions=17, text_positions=9,
            n_pages=16, max_entries=4,
        )

    def test_clean_lifecycle_audits_clean(self):
        kv = self._kv()
        ids = np.arange(TEXT_SEQ, dtype=np.int32)
        assert kv.leak_check() == []
        kv.admit_miss(0, ids, register=False)
        kv.ensure(0, 3)
        assert kv.leak_check() == []
        kv.release(0)
        assert kv.leak_check() == []

    def test_detects_refcount_drift(self):
        kv = self._kv()
        kv.admit_miss(0, np.arange(TEXT_SEQ, dtype=np.int32), register=False)
        kv.pool._ref[int(kv.table[0, 0])] += 1  # simulated leak
        assert any("refcount" in p for p in kv.leak_check())

    def test_detects_reservation_drift(self):
        kv = self._kv()
        kv.admit_miss(0, np.arange(TEXT_SEQ, dtype=np.int32), register=False)
        kv._debt[0] += 1
        assert any("pages_per_row" in p for p in kv.leak_check())


# ------------------------------------------------------------- HTTP layer


def _post(port, body, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


class TestHTTPQoS:
    def test_priority_tenant_and_qos_surfaces(self, toy):
        from dalle_pytorch_tpu.data.tokenizer import ByteTokenizer

        eng = _make_engine(toy, paged=False)
        eng.tokenizer = ByteTokenizer()
        server = ServingServer(eng, port=0, request_timeout_s=60).start()
        try:
            port = server.port
            status, payload = _post(
                port,
                {"prompt": "red", "priority": "high", "tenant": "acme",
                 "seed": 3},
            )
            assert status == 200 and len(payload["tokens"][0]) == IMG_SEQ

            with pytest.raises(urllib.error.HTTPError) as e:
                _post(port, {"prompt": "red", "priority": "urgent"})
            assert e.value.code == 400

            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10
            ) as resp:
                health = json.loads(resp.read())
            qos = health["qos"]
            assert qos["queue_by_class"] == {
                "high": 0, "normal": 0, "low": 0
            }
            assert qos["preempt_enabled"] is True
            assert "preemptions" in qos and "shed" in qos

            # the metric families render with their reason labels
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as resp:
                text = resp.read().decode()
            assert "dalle_serving_queue_depth_rows_by_class" in text
            assert "dalle_serving_dispatch_retries_total" in text
        finally:
            server.shutdown()

    def test_quota_429_with_retry_after(self, toy):
        from dalle_pytorch_tpu.data.tokenizer import ByteTokenizer

        eng = _make_engine(toy, paged=False)
        eng.tokenizer = ByteTokenizer()
        # quota 0: every tenanted submission is over quota — the cheapest
        # deterministic way to drive the 429 path over real HTTP
        server = ServingServer(
            eng, port=0, request_timeout_s=60, tenant_quota_rows=0
        ).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(server.port, {"prompt": "red", "tenant": "flooder"})
            assert e.value.code == 429
            retry = e.value.headers.get("Retry-After")
            assert retry is not None and int(retry) >= 1
        finally:
            server.shutdown()
