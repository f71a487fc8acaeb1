"""Replica fleet router: health-aware routing, failover, drain — chaos-tested.

The load-bearing contracts, in order of consequence:

  * FAILOVER IS LATENCY, NEVER CORRECTNESS — the router pins the seed
    before the first dispatch and decode is (seed, position)-keyed, so a
    request re-dispatched after a replica crash/wedge returns tokens
    BIT-IDENTICAL to the undisturbed run (chaos pin: kill a real replica
    mid-decode under concurrent load; 100% of requests still complete).
  * RETRIES CANNOT AMPLIFY AN OUTAGE — the retry budget refills as a
    fraction of recent successes; during a full-fleet outage total
    dispatch attempts stay within `M + initial_budget`, and recovery
    resumes service with no router restart.
  * A ROLLING RESTART IS A ZERO-ERROR EVENT — drain stops new
    admissions, waits out the replica's outstanding rows, then ejects
    it; every in-flight and subsequent request completes.
  * a flapping replica cannot absorb live traffic — the circuit opens on
    an error burst, probes back off exponentially, and recovery goes
    through one half-open trial request.

Stub replicas (scriptable HTTP servers) drive the policy/state-machine
tests with a stubbed router clock but REAL sockets; the chaos pins run
against real toy `ContinuousEngine` replicas behind real `ServingServer`s.
"""

import io
import json
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dalle_pytorch_tpu.models.dalle import DALLE
from dalle_pytorch_tpu.obs.logging import StructuredLog
from dalle_pytorch_tpu.obs.tracing import Tracer
from dalle_pytorch_tpu.serving.engine import ContinuousEngine
from dalle_pytorch_tpu.serving.faults import FaultInjector
from dalle_pytorch_tpu.serving.router import (
    FleetRouter,
    RetryBudget,
    RouterServer,
    format_route_header,
    parse_route_header,
)
from dalle_pytorch_tpu.serving.server import ServingServer
from dalle_pytorch_tpu.training.metrics import MetricsRegistry

TEXT_SEQ = 8
FMAP = 4
IMG_SEQ = FMAP * FMAP


# ------------------------------------------------------------ stub fleet


class _StubHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):
        pass

    def do_GET(self):
        owner = self.server.owner
        if self.path.startswith("/healthz"):
            code = owner.health_code
            body = json.dumps({"status": owner.health_tier}).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        else:
            self.send_error(404)

    def do_POST(self):
        owner = self.server.owner
        length = int(self.headers.get("Content-Length", "0") or 0)
        raw = self.rfile.read(length) if length else b"{}"
        with owner.lock:
            owner.hits += 1
            owner.requests.append({
                "path": self.path,
                "body": json.loads(raw or b"{}"),
                "route": self.headers.get("x-dalle-route"),
                "trace": self.headers.get("x-dalle-trace"),
            })
            behavior = owner.behavior
            delay = owner.delay_s
        if self.path.startswith("/admin/"):
            self._json(200, {"ok": True})
            return
        if delay:
            time.sleep(delay)
        if behavior == "ok":
            body = owner.requests[-1]["body"]
            self._json(200, {
                "tokens": [[int(body.get("seed", 0))] * 4],
                "seed": body.get("seed"),
                "replica": owner.name,
                "route": owner.requests[-1]["route"],
                "trace": owner.requests[-1]["trace"],
                "trace_id": "deadbeefdeadbeef",
            })
        elif behavior == "error":
            self._json(500, {"error": "engine fell over"})
        elif behavior == "busy":
            self._json(
                503, {"error": "queue full"},
                [("Retry-After", str(owner.retry_after))],
            )
        elif behavior == "quota":
            self._json(
                429, {"error": "tenant over quota"},
                [("Retry-After", str(owner.retry_after))],
            )
        elif behavior == "reset":
            raise ConnectionError("stub reset")  # socket dies, no response
        else:
            raise AssertionError(f"unknown behavior {behavior}")

    def _json(self, code, payload, extra=()):
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in extra:
            self.send_header(k, v)
        self.end_headers()
        try:
            self.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            pass


class _StubServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True


class StubReplica:
    """Scriptable replica: behavior switchable mid-test, every request
    recorded (the chaos assertions count dispatch attempts here)."""

    def __init__(self, name="stub"):
        self.name = name
        self.behavior = "ok"
        self.delay_s = 0.0
        self.retry_after = 7
        self.health_code = 200
        self.health_tier = "ok"
        self.hits = 0
        self.requests = []
        self.lock = threading.Lock()
        self._httpd = _StubServer(("127.0.0.1", 0), _StubHandler)
        self._httpd.owner = self
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, kwargs={"poll_interval": 0.02},
            daemon=True,
        )
        self._thread.start()

    @property
    def port(self):
        return self._httpd.server_address[1]

    @property
    def url(self):
        return f"http://127.0.0.1:{self.port}"

    def kill(self):
        """Hard socket kill: nothing listens afterwards (ECONNREFUSED)."""
        self._httpd.shutdown()
        self._httpd.server_close()

    close = kill


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += float(s)


def _wait_for(cond, deadline_s=10.0, interval_s=0.005):
    """Deadline-poll a predicate instead of sleeping a fixed interval —
    the drain tests need "requests are in flight NOW", and a flat
    sleep(0.1) is both flaky under CPU contention (threads not yet
    dispatched) and slack on fast machines."""
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval_s)
    return cond()


def _fleet(n=2, clock=None, **kw):
    stubs = [StubReplica(f"r{i}") for i in range(n)]
    kw.setdefault("probe_interval_s", 0.5)
    router = FleetRouter(
        [f"{s.name}={s.url}" for s in stubs],
        registry=MetricsRegistry(),
        time_fn=clock if clock is not None else time.monotonic,
        **kw,
    )
    return stubs, router


def _route(router, body=None, headers=None):
    raw = json.dumps(body or {"prompt": "x", "seed": 1}).encode()
    status, resp, extra = router.handle_generate(raw, headers or {})
    payload = json.loads(resp) if resp else {}
    return status, payload, dict(extra)


def _counter(registry, name, label=None):
    fam = registry.get(name)
    if fam is None:
        return 0
    if label is not None:
        items = dict(fam.items())
        return int(items[label].value) if label in items else 0
    if hasattr(fam, "items"):
        return int(sum(c.value for _, c in fam.items()))
    return int(fam.value)


# ----------------------------------------------------------- retry budget


class TestRetryBudget:
    def test_refills_on_success_fraction(self):
        b = RetryBudget(ratio=0.5, initial=1.0, cap=2.0)
        assert b.withdraw() and not b.withdraw()
        for _ in range(2):
            b.deposit()
        assert b.balance == 1.0
        assert b.withdraw() and not b.withdraw()

    def test_cap_bounds_banked_credit(self):
        b = RetryBudget(ratio=1.0, initial=0.0, cap=3.0)
        for _ in range(50):
            b.deposit()
        assert b.balance == 3.0

    def test_counters(self):
        b = RetryBudget(ratio=0.0, initial=1.0)
        assert b.withdraw() and not b.withdraw()
        assert b.withdrawn == 1 and b.denied == 1


# ---------------------------------------------------------- header codec


class TestRouteHeader:
    def test_round_trip(self):
        assert parse_route_header(format_route_header("west", 2, True)) == {
            "replica": "west", "attempt": 2, "hedged": True,
        }

    @pytest.mark.parametrize("junk", [
        None, "", "x", "a;b;c", "a;1;2", "a b;1;0", ";;", "a;1", 7,
        "a;99999;0",
    ])
    def test_garbage_rejected(self, junk):
        assert parse_route_header(junk) is None


# -------------------------------------------------------- routing policy


class TestRoutingPolicy:
    def test_idle_fleet_spreads_traffic(self):
        stubs, router = _fleet(2)
        try:
            for i in range(10):
                status, payload, _ = _route(
                    router, {"prompt": "x", "seed": i}
                )
                assert status == 200
            assert stubs[0].hits >= 3 and stubs[1].hits >= 3
        finally:
            for s in stubs:
                s.kill()

    def test_seed_pinned_when_client_sent_none(self):
        stubs, router = _fleet(1)
        try:
            status, payload, _ = _route(router, {"prompt": "x"})
            assert status == 200
            sent = stubs[0].requests[0]["body"]
            assert isinstance(sent["seed"], int)
            assert payload["seed"] == sent["seed"]
        finally:
            stubs[0].kill()

    def test_degraded_replica_serves_high_not_low(self):
        clock = FakeClock()
        stubs, router = _fleet(2, clock=clock)
        try:
            stubs[0].health_tier = "degraded"
            router.probe_once()
            assert router.replicas[0].health == "degraded"
            stubs[1].kill()  # only the degraded replica remains
            # give the breaker a clean slate: mark r1 ejected via probes
            for _ in range(router.eject_after_probe_failures):
                clock.advance(router.probe_interval_s + 0.01)
                router.probe_once()
            assert router.replicas[1].health == "ejected"
            status, payload, _ = _route(
                router, {"prompt": "x", "seed": 1, "priority": "high"}
            )
            assert status == 200 and payload["replica"] == "r0"
            status, payload, _ = _route(
                router, {"prompt": "x", "seed": 2, "priority": "low"}
            )
            assert status == 503  # low may not touch a degraded replica
            assert "Retry-After" in _route(
                router, {"prompt": "x", "seed": 3, "priority": "low"}
            )[2]
        finally:
            stubs[0].kill()

    def test_retry_after_cools_that_class_only(self):
        clock = FakeClock()
        stubs, router = _fleet(2, clock=clock)
        try:
            stubs[0].behavior = "busy"
            stubs[0].retry_after = 30
            # normal request: r0 backpressures -> served by r1
            status, payload, _ = _route(router, {"prompt": "x", "seed": 1})
            assert status == 200 and payload["replica"] == "r1"
            assert _counter(
                router.registry, "dalle_router_failovers_total",
                "backpressure",
            ) == 1
            hits_before = stubs[0].hits
            # r0 now cooled for "normal": next normal goes straight to r1
            status, payload, _ = _route(router, {"prompt": "x", "seed": 2})
            assert status == 200 and payload["replica"] == "r1"
            assert stubs[0].hits == hits_before
            # but "high" is NOT cooled: r0 is tried again (and cools high)
            status, payload, _ = _route(
                router, {"prompt": "x", "seed": 3, "priority": "high"}
            )
            assert status == 200 and payload["replica"] == "r1"
            assert stubs[0].hits == hits_before + 1
            # cooldown expires on the stubbed clock
            stubs[0].behavior = "ok"
            clock.advance(31.0)
            stubs[1].kill()
            status, payload, _ = _route(router, {"prompt": "x", "seed": 4})
            assert status == 200 and payload["replica"] == "r0"
        finally:
            stubs[0].kill()

    def test_tenant_quota_429_passes_through_uncooled(self):
        """A 429 is tenant-scoped: the client sees its own quota error
        (with the replica's Retry-After), the replica is NOT cooled for
        the class, and other tenants keep routing to it."""
        stubs, router = _fleet(2)
        try:
            stubs[0].behavior = "quota"
            stubs[0].retry_after = 9
            status, payload, extra = _route(router, {"prompt": "x", "seed": 1})
            assert status == 429 and extra.get("Retry-After") == "9"
            assert stubs[1].hits == 0, "429 must not fail over"
            with router._lock:
                assert not router.replicas[0].cooldowns, (
                    "tenant quota must not cool the replica for the class"
                )
            # a different (under-quota) tenant's request may still land
            # on r0 once it heals
            stubs[0].behavior = "ok"
            status, _, _ = _route(router, {"prompt": "x", "seed": 2})
            assert status == 200
        finally:
            for s in stubs:
                s.kill()

    def test_bad_request_rejected_without_dispatch(self):
        stubs, router = _fleet(1)
        try:
            status, payload, _ = _route(router, {"prompt": "x", "priority": "vip"})
            assert status == 400
            status, _, _ = router.handle_generate(b"not json", {})
            assert status == 400
            assert stubs[0].hits == 0
        finally:
            stubs[0].kill()

    def test_replica_500_fails_over_exactly_once(self):
        stubs, router = _fleet(2)
        try:
            stubs[0].behavior = "error"
            status, payload, _ = _route(router, {"prompt": "x", "seed": 1})
            assert status == 200  # 500 fails over
            total = stubs[0].hits + stubs[1].hits
            assert total == 2
            assert _counter(
                router.registry, "dalle_router_failovers_total", "status"
            ) == 1
        finally:
            for s in stubs:
                s.kill()


# ------------------------------------------------------ failover + breaker


class TestFailoverAndBreaker:
    def test_transport_failure_fails_over(self):
        stubs, router = _fleet(2)
        try:
            stubs[0].kill()  # hard socket kill: ECONNREFUSED
            ok = 0
            for i in range(4):
                status, payload, _ = _route(
                    router, {"prompt": "x", "seed": i}
                )
                ok += status == 200
            assert ok == 4
            assert _counter(
                router.registry, "dalle_router_failovers_total", "transport"
            ) >= 1
        finally:
            stubs[1].kill()

    def test_error_burst_opens_circuit_and_trial_closes_it(self):
        clock = FakeClock()
        stubs, router = _fleet(
            2, clock=clock, error_min_samples=2, error_rate_threshold=0.5,
        )
        try:
            stubs[0].behavior = "error"
            for i in range(3):
                status, _, _ = _route(router, {"prompt": "x", "seed": i})
                assert status == 200  # r1 carries every request
            assert router.replicas[0].health == "ejected"
            assert router.replicas[0].ejected_reason == "error_rate"
            hits = stubs[0].hits
            for i in range(3):  # ejected: r0 sees NO live traffic
                _route(router, {"prompt": "x", "seed": 10 + i})
            assert stubs[0].hits == hits
            # recovery: replica heals, probe half-opens after the backoff
            stubs[0].behavior = "ok"
            clock.advance(router.replicas[0].probe_backoff_s + 0.01)
            router.probe_once()
            assert router.replicas[0].health == "half_open"
            # the trial request closes the circuit
            for i in range(4):
                status, _, _ = _route(router, {"prompt": "x", "seed": 20 + i})
                assert status == 200
            assert router.replicas[0].health == "healthy"
            assert stubs[0].hits > hits
        finally:
            for s in stubs:
                s.kill()

    def test_failed_trial_reopens_with_deeper_backoff(self):
        clock = FakeClock()
        stubs, router = _fleet(
            2, clock=clock, error_min_samples=2, error_rate_threshold=0.5,
        )
        try:
            stubs[0].behavior = "error"
            for i in range(3):
                _route(router, {"prompt": "x", "seed": i})
            first_backoff = router.replicas[0].probe_backoff_s
            stubs[0].health_tier = "ok"  # healthz lies; dispatches still fail
            clock.advance(first_backoff + 0.01)
            router.probe_once()
            assert router.replicas[0].health == "half_open"
            _route(router, {"prompt": "x", "seed": 9})  # trial fails
            assert router.replicas[0].health == "ejected"
            assert router.replicas[0].ejected_reason == "trial"
            assert router.replicas[0].probe_backoff_s > first_backoff
        finally:
            for s in stubs:
                s.kill()

    def test_probe_failures_eject_and_backoff_caps(self):
        clock = FakeClock()
        stubs, router = _fleet(
            2, clock=clock, probe_backoff_s=1.0, probe_backoff_max_s=4.0,
        )
        try:
            stubs[0].kill()
            for _ in range(router.eject_after_probe_failures):
                clock.advance(router.probe_interval_s + 0.01)
                router.probe_once()
            rep = router.replicas[0]
            assert rep.health == "ejected" and rep.ejected_reason == "probe"
            for _ in range(6):  # ejected probes keep failing: backoff caps
                clock.advance(rep.probe_backoff_s + 0.01)
                router.probe_once()
            assert rep.probe_backoff_s == 4.0
        finally:
            stubs[1].kill()


# ------------------------------------------------------------ tail hedging


class TestHedging:
    def test_hedge_first_wins_and_counts(self):
        stubs, router = _fleet(2, hedge_after_ms=50.0)
        try:
            slow = next(s for s in stubs if s.name == "r0")
            slow.delay_s = 2.0
            t0 = time.monotonic()
            status, payload, extra = _route(
                router, {"prompt": "x", "seed": 5}
            )
            latency = time.monotonic() - t0
            assert status == 200
            assert payload["replica"] == "r1", "hedge's answer must win"
            assert latency < 1.5, "first-wins: no waiting out the slow primary"
            assert _counter(router.registry, "dalle_router_hedges_total") == 1
            assert _counter(
                router.registry, "dalle_router_hedge_wins_total"
            ) == 1
        finally:
            for s in stubs:
                s.kill()

    def test_fast_primary_never_hedges(self):
        stubs, router = _fleet(2, hedge_after_ms=500.0)
        try:
            for i in range(3):
                status, _, _ = _route(router, {"prompt": "x", "seed": i})
                assert status == 200
            assert _counter(router.registry, "dalle_router_hedges_total") == 0
        finally:
            for s in stubs:
                s.kill()


# --------------------------------------------- retry budget: the outage pin


class TestRetryBudgetUnderOutage:
    def test_full_outage_attempts_stay_within_budget_and_recovery(self):
        """The acceptance pin: every replica failing, M requests cost at
        most M + initial_budget dispatch attempts fleet-wide (the budget
        refills only on success, so a dead fleet cannot be hammered),
        every client gets an orderly 5xx, and when the fleet heals the
        SAME router resumes service — no restart, no manual reset."""
        clock = FakeClock()
        stubs, router = _fleet(
            3, clock=clock,
            retry_budget_initial=4.0, retry_budget_ratio=0.25,
            error_min_samples=10_000,  # breaker off: count raw attempts
        )
        try:
            for s in stubs:
                s.behavior = "error"  # FULL outage: nothing succeeds
            M = 15
            statuses = []
            for i in range(M):
                status, _, _ = _route(router, {"prompt": "x", "seed": i})
                statuses.append(status)
            total_attempts = sum(s.hits for s in stubs)
            assert total_attempts <= M + 4, (
                f"retry amplification: {total_attempts} attempts for {M} "
                "requests against a budget of 4"
            )
            assert all(s in (500, 503) for s in statuses), statuses
            assert router.budget.balance < 1.0
            # fleet heals: service resumes through the same router
            for s in stubs:
                s.behavior = "ok"
            for i in range(6):
                status, _, _ = _route(router, {"prompt": "x", "seed": 100 + i})
                assert status == 200
            # successes refilled retry capacity (0.25 x 6 > 1)
            assert router.budget.balance >= 1.0
        finally:
            for s in stubs:
                s.kill()

    def _outage_setup(self):
        clock = FakeClock()
        stubs, router = _fleet(2, clock=clock, retry_budget_initial=2.0)
        for s in stubs:
            s.behavior = "error"
        return clock, stubs, router

    def test_budget_exhausted_is_an_orderly_503(self):
        clock, stubs, router = self._outage_setup()
        try:
            seen = set()
            for i in range(6):
                status, payload, _ = _route(router, {"prompt": "x", "seed": i})
                seen.add(status)
            assert seen <= {500, 503}
        finally:
            for s in stubs:
                s.kill()


# ------------------------------------------------------------ downed fleet


def _stub_everything_ejected(clock, stubs, router):
    for s in stubs:
        s.kill()
    for _ in range(router.eject_after_probe_failures):
        clock.advance(router.probe_interval_s + 0.01)
        router.probe_once()


class TestUnroutable:
    def test_all_ejected_rejects_fast_with_retry_after(self):
        clock = FakeClock()
        stubs, router = _fleet(2, clock=clock)
        _stub_everything_ejected(clock, stubs, router)
        assert all(r.health == "ejected" for r in router.replicas)
        t0 = time.monotonic()
        status, payload, extra = _route(router, {"prompt": "x", "seed": 1})
        assert status == 503 and "Retry-After" in extra
        assert time.monotonic() - t0 < 1.0, "unroutable must fail FAST"
        assert _counter(
            router.registry, "dalle_router_unroutable_total"
        ) == 1
        healthy, detail = router.health()
        assert not healthy and detail["status"] == "unhealthy"


# -------------------------------------------------------------- HTTP layer


def _http(method, port, path, body=None, headers=None, timeout=10):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=(json.dumps(body).encode() if body is not None
              else (b"" if method == "POST" else None)),
        headers={"Content-Type": "application/json", **(headers or {})},
        method=method,
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read() or b"{}"), dict(
            resp.headers
        )


class TestRouterHTTP:
    def test_generate_healthz_metrics_debug_and_admin(self):
        stubs, router = _fleet(2)
        server = RouterServer(router, port=0, probes=False).start()
        try:
            port = server.port
            status, payload, headers = _http(
                "POST", port, "/generate", {"prompt": "x", "seed": 3}
            )
            assert status == 200 and payload["tokens"] == [[3, 3, 3, 3]]
            assert headers.get("x-dalle-replica") in ("r0", "r1")

            status, health, _ = _http("GET", port, "/healthz")
            assert status == 200 and health["status"] == "ok"
            assert health["role"] == "router"

            status, detail, _ = _http("GET", port, "/debug/replicas")
            assert {r["name"] for r in detail["replicas"]} == {"r0", "r1"}
            assert "retry_budget" in detail

            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as resp:
                text = resp.read().decode()
            assert "dalle_router_replica_state" in text
            assert "dalle_router_retry_budget" in text

            # admin drain via HTTP, then undrain
            status, d, _ = _http(
                "POST", port, "/admin/drain?replica=r0&wait_s=2"
            )
            assert status == 200 and d["mode"] == "drained"
            for i in range(4):  # r0 out of rotation
                _http("POST", port, "/generate", {"prompt": "x", "seed": i})
            assert all(
                r["body"].get("seed") == 3 for r in stubs[0].requests
            ), "drained replica must see no new traffic"
            status, d, _ = _http("POST", port, "/admin/undrain?replica=r0")
            assert status == 200 and d["mode"] == "active"

            with pytest.raises(urllib.error.HTTPError) as e:
                _http("POST", port, "/admin/drain?replica=nope")
            assert e.value.code == 404
            with pytest.raises(urllib.error.HTTPError) as e:
                _http("POST", port, "/admin/drain")
            assert e.value.code == 400
        finally:
            server.shutdown()
            for s in stubs:
                s.kill()

    def test_trace_context_parented_and_route_header_stamped(self):
        stubs, router = _fleet(1)
        server = RouterServer(router, port=0, probes=False).start()
        try:
            trace_id = "abcd1234abcd1234"
            _http(
                "POST", server.port, "/generate",
                {"prompt": "x", "seed": 1},
                headers={"x-dalle-trace": f"{trace_id}/client:h:1:0"},
            )
            sent = stubs[0].requests[0]
            # the router ADOPTED the inbound trace id and parented the
            # replica hop into its own dispatch span
            assert sent["trace"].startswith(trace_id + "/")
            parent_uid = sent["trace"].split("/", 1)[1]
            assert parent_uid.startswith(f"{router.site}:")
            # routing decision rides the route header
            assert parse_route_header(sent["route"]) == {
                "replica": "r0", "attempt": 0, "hedged": False,
            }
        finally:
            server.shutdown()
            stubs[0].kill()


# ---------------------------------------- drain under load (stub replicas)


class TestDrainUnderLoad:
    def test_drain_waits_out_inflight_and_routes_around(self):
        stubs, router = _fleet(2)
        try:
            for s in stubs:
                s.delay_s = 0.3
            results = []
            threads = [
                threading.Thread(
                    target=lambda i=i: results.append(
                        _route(router, {"prompt": "x", "seed": i})[0]
                    )
                )
                for i in range(4)
            ]
            for t in threads:
                t.start()
            # requests are in flight on both replicas: each stub counts
            # the hit on arrival, then holds the request for delay_s
            assert _wait_for(
                lambda: stubs[0].hits >= 1 and stubs[1].hits >= 1
            ), "requests never reached both replicas"
            detail = router.drain("r0", wait_s=5.0)
            assert detail["mode"] == "drained"
            assert detail["outstanding_rows"] == 0
            for t in threads:
                t.join(timeout=10)
            assert results == [200, 200, 200, 200], (
                "drain must be a zero-error event"
            )
            hits = stubs[0].hits
            for i in range(3):
                status, _, _ = _route(router, {"prompt": "x", "seed": 10 + i})
                assert status == 200
            assert stubs[0].hits == hits, "drained replica got new traffic"
            router.undrain("r0")
            assert router.replicas[0].mode == "active"
        finally:
            for s in stubs:
                s.kill()

    def test_drain_propagates_to_replica_admin(self):
        stubs, router = _fleet(2)
        try:
            router.drain("r0", wait_s=1.0, propagate=True)
            admin = [
                r for r in stubs[0].requests
                if r["path"].startswith("/admin/drain")
            ]
            assert admin, "propagate=1 must hit the replica's own drain"
            router.undrain("r0", propagate=True)
            assert any(
                r["path"].startswith("/admin/undrain")
                for r in stubs[0].requests
            )
        finally:
            for s in stubs:
                s.kill()


# --------------------------------------- replica-side admin + log stamping


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


def _replica_server(toy, log=None, **kw):
    from dalle_pytorch_tpu.data.tokenizer import ByteTokenizer

    model, params = toy
    eng = ContinuousEngine(
        model=model, variables=params, max_batch=2, chunk_tokens=2,
        prefill_batch=2, registry=MetricsRegistry(),
    )
    eng.tokenizer = ByteTokenizer()
    return eng, ServingServer(
        eng, port=0, request_timeout_s=60, log=log, **kw
    ).start()


class TestReplicaAdminDrain:
    def test_drain_refuses_intake_reversibly(self, toy):
        eng, server = _replica_server(toy)
        try:
            port = server.port
            status, d, _ = _http("POST", port, "/admin/drain")
            assert status == 200 and d["draining"] is True
            # healthz reports draining at 503 (a router pulls it)
            with pytest.raises(urllib.error.HTTPError) as e:
                _http("GET", port, "/healthz")
            assert e.value.code == 503
            assert json.loads(e.value.read())["drain"]["quiesced"] is True
            # new work refused with Retry-After
            with pytest.raises(urllib.error.HTTPError) as e:
                _http("POST", port, "/generate", {"prompt": "x", "seed": 1})
            assert e.value.code == 503
            assert e.value.headers.get("Retry-After") is not None
            # undrain restores service end to end
            status, d, _ = _http("POST", port, "/admin/undrain")
            assert status == 200 and d["draining"] is False
            status, health, _ = _http("GET", port, "/healthz")
            assert status == 200
            status, payload, _ = _http(
                "POST", port, "/generate",
                {"prompt": "red", "seed": 3}, timeout=120,
            )
            assert status == 200 and len(payload["tokens"][0]) == IMG_SEQ
        finally:
            server.shutdown()

    def test_route_header_stamped_into_request_log_and_state_dump(self, toy):
        stream = io.StringIO()
        log = StructuredLog(stream=stream, site="repl-a")
        eng, server = _replica_server(toy, log=log)
        try:
            status, payload, _ = _http(
                "POST", server.port, "/generate",
                {"prompt": "red", "seed": 3},
                headers={"x-dalle-route": format_route_header(
                    "repl-a", 2, True
                )},
                timeout=120,
            )
            assert status == 200
            lines = [
                json.loads(l) for l in stream.getvalue().splitlines()
            ]
            req_lines = [l for l in lines if l.get("event") == "request"]
            assert req_lines, "no request log line written"
            line = req_lines[-1]
            # routing decision attributable per attempt...
            assert line["replica"] == "repl-a"
            assert line["attempt"] == 2 and line["hedged"] is True
            # ...joined against the stable process identity
            assert line["site"] == "repl-a" and "host" in line and "pid" in line
            # /debug/state carries the same identity triple
            status, dump, _ = _http("GET", server.port, "/debug/state")
            assert dump["identity"]["site"] == "repl-a"
            assert {"site", "pid", "host"} <= set(dump["identity"])
            # a malformed route header stamps nothing
            status, payload, _ = _http(
                "POST", server.port, "/generate",
                {"prompt": "red", "seed": 4},
                headers={"x-dalle-route": "garbage;;;"}, timeout=120,
            )
            assert status == 200
            line = [
                json.loads(l) for l in stream.getvalue().splitlines()
                if json.loads(l).get("event") == "request"
            ][-1]
            assert "attempt" not in line
        finally:
            server.shutdown()


# ------------------------------------------------- chaos: real toy replicas


def _post_generate(port, body, timeout=120, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


class TestChaosRealReplicas:
    """The acceptance pin: 3 REAL in-process replicas (toy
    ContinuousEngine behind ServingServer), one killed/wedged mid-decode
    under concurrent load — 100% completion, bit-identical tokens for
    re-dispatched requests, zero client-visible errors for a drain."""

    def _fleet(self, toy, n=3, **router_kw):
        servers = []
        for _ in range(n):
            _, server = _replica_server(toy)
            servers.append(server)
        router_kw.setdefault("attempt_timeout_s", 30.0)
        router = FleetRouter(
            [f"r{i}=http://127.0.0.1:{s.port}" for i, s in enumerate(servers)],
            registry=MetricsRegistry(),
            **router_kw,
        )
        front = RouterServer(router, port=0, probes=False).start()
        return servers, router, front

    def test_replica_wedged_mid_decode_all_complete_bit_identical(self, toy):
        """The chaos pin. Reference pass over a healthy 3-replica fleet;
        then one replica's chunk dispatch is wedged (FaultInjector
        stall past the router's attempt timeout — the request is
        mid-decode when the wedge bites) under concurrent open-loop
        load: every request still completes, re-dispatched requests
        return bit-identical tokens, and once the wedged replica is
        hard-killed (socket gone, ECONNREFUSED) the fleet keeps
        serving.

        Deflaked (the PR 12 contention flake): the wedge is an
        EVENT-HELD stall (released in the teardown) instead of a 6s
        sleep, so a contention-stretched run can never see the wedged
        replica come back mid-assertion; and the attempt timeout is 4s
        (toy decode is ~100x faster), so a slow healthy replica under
        CPU contention is not misread as wedged — the budgets no longer
        ride on wall-clock races."""
        servers, router, front = self._fleet(toy, attempt_timeout_s=4.0)
        unwedge = threading.Event()
        try:
            port = front.port
            seeds = [101, 102, 103, 104]
            bodies = [
                {"prompt": "red circle", "seed": s, "timeout_s": 60}
                for s in seeds
            ]
            # reference pass over the healthy fleet (same seeds — decode
            # is (seed, position)-keyed, so these ARE the ground truth)
            refs = {}
            for body in bodies:
                status, payload = _post_generate(port, body)
                assert status == 200
                refs[body["seed"]] = payload["tokens"]

            # wedge replica 0: its next chunk dispatch holds until the
            # test releases it — longer than any attempt timeout by
            # construction — freezing every row it holds MID-DECODE;
            # requests routed there must fail over
            servers[0].engine.faults = FaultInjector().stall_nth(
                "chunk", 1, until=unwedge
            )

            results = {}
            errors = []

            def client(body):
                try:
                    status, payload = _post_generate(port, body)
                    if status != 200:
                        errors.append((body["seed"], status))
                    else:
                        results[body["seed"]] = payload["tokens"]
                except Exception as exc:
                    errors.append((body["seed"], repr(exc)))

            threads = [
                threading.Thread(target=client, args=(b,)) for b in bodies
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not errors, f"chaos run had client-visible errors: {errors}"
            assert set(results) == set(seeds), "not every request completed"
            for seed in seeds:
                np.testing.assert_array_equal(
                    results[seed], refs[seed],
                    err_msg=f"failover changed tokens for seed {seed}",
                )
            # at least one request really did leave the wedged replica
            assert _counter(
                router.registry, "dalle_router_failovers_total", "transport"
            ) >= 1, "no request ever timed out off the wedged replica"

            # escalate: hard socket kill of the wedged replica
            # (ECONNREFUSED from now on) — the fleet must keep serving.
            # Release the wedge first so the worker thread can exit.
            unwedge.set()
            servers[0].shutdown(drain=False)
            for seed in (201, 202):
                status, payload = _post_generate(
                    port, {"prompt": "after the crash", "seed": seed,
                           "timeout_s": 60}
                )
                assert status == 200
        finally:
            unwedge.set()
            front.shutdown()
            for s in servers[1:]:
                s.shutdown()

    def test_replica_dead_before_dispatch_fails_over(self, toy):
        """Crash-kill flavor: the replica is GONE (connection refused)
        when the dispatch happens — failover completes bit-identically
        against the healthy-fleet reference."""
        servers, router, front = self._fleet(toy)
        try:
            port = front.port
            body = {"prompt": "crash", "seed": 555, "timeout_s": 60}
            status, payload = _post_generate(port, body)
            assert status == 200
            ref = payload["tokens"]
            servers[0].shutdown(drain=False)  # corpse
            for _ in range(3):  # every retry lands somewhere alive
                status, payload = _post_generate(port, body)
                assert status == 200
                np.testing.assert_array_equal(payload["tokens"], ref)
        finally:
            front.shutdown()
            for s in servers[1:]:
                s.shutdown()

    def test_drain_during_load_is_zero_error_and_rejoin(self, toy):
        servers, router, front = self._fleet(toy)
        try:
            port = front.port
            seeds = list(range(300, 306))
            statuses = []

            def client(seed):
                status, _ = _post_generate(
                    port, {"prompt": "drain", "seed": seed, "timeout_s": 60}
                )
                statuses.append(status)

            threads = [
                threading.Thread(target=client, args=(s,)) for s in seeds
            ]
            for t in threads:
                t.start()
            # drain only once the burst is actually being served: rows
            # outstanding somewhere, or (fast machines) already finished
            assert _wait_for(
                lambda: len(statuses) > 0
                or sum(r.outstanding_rows for r in router.replicas) > 0,
                deadline_s=30.0,
            ), "burst never reached the fleet"
            detail = router.drain("r1", wait_s=30.0, propagate=True)
            assert detail["mode"] == "drained"
            for t in threads:
                t.join(timeout=120)
            assert statuses == [200] * len(seeds), (
                f"rolling restart leaked errors: {statuses}"
            )
            # the drained replica can restart without anyone noticing:
            # here we just verify it holds no outstanding rows and is out
            # of rotation, then rejoin it
            assert router._find("r1").outstanding_rows == 0
            router.undrain("r1", propagate=True)
            status, _ = _post_generate(
                port, {"prompt": "back", "seed": 999, "timeout_s": 60}
            )
            assert status == 200
        finally:
            front.shutdown()
            for s in servers:
                s.shutdown()


# --------------------------------------------------------- the router CLI


@pytest.mark.slow
def test_serve_cli_router_mode_e2e():
    """`serve.py --router` end to end as a subprocess: readiness line,
    routed /generate, /debug/replicas, clean SIGTERM shutdown."""
    import os
    import re
    import signal as signal_mod
    import subprocess
    import sys
    from pathlib import Path

    stub = StubReplica("r0")
    proc = subprocess.Popen(
        [sys.executable, "serve.py", "--router",
         "--replicas", f"edge=http://127.0.0.1:{stub.port}",
         "--port", "0", "--probe_interval_s", "0.2"],
        cwd=Path(__file__).resolve().parents[1],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        line = ""
        for _ in range(200):
            line = proc.stdout.readline()
            if "[router] listening" in line:
                break
        m = re.search(r"http://127\.0\.0\.1:(\d+)", line)
        assert m, f"no readiness line: {line!r}"
        port = int(m.group(1))
        status, payload, _ = _http(
            "POST", port, "/generate", {"prompt": "x", "seed": 7}
        )
        assert status == 200 and payload["tokens"] == [[7, 7, 7, 7]]
        status, detail, _ = _http("GET", port, "/debug/replicas")
        assert detail["replicas"][0]["name"] == "edge"
        proc.send_signal(signal_mod.SIGTERM)
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
        stub.kill()
