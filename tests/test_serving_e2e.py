"""Serving integration: GenerationEngine semantics + the HTTP service.

Engine tests pin the serving-specific sampler contract (fixed-shape
padding, batch-composition-invariant per-seed RNG, per-row sampling
params). Server tests run the full stack — ThreadingHTTPServer →
MicroBatcher → engine — on localhost: two concurrent POST /generate
coalescing into one padded batch (occupancy > 1 in /metrics), plus the
overload/error paths against a fake engine. The slow-marked test drives
`serve.py` itself against a CLI-trained toy checkpoint.
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
from dalle_pytorch_tpu.models.dvae import DiscreteVAE
from dalle_pytorch_tpu.serving.engine import GenerationEngine, SampleSpec
from dalle_pytorch_tpu.serving.server import ServingServer
from dalle_pytorch_tpu.training.metrics import MetricsRegistry

TEXT_SEQ = 8
FMAP = 4
IMG_SEQ = FMAP * FMAP
IMG_PX = 16  # FMAP * 2**num_layers


def _build_engine(batch_shapes=(1, 2, 4), cond_scale=1.0):
    from dalle_pytorch_tpu.data.tokenizer import ByteTokenizer

    tokenizer = ByteTokenizer()
    vae = DiscreteVAE(
        image_size=IMG_PX, num_layers=2, num_tokens=32,
        codebook_dim=16, hidden_dim=16,
    )
    vae_params = vae.init(
        {"params": jax.random.PRNGKey(0), "gumbel": jax.random.PRNGKey(1)},
        jnp.zeros((1, IMG_PX, IMG_PX, 3)),
    )["params"]
    model = DALLE(
        dim=32, depth=2, heads=2, dim_head=8,
        num_image_tokens=32, image_fmap_size=FMAP,
        num_text_tokens=tokenizer.vocab_size, text_seq_len=TEXT_SEQ,
        shift_tokens=False, rotary_emb=True,
    )
    text = jnp.zeros((1, TEXT_SEQ), jnp.int32)
    toks = jnp.zeros((1, IMG_SEQ), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(42), text, toks)
    return GenerationEngine(
        model=model, variables=params, vae=vae, vae_params=vae_params,
        batch_shapes=batch_shapes, cond_scale=cond_scale,
        tokenizer=tokenizer, registry=MetricsRegistry(),
    )


@pytest.fixture(scope="module")
def engine():
    return _build_engine()


def spec(seed, temperature=1.0, top_k=0.9):
    ids = np.zeros(TEXT_SEQ, np.int32)
    ids[:3] = (5, 6, 7)
    return SampleSpec(ids, seed=seed, temperature=temperature, top_k=top_k)


class TestGenerationEngine:
    def test_shapes_padding_and_stats(self, engine):
        tokens, pixels = engine.generate([spec(0), spec(1)])
        assert tokens.shape == (2, IMG_SEQ) and tokens.dtype == np.int32
        assert (tokens >= 0).all() and (tokens < 32).all()
        assert pixels.shape == (2, IMG_PX, IMG_PX, 3)
        assert pixels.min() >= 0.0 and pixels.max() <= 1.0
        # 2 rows rounded up to the compiled shape 2 -> no padding; 3 rows
        # round up to 4
        before = engine.stats.rows_padded
        t3, _ = engine.generate([spec(2), spec(3), spec(4)])
        assert t3.shape == (3, IMG_SEQ)
        assert engine.stats.rows_padded == before + 1

    def test_pick_shape(self, engine):
        assert engine.pick_shape(1) == 1
        assert engine.pick_shape(2) == 2
        assert engine.pick_shape(3) == 4
        with pytest.raises(AssertionError):
            engine.pick_shape(5)

    def test_seed_determinism_and_variation(self, engine):
        a1, _ = engine.generate([spec(123)])
        a2, _ = engine.generate([spec(123)])
        b, _ = engine.generate([spec(124)])
        np.testing.assert_array_equal(a1, a2)
        assert not np.array_equal(a1, b), "different seeds must differ"

    def test_batch_composition_invariance(self, engine):
        """A request's tokens depend only on its (seed, prompt, params) —
        not on which micro-batch or padding slot it lands in. This is what
        makes dynamic batching transparent to callers."""
        alone, _ = engine.generate([spec(55)])
        batched, _ = engine.generate([spec(99), spec(55), spec(7)])
        np.testing.assert_array_equal(alone[0], batched[1])

    def test_per_row_sampling_params(self, engine):
        """Greedy rows (tiny temperature, keep-1 top-k) are deterministic
        across DIFFERENT seeds while stochastic rows vary — the per-row
        parameters really are per-row inside one batch."""
        greedy = [spec(s, temperature=1e-6, top_k=1.0) for s in (1, 2)]
        hot = [spec(s, temperature=1.0, top_k=0.0) for s in (1, 2)]
        toks, _ = engine.generate(greedy + hot)
        np.testing.assert_array_equal(toks[0], toks[1])
        assert not np.array_equal(toks[2], toks[3])

    def test_warmup_and_compile_counters(self):
        eng = _build_engine(batch_shapes=(1, 2))
        eng.warmup()
        assert eng.stats.compiled_shapes == (1, 2)
        misses = eng.registry.get(
            "dalle_serving_engine_compile_misses_total"
        ).value
        hits_before = eng.registry.get(
            "dalle_serving_engine_compile_hits_total"
        ).value
        eng.generate([spec(0)])
        assert eng.registry.get(
            "dalle_serving_engine_compile_misses_total"
        ).value == misses
        assert eng.registry.get(
            "dalle_serving_engine_compile_hits_total"
        ).value == hits_before + 1

    def test_rerank_without_clip_is_identity(self, engine):
        imgs = np.random.rand(3, IMG_PX, IMG_PX, 3).astype(np.float32)
        out, scores, order = engine.rerank("a prompt", imgs)
        np.testing.assert_array_equal(out, imgs)
        assert (scores == 0).all()
        np.testing.assert_array_equal(order, np.arange(3))

    def test_tokenize(self, engine):
        ids = engine.tokenize("red circle")
        assert ids.shape == (TEXT_SEQ,) and ids.dtype == np.int32
        assert (ids > 0).any()


# ------------------------------------------------------------- HTTP layer


def _post(port, body, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _get(port, path, timeout=10):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as resp:
        return resp.status, resp.read().decode()


def _scrape(metrics_text, name):
    for line in metrics_text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[-1])
    raise AssertionError(f"metric {name} not found")


class TestServingHTTP:
    def test_concurrent_requests_coalesce(self, engine):
        """The acceptance path: two concurrent POSTs arrive within the
        flush deadline and run as ONE padded batch — visible as a
        batch-occupancy observation > 1 in /metrics."""
        engine.warmup()  # all rungs compiled: request latency ~ms, << deadline
        server = ServingServer(
            engine, port=0, max_delay_ms=500, request_timeout_s=60
        ).start()
        try:
            port = server.port
            occ = engine.registry.get("dalle_serving_batch_occupancy_rows")
            base_batches, base_rows = occ.count, occ.sum

            results = {}

            def client(tag, seed):
                results[tag] = _post(
                    port, {"prompt": "small red circle", "seed": seed}
                )

            threads = [
                threading.Thread(target=client, args=(t, s))
                for t, s in (("a", 11), ("b", 22))
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)

            for tag in ("a", "b"):
                status, payload = results[tag]
                assert status == 200
                assert payload["shape"] == [1, IMG_PX, IMG_PX, 3]
                assert len(payload["tokens"]) == 1
                assert len(payload["tokens"][0]) == IMG_SEQ
                assert len(payload["images_png_b64"]) == 1
                import base64

                png = base64.b64decode(payload["images_png_b64"][0])
                assert png[:8] == b"\x89PNG\r\n\x1a\n"
            # both rows flushed in one batch: 1 more batch, 2 more rows
            assert occ.count == base_batches + 1, (
                "two concurrent requests should coalesce into one batch"
            )
            assert occ.sum == base_rows + 2

            # /healthz
            status, body = _get(port, "/healthz")
            health = json.loads(body)
            assert status == 200 and health["status"] == "ok"

            # /metrics: Prometheus text with the advertised instruments
            status, text = _get(port, "/metrics")
            assert status == 200
            assert _scrape(text, "dalle_serving_requests_total") >= 2
            assert _scrape(text, "dalle_serving_images_total") >= 2
            assert _scrape(text, "dalle_serving_queue_depth_rows") == 0
            assert _scrape(text, "dalle_serving_request_latency_seconds_p50") > 0
            assert _scrape(text, "dalle_serving_request_latency_seconds_p95") > 0
            assert "dalle_serving_batch_occupancy_rows_bucket" in text
            assert _scrape(
                text, "dalle_serving_engine_compile_hits_total"
            ) >= 1
        finally:
            server.shutdown()

    def test_seeded_request_reproducible_over_http(self, engine):
        server = ServingServer(
            engine, port=0, max_delay_ms=5, request_timeout_s=60
        ).start()
        try:
            body = {"prompt": "blue square", "seed": 777, "num_images": 2}
            _, p1 = _post(server.port, body)
            _, p2 = _post(server.port, body)
            assert p1["tokens"] == p2["tokens"]
            assert p1["seed"] == 777
        finally:
            server.shutdown()

    def test_bad_requests_rejected(self, engine):
        server = ServingServer(engine, port=0, max_delay_ms=5).start()
        try:
            port = server.port
            for body in (
                {"prompt": ""},
                {"prompt": "x", "num_images": 99},
                {"prompt": "x", "top_k": 7.0},
                {"prompt": "x", "seed": "abc"},
                {"prompt": "x", "seed": [1, 2]},
                {"prompt": "x", "temperature": -1.0},
                {"prompt": "x", "temperature": float("nan")},
                {"prompt": "x", "timeout_s": -1},
                {"prompt": "x", "timeout_s": float("nan")},
                {"prompt": "x", "timeout_s": 1e12},
                {"prompt": "x", "rerank": True},  # no CLIP loaded
                {"nope": 1},
            ):
                with pytest.raises(urllib.error.HTTPError) as e:
                    _post(port, body)
                assert e.value.code == 400
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(port, "/nope")
            assert e.value.code == 404
        finally:
            server.shutdown()


class FakeServingEngine:
    """Engine test double with the full surface ServingServer touches."""

    def __init__(self, block_event=None, fail=False, max_batch=4):
        from dalle_pytorch_tpu.serving.engine import EngineStats

        self.max_batch = max_batch
        self.batch_shapes = (max_batch,)
        self.registry = MetricsRegistry()
        self.stats = EngineStats()
        self.clip = None
        self.block_event = block_event
        self.fail = fail

    def tokenize(self, prompt):
        return np.zeros(8, np.int32)

    def generate(self, specs):
        if self.block_event is not None:
            assert self.block_event.wait(10.0)
        if self.fail:
            raise RuntimeError("engine exploded")
        # row i's tokens carry its seed so response pairing is checkable
        toks = np.stack(
            [np.full(4, s.seed, dtype=np.int32) for s in specs]
        )
        return toks, None


class RerankingFakeEngine(FakeServingEngine):
    """Returns pixels and a rerank that REVERSES row order, to pin the
    tokens/images/scores pairing contract of the response payload."""

    def __init__(self):
        super().__init__()
        self.clip = object()  # truthy: server includes clip_scores

    def generate(self, specs):
        toks, _ = super().generate(specs)
        pixels = np.zeros((len(specs), 4, 4, 3), np.float32)
        for i, s in enumerate(specs):
            pixels[i] = (s.seed % 7) / 7.0
        return toks, pixels

    def rerank(self, prompt, images):
        order = np.arange(len(images))[::-1]
        scores = np.arange(len(images), dtype=np.float32)[::-1]
        return images[order], scores, order


class TestServingRerank:
    def test_rerank_keeps_tokens_paired_with_images(self):
        server = ServingServer(
            RerankingFakeEngine(), port=0, max_delay_ms=5
        ).start()
        try:
            _, payload = _post(
                server.port,
                {"prompt": "x", "num_images": 3, "seed": 100, "rerank": True},
            )
            # rows were generated with seeds 100,101,102; reversal means
            # tokens come back 102,101,100 — matching the reordered images
            assert [t[0] for t in payload["tokens"]] == [102, 101, 100]
            assert payload["clip_scores"] == [2.0, 1.0, 0.0]
            assert payload["shape"] == [3, 4, 4, 3]
        finally:
            server.shutdown()


class TestServingOverloadPaths:
    def test_engine_error_returns_500_and_unhealthy(self):
        server = ServingServer(
            FakeServingEngine(fail=True), port=0, max_delay_ms=5
        ).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(server.port, {"prompt": "boom"})
            assert e.value.code == 500
            # fail fast is also visible to orchestrators via /healthz
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(server.port, "/healthz")
            assert e.value.code == 503
            assert "engine exploded" in json.loads(e.value.read())["last_error"]
        finally:
            server.shutdown()

    def test_queue_full_returns_503(self):
        gate = threading.Event()
        eng = FakeServingEngine(block_event=gate, max_batch=1)
        server = ServingServer(
            eng, port=0, max_delay_ms=1, max_queue_rows=1,
            request_timeout_s=30,
        ).start()
        try:
            port = server.port
            t1 = threading.Thread(
                target=lambda: _post(port, {"prompt": "a"})
            )
            t1.start()
            time.sleep(0.3)  # t1's request is in the engine, queue empty
            t2 = threading.Thread(
                target=lambda: _post(port, {"prompt": "b"})
            )
            t2.start()
            time.sleep(0.3)  # t2's request fills the 1-row queue
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(port, {"prompt": "c"})
            assert e.value.code == 503
            assert e.value.headers.get("Retry-After") == "1"
            gate.set()
            t1.join(timeout=10)
            t2.join(timeout=10)
        finally:
            server.shutdown()

    def test_queued_timeout_returns_504(self):
        gate = threading.Event()
        eng = FakeServingEngine(block_event=gate, max_batch=1)
        server = ServingServer(
            eng, port=0, max_delay_ms=1, request_timeout_s=30
        ).start()
        try:
            port = server.port
            t1 = threading.Thread(target=lambda: _post(port, {"prompt": "a"}))
            t1.start()
            time.sleep(0.3)
            # queued behind the blocked batch with a tiny timeout
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(port, {"prompt": "b", "timeout_s": 0.1})
            assert e.value.code == 504
            gate.set()
            t1.join(timeout=10)
        finally:
            server.shutdown()

    def test_health_recovers_after_transient_engine_error(self):
        eng = FakeServingEngine(fail=True)
        server = ServingServer(eng, port=0, max_delay_ms=5).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(server.port, {"prompt": "boom"})
            assert e.value.code == 500
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(server.port, "/healthz")
            assert e.value.code == 503
            eng.fail = False  # transient: the next batch succeeds
            status, _ = _post(server.port, {"prompt": "ok"})
            assert status == 200
            status, body = _get(server.port, "/healthz")
            assert status == 200 and json.loads(body)["status"] == "ok"
        finally:
            server.shutdown()

    def test_health_error_decays_without_traffic(self):
        """A health-gated router pulls traffic on 503, so the error must
        time out on its own — not wait for a successful batch that can
        never come."""
        eng = FakeServingEngine(fail=True)
        server = ServingServer(eng, port=0, max_delay_ms=5).start()
        server.error_window_s = 0.3
        try:
            with pytest.raises(urllib.error.HTTPError):
                _post(server.port, {"prompt": "boom"})
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(server.port, "/healthz")
            assert e.value.code == 503
            time.sleep(0.4)  # no traffic at all; the error window lapses
            status, body = _get(server.port, "/healthz")
            assert status == 200
            # the error is still reported for debugging, just not gating
            assert "engine exploded" in json.loads(body)["last_error"]
        finally:
            server.shutdown()

    def test_serve_forever_after_shutdown_returns(self):
        """A SIGTERM during startup shuts down before the serve loop runs;
        entering it afterwards must be a no-op, not a closed-socket crash."""
        server = ServingServer(FakeServingEngine(), port=0, max_delay_ms=1)
        server.shutdown()
        server.serve_forever()  # returns immediately

    def test_shutdown_before_start_does_not_hang(self):
        """socketserver's shutdown() waits on an event only serve_forever
        sets; a never-started server must still tear down cleanly."""
        server = ServingServer(FakeServingEngine(), port=0, max_delay_ms=1)
        t = threading.Thread(target=server.shutdown, daemon=True)
        t.start()
        t.join(timeout=5)
        assert not t.is_alive(), "shutdown() deadlocked on a never-started server"

    def test_shutdown_drains_inflight(self):
        gate = threading.Event()
        eng = FakeServingEngine(block_event=gate, max_batch=1)
        server = ServingServer(eng, port=0, max_delay_ms=1).start()
        port = server.port
        results = {}

        def client():
            results["r"] = _post(port, {"prompt": "a"})

        t = threading.Thread(target=client)
        t.start()
        time.sleep(0.3)
        gate.set()
        server.shutdown(drain=True)
        t.join(timeout=10)
        assert results["r"][0] == 200


@pytest.mark.slow
class TestServeCliEndToEnd:
    def test_serve_cli(self, tmp_path):
        """Train a toy checkpoint via the CLIs, start `serve.py`, POST two
        concurrent requests, assert coalescing + metrics, SIGINT-drain."""
        import signal
        import subprocess
        import sys

        from test_e2e import REPO, run_cli, _tiny_vae_ckpt

        vae_path = _tiny_vae_ckpt(tmp_path)
        run_cli(
            "train_dalle.py", "--image_text_folder", "rainbow:32",
            "--vae_path", str(vae_path),
            "--epochs", "1", "--batch_size", "8",
            "--set", "model.dim=64", "--set", "model.depth=1",
            "--set", "model.heads=2", "--set", "model.dim_head=16",
            "--set", "model.text_seq_len=32", "--set", "bf16=false",
            "--set", "log_images_freq=0",
            "--set", "debug=true", cwd=tmp_path,
        )
        ckpt = tmp_path / "checkpoints" / "dalle.npz"
        assert ckpt.exists()

        import os

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        trace_dump = tmp_path / "traces.json"
        proc = subprocess.Popen(
            [
                sys.executable, str(REPO / "serve.py"),
                "--dalle_path", str(ckpt), "--port", "0",
                "--batch_shapes", "1,2", "--max_delay_ms", "500",
                "--trace-dump", str(trace_dump),
            ],
            cwd=tmp_path, env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        try:
            port = None
            deadline = time.monotonic() + 600
            lines = []
            while time.monotonic() < deadline:
                line = proc.stdout.readline()
                if not line:
                    break
                lines.append(line)
                if "listening on" in line:
                    port = int(line.split("http://")[1].split()[0].rsplit(":", 1)[1])
                    break
            assert port is not None, f"server never came up:\n{''.join(lines)}"

            results = {}

            def client(tag, seed):
                results[tag] = _post(
                    port, {"prompt": "small red circle", "seed": seed},
                    timeout=120,
                )

            threads = [
                threading.Thread(target=client, args=(t, s))
                for t, s in (("a", 1), ("b", 2))
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            for tag in ("a", "b"):
                status, payload = results[tag]
                assert status == 200
                assert payload["shape"] == [1, 16, 16, 3]

            status, text = _get(port, "/metrics")
            assert status == 200
            assert _scrape(text, "dalle_serving_requests_total") == 2
            # the two concurrent requests coalesced into one 2-row batch
            assert _scrape(text, "dalle_serving_batches_total") == 1
            assert _scrape(text, "dalle_serving_batch_occupancy_rows_sum") == 2
            status, body = _get(port, "/healthz")
            assert json.loads(body)["status"] == "ok"

            status, body = _get(port, "/debug/traces")
            assert status == 200
            live = json.loads(body)
            assert any(
                e.get("name") == "generate" for e in live["traceEvents"]
            )

            proc.send_signal(signal.SIGINT)
            assert proc.wait(timeout=60) == 0
            # --trace-dump wrote a Perfetto-loadable file on drain
            dumped = json.loads(trace_dump.read_text())
            assert len(dumped["traceEvents"]) >= len(live["traceEvents"])
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)
