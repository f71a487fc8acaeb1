#!/usr/bin/env python
"""Prove on the chip that the main path still starts: train -> generate -> serve.

Drives the README's flow through the CLIs a user would call, at the full
width of the flagship (dim 1024, depth 12, 16 heads x 64, text 256 + 32x32
image tokens, bf16, default attn_impl=auto -> Pallas flash attention and
flash decode, 256 px / 8192-code dVAE, the shipped 32k BPE), with random
weights from the seed and the smallest step/request counts that still
exercise every program:

    train_vae.py -> train_dalle.py -> generate.py -> serve.py (micro engine)
    -> serve.py --engine continuous --compile_cache D (cold)
    -> the same replica again (must boot warm: zero uncached compiles)

    python chip_smoke.py             one chip; what the driver runs
    python chip_smoke.py --chips 4   only the mesh paths and what they are
                                     compared with (fsdp=2 x tp=2 training
                                     vs one device; serve --mesh dp=1,tp=4
                                     vs an unsharded replica)
    python chip_smoke.py --rehearse  the same control flow at a toy size on
                                     the CPU (Pallas in interpret mode); it
                                     can never print the "ok": true line

One process per chip: this parent never imports JAX. It runs the CLIs as
children, one after another, each exited before the next starts, and takes
the device description from the `[device]` line a child printed. Any phase
that fails ends the run with a non-zero exit and `"ok": false`; a platform
other than `tpu` is a failure, never a fallback. The last stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Everything is written under `chip_smoke_out/` (wiped at start), and
`native/build/` is removed once the preflight has found the chip, so the
tokenizer library is built from the committed source. The XLA compile cache follows the repo's one rule
(`JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_cache`), so a second run
in the same place reports cache hits.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chip_smoke_out"
#: the driver's limit is 1200 s; stop (and reap every child) before it does
DEADLINE_S = 1170

FLAGSHIP = dict(
    model=[
        "model.dim=1024", "model.depth=12", "model.heads=16",
        "model.dim_head=64", "model.text_seq_len=256",
        "model.shift_tokens=true", "model.rotary_emb=true",
    ],
    # batch 16 without remat is 21 MB over the v5e's HBM (the compiler says
    # so, tests/test_tpu_compile.py); with remat it fits
    train=["model.reversible=true"],
    # --chips 4 only: the scan executor (same math, one layer body in the
    # HLO) compiles the sharded programs several times faster, and a
    # four-chip call is charged four times over; one chip runs the default
    four_chip_train=["model.executor=scan"],
    vae=["vae.image_size=256"],  # 3 layers, 8192 codes: 32x32 = 1024 tokens
    image_size=256, image_tokens=1024, num_image_tokens=8192,
    batch=16, pairs=64,  # 4 optimizer steps
    serve_shapes="1,4", num_images=4,
)
REHEARSAL = dict(
    model=[
        "model.dim=64", "model.depth=2", "model.heads=4",
        "model.dim_head=16", "model.text_seq_len=16",
        "model.shift_tokens=true", "model.rotary_emb=true",
        "model.attn_impl=flash",  # auto would stay dense at this length
    ],
    train=["model.reversible=true"],
    four_chip_train=["model.executor=scan"],
    vae=["vae.image_size=32", "vae.num_layers=2", "vae.num_tokens=64",
         "vae.codebook_dim=32", "vae.hidden_dim=16"],
    image_size=32, image_tokens=64, num_image_tokens=64,
    batch=4, pairs=16,
    serve_shapes="1,2", num_images=2,
)

PROMPT = "small red circle"
DEVICE_RE = re.compile(r"^\[device\] (\{.*\})$", re.M)
COMPILES_RE = re.compile(r"^\[compiles\] (\{.*\})$", re.M)
LOSS_RE = re.compile(r"^\d+ (\d+) loss - (\S+)$", re.M)
PLACEMENT_RE = re.compile(r"^\[placement\] (\{.*\})$", re.M)
LISTEN_RE = re.compile(r"^\[serve\] listening on (http://\S+)", re.M)


class SmokeFailure(Exception):
    def __init__(self, phase: str, error: str):
        super().__init__(f"{phase}: {error}")
        self.phase, self.error = phase, error


class Smoke:
    def __init__(self, size: dict, chips: int, rehearse: bool):
        self.size, self.chips, self.rehearse = size, chips, rehearse
        self.device = None
        self.children = []

    # ------------------------------------------------------------ children

    def _env(self):
        env = dict(os.environ)
        env.setdefault("TPU_LOG_DIR", "disabled")
        if self.rehearse:
            env["JAX_PLATFORMS"] = "cpu"
            if self.chips > 1:
                env["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "")
                    + f" --xla_force_host_platform_device_count={self.chips}"
                ).strip()
        return env

    def _spawn(self, phase: str, cmd: list):
        log = OUT / "logs" / f"{phase}.log"
        log.parent.mkdir(parents=True, exist_ok=True)
        proc = subprocess.Popen(
            [sys.executable, *cmd], cwd=OUT, env=self._env(),
            stdout=log.open("w"), stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        self.children.append(proc)
        return proc, log

    def reap(self):
        """Stop every process this run started (whole process groups)."""
        for proc in self.children:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()

    def check_device(self, phase: str, text: str) -> dict:
        m = DEVICE_RE.search(text)
        if not m:
            raise SmokeFailure(phase, "child printed no [device] line")
        dev = json.loads(m.group(1))
        want = "cpu" if self.rehearse else "tpu"
        if dev["platform"] != want or dev["count"] != self.chips:
            raise SmokeFailure(
                phase, f"needs {self.chips} {want} device(s), JAX found {dev}"
            )
        self.device = dev
        return dev

    def run_cli(self, phase: str, cmd: list, timeout: float) -> str:
        """One batch CLI to completion; its output, or SmokeFailure."""
        t0 = time.monotonic()
        proc, log = self._spawn(phase, cmd)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.reap()
            raise SmokeFailure(phase, f"timed out after {timeout:.0f}s ({log})")
        text = log.read_text()
        if rc != 0:
            raise SmokeFailure(
                phase, f"exit {rc}: " + " | ".join(text.splitlines()[-6:])
            )
        self.check_device(phase, text)
        self.last_seconds = round(time.monotonic() - t0, 1)
        return text

    def report(self, phase: str, **fields):
        print(json.dumps({"phase": phase, **fields}), flush=True)

    @staticmethod
    def compiles(phase: str, text: str) -> dict:
        m = COMPILES_RE.search(text)
        if not m:
            raise SmokeFailure(phase, "child printed no [compiles] line")
        return json.loads(m.group(1))

    @staticmethod
    def losses(phase: str, text: str, want_steps: int) -> list:
        found = [float(v) for _, v in LOSS_RE.findall(text)]
        if len(found) != want_steps:
            raise SmokeFailure(
                phase, f"expected {want_steps} logged losses, got {found}"
            )
        if not all(math.isfinite(v) and v > 0 for v in found):
            raise SmokeFailure(phase, f"loss not finite and positive: {found}")
        return found

    # -------------------------------------------------------------- phases

    def preflight(self):
        """Which device would the children get? A child says; on anything
        but the expected platform the run ends here, before any work."""
        text = self.run_cli(
            "preflight",
            ["-c", "import sys; sys.path.insert(0, sys.argv[1]); "
             "from dalle_pytorch_tpu.utils.device import log_device; "
             "log_device()", str(ROOT)],
            timeout=180,
        )
        self.report("preflight", device=self.device, seconds=self.last_seconds)
        # from here on the run is real: build the tokenizer library from the
        # committed source, never from a binary that came with the copy
        shutil.rmtree(ROOT / "native" / "build", ignore_errors=True)

    def train_vae(self):
        s = self.size
        pairs = s["pairs"]
        vae = OUT / "vae.npz"
        text = self.run_cli(
            "train_vae",
            [str(ROOT / "train_vae.py"), "--image_folder", f"rainbow:{pairs}",
             "--epochs", "1", "--batch_size", str(s["batch"]),
             "--output", str(vae), *_sets(s["vae"]),
             *_sets([f"output_dir={OUT / 'vae_run'}", "log_every_n_steps=1"])],
            timeout=420,
        )
        steps = pairs // s["batch"]
        losses = self.losses("train_vae", text, steps)
        if not vae.exists():
            raise SmokeFailure("train_vae", f"no checkpoint at {vae}")
        self.report("train_vae", steps=steps, losses=losses,
                    compiles=self.compiles("train_vae", text),
                    seconds=self.last_seconds)
        return vae

    def train_dalle(self, vae: Path, tag: str, extra=()):
        s = self.size
        run_dir = OUT / tag
        text = self.run_cli(
            tag,
            [str(ROOT / "train_dalle.py"),
             "--image_text_folder", f"rainbow:{s['pairs']}",
             "--vae_path", str(vae), "--epochs", "1",
             "--batch_size", str(s["batch"]),
             *_sets(s["model"] + s["train"] + list(extra)),
             *_sets([f"output_dir={run_dir}", "log_every_n_steps=1"])],
            timeout=720,
        )
        steps = s["pairs"] // s["batch"]
        losses = self.losses(tag, text, steps)
        ckpt = run_dir / "dalle.npz"
        if not ckpt.exists():
            raise SmokeFailure(tag, f"no checkpoint at {ckpt}")
        placement = PLACEMENT_RE.search(text)
        placement = json.loads(placement.group(1))["bytes_in_use"] if placement else None
        self.report(tag, steps=steps, batch=s["batch"], losses=losses,
                    bytes_in_use_per_device=placement,
                    compiles=self.compiles(tag, text),
                    seconds=self.last_seconds)
        return ckpt, losses, placement

    def generate(self, ckpt: Path):
        s = self.size
        images = OUT / "images"
        text = self.run_cli(
            "generate",
            [str(ROOT / "generate.py"), "--dalle_path", str(ckpt),
             "--text", PROMPT, "--num_images", str(s["num_images"]),
             "--batch_size", str(s["num_images"]),
             "--outputs_dir", str(images), "--seed", "0"],
            timeout=480,
        )
        pngs = sorted(images.glob("*/[0-9]*.png"))
        if len(pngs) != s["num_images"]:
            raise SmokeFailure("generate", f"expected {s['num_images']} images, found {pngs}")
        for png in pngs:
            w, h = _png_size(png)
            if (w, h) != (s["image_size"], s["image_size"]):
                raise SmokeFailure("generate", f"{png.name} is {w}x{h}")
        self.report("generate", images=len(pngs),
                    pixels=[s["image_size"], s["image_size"]],
                    compiles=self.compiles("generate", text),
                    seconds=self.last_seconds)

    def serve(self, phase: str, ckpt: Path, args: list, seeds: list,
              want_warm: bool = False):
        """Boot `serve.py`, wait for readiness, answer seeded requests,
        check /healthz, SIGINT, expect a clean exit. Returns
        {seed: tokens} (first request of each seed)."""
        s = self.size
        t0 = time.monotonic()
        proc, log = self._spawn(
            phase,
            [str(ROOT / "serve.py"), "--dalle_path", str(ckpt), "--port", "0",
             "--batch_shapes", s["serve_shapes"], "--request_timeout_s", "300",
             "--profile_dir", str(OUT / "profiles"), *args],
        )
        url = None
        while url is None:
            if proc.poll() is not None:
                raise SmokeFailure(
                    phase, f"exited {proc.returncode} before it listened: "
                    + " | ".join(log.read_text().splitlines()[-6:])
                )
            if time.monotonic() - t0 > 600:
                self.reap()
                raise SmokeFailure(phase, f"not listening after 600s ({log})")
            m = LISTEN_RE.search(log.read_text())
            url = m.group(1) if m else None
            time.sleep(0.5)
        boot_s = round(time.monotonic() - t0, 1)
        text = log.read_text()
        self.check_device(phase, text)
        events = _events(text)
        warm = events.get("warmup_done")
        if not warm or warm["compiles"] <= 0:
            raise SmokeFailure(phase, f"warmup compiled nothing: {warm}")
        if want_warm:
            plan = events.get("boot_cache_plan", {})
            if (plan.get("mode") != "warm" or warm["uncached_compiles"] != 0
                    or warm["boot_cache_mode"] != "warm"):
                bad = {p: v for p, v in plan.get("programs", {}).items()
                       if v != "hit"}
                raise SmokeFailure(
                    phase, f"second boot was not warm: plan {plan.get('mode')!r}"
                    f" ({plan.get('reason')}), artefacts not hit: {bad}, "
                    f"uncached compiles {warm['uncached_compiles']}"
                )
        if "boot_cache_export_failed" in events:
            raise SmokeFailure(
                phase, f"AOT export failed: {events['boot_cache_export_failed']}"
            )

        tokens, latencies = {}, []
        for seed in seeds:
            reply = _post(url + "/generate", {"prompt": PROMPT, "seed": seed})
            toks = reply["tokens"]
            if (len(toks) != 1 or len(toks[0]) != s["image_tokens"]
                    or not all(0 <= t < s["num_image_tokens"] for t in toks[0])):
                raise SmokeFailure(phase, f"seed {seed}: bad token grid")
            if reply.get("shape") != [1, s["image_size"], s["image_size"], 3]:
                raise SmokeFailure(phase, f"seed {seed}: pixels {reply.get('shape')}")
            if seed in tokens and tokens[seed] != toks[0]:
                raise SmokeFailure(phase, f"seed {seed} twice gave different tokens")
            tokens.setdefault(seed, toks[0])
            latencies.append(reply["latency_ms"])
        health = _get(url + "/healthz")
        if health.get("status") != "ok":
            raise SmokeFailure(phase, f"/healthz says {health.get('status')!r}")
        programs = _get(url + "/debug/programs").get("programs", [])
        failed = [p for p in programs if "error" in p]
        if failed:
            raise SmokeFailure(phase, f"program cost capture failed: {failed}")

        proc.send_signal(signal.SIGINT)
        try:
            rc = proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            self.reap()
            raise SmokeFailure(phase, "did not exit within 120s of SIGINT")
        if rc != 0:
            raise SmokeFailure(phase, f"exit {rc} after SIGINT")
        served = _events(log.read_text()).get("served")
        if not served or served["compiles_while_serving"] != 0:
            raise SmokeFailure(phase, f"compiled while serving: {served}")
        timing = {}
        if not self.rehearse:  # a rate means something only on the chip
            timing = dict(
                request_latency_ms=latencies,
                image_tokens_per_s=[
                    round(s["image_tokens"] / (ms / 1000.0), 1) for ms in latencies
                ],
            )
        self.report(
            phase, boot_seconds=boot_s, requests=len(seeds),
            same_seed_identical=True,
            # reported, not asserted: a few-step model whose image tokens
            # collapsed samples the same grid whatever the seed
            distinct_seeds_differ=len({tuple(t) for t in tokens.values()}) == len(tokens),
            warmup={k: warm[k] for k in (
                "compiles", "cache_hits", "uncached_compiles",
                "boot_cache_mode", "boot_seconds")},
            compiles_while_serving=0, **timing,
            bytes_in_use_per_device=events.get("placement", {}).get("bytes_in_use"),
            mesh=health.get("mesh"),
            seconds=round(time.monotonic() - t0, 1),
        )
        return tokens

    # --------------------------------------------------------------- plans

    def one_chip(self):
        self.preflight()
        vae = self.train_vae()
        ckpt, _, _ = self.train_dalle(vae, "train_dalle")
        self.generate(ckpt)
        seeds = [7, 7, 8]
        micro = self.serve("serve_micro", ckpt, [], seeds)
        cache = ["--engine", "continuous", "--compile_cache", str(OUT / "serve_cache")]
        cold = self.serve("serve_continuous_cold", ckpt, cache, seeds)
        warm = self.serve("serve_continuous_warm", ckpt, cache, seeds[1:],
                          want_warm=True)
        if any(warm[k] != cold[k] for k in warm):
            raise SmokeFailure(
                "serve_continuous_warm",
                "the warm-booted replica answered a seed differently from the cold one",
            )
        # decode-composition invariance (bit-exact on the CPU suite) is
        # reported, not asserted: the two engines run differently fused
        # programs here, and one flipped sample changes every later token
        agree = sum(a == b for a, b in zip(micro[7], cold[7])) / len(cold[7])
        self.report("engines", micro_vs_continuous_token_agreement=round(agree, 4),
                    warm_boot_tokens_identical=True)

    def four_chips(self):
        self.preflight()
        vae = self.train_vae()
        scan = self.size["four_chip_train"]
        _, sharded, placement = self.train_dalle(
            vae, "train_dalle_fsdp2_tp2", scan + ["mesh.fsdp=2", "mesh.tp=2"])
        ckpt, single, _ = self.train_dalle(
            vae, "train_dalle_one_device", scan + ["mesh.dp=1"])
        # tests/test_parallel.py holds fp32 steps at matmul precision
        # "highest" to rtol 1e-5; these are bf16 steps whose reductions the
        # mesh splits differently, so the stream is held to bf16's own
        # resolution (2^-8 per value, averaged over the batch's tokens)
        rel = [abs(a - b) / abs(b) for a, b in zip(sharded, single)]
        if max(rel) > 5e-3:
            raise SmokeFailure(
                "train_dalle_fsdp2_tp2",
                f"loss streams disagree: sharded {sharded} vs one device {single}",
            )
        if not self.rehearse:
            # (device 0 also still holds the unsharded copy `model.init` made)
            if (not placement or len(placement) != 4
                    or min(placement) < 0.1 * sum(placement)
                    or max(placement) > 0.75 * sum(placement)):
                raise SmokeFailure(
                    "train_dalle_fsdp2_tp2",
                    f"state is not spread over the four devices: {placement}",
                )
        self.report("loss_streams", sharded=sharded, one_device=single,
                    max_relative_difference=max(rel))
        # the smallest warmup ladder that serves: no cost/AOT compiles, no
        # resume or preview programs (sizes, not widths)
        lean = ["--engine", "continuous", "--no_program_costs", "--no_resume",
                "--preview_every", "0"]
        seeds = [7, 7, 8]
        plain = self.serve("serve_unsharded", ckpt, lean, seeds)
        mesh = self.serve("serve_mesh_tp4", ckpt,
                          lean + ["--mesh", f"dp=1,tp={self.chips}"], seeds)
        if mesh != plain:
            bad = {k: sum(a != b for a, b in zip(mesh[k], plain[k])) for k in plain}
            raise SmokeFailure(
                "serve_mesh_tp4",
                f"sharded tokens differ from the unsharded replica's "
                f"(differing positions per seed: {bad})",
            )
        self.report("sharded_serving", tokens_identical_to_unsharded=True)


# ------------------------------------------------------------------ helpers


def _sets(pairs):
    out = []
    for p in pairs:
        out += ["--set", p]
    return out


def _events(text: str) -> dict:
    """serve.py's structured log: the last JSON line of each event kind."""
    events = {}
    for line in text.splitlines():
        if line.startswith("{"):
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict) and "event" in rec:
                events[rec["event"]] = rec
    return events


def _post(url: str, body: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=320) as resp:
        return json.loads(resp.read())


def _get(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=60) as resp:
        return json.loads(resp.read())


def _png_size(path: Path):
    head = path.read_bytes()[:24]
    assert head[:8] == b"\x89PNG\r\n\x1a\n", f"{path} is not a PNG"
    return int.from_bytes(head[16:20], "big"), int.from_bytes(head[20:24], "big")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--rehearse", action="store_true",
                   help="toy size on the CPU; cannot report ok")
    args = p.parse_args(argv)

    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True)
    smoke = Smoke(REHEARSAL if args.rehearse else FLAGSHIP, args.chips,
                  args.rehearse)

    def _deadline(signum, frame):
        raise SmokeFailure("deadline", f"run exceeded {DEADLINE_S}s")

    signal.signal(signal.SIGALRM, _deadline)
    signal.alarm(DEADLINE_S)
    t0 = time.monotonic()
    try:
        smoke.four_chips() if args.chips == 4 else smoke.one_chip()
    except Exception as exc:  # any failure ends the run; none is carried past
        smoke.reap()
        phase = getattr(exc, "phase", "chip_smoke")
        error = getattr(exc, "error", repr(exc))
        print(json.dumps({"ok": False, "phase": phase, "error": error,
                          "device": smoke.device}), flush=True)
        return 1
    finally:
        signal.alarm(0)
        smoke.reap()
    print(json.dumps({"total_seconds": round(time.monotonic() - t0, 1)}), flush=True)
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": smoke.device}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": smoke.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
