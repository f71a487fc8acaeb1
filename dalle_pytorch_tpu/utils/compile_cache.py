"""Persistent compile cache + AOT executable export for seconds-scale boots.

Every replica boot re-traces and re-compiles the whole serving program
ladder — for a fleet, mean time to full capacity after a crash is
dominated by XLA compilation, not failure detection (the pjit/TPUv4
systems literature treats compile amortization as a first-class
operational constraint, PAPERS.md). This module makes a restart cheap:

  * **XLA executable store**: jax's persistent compilation cache, so
    every jit/pjit compile — warmup ladder, AOT cost capture, lazy pixel
    decode — is content-addressed by HLO hash and the second boot LOADS
    executables instead of compiling them. ONE rule places it, for every
    entry point of the repo (`enable_xla_cache`): where the environment
    sets `JAX_COMPILATION_CACHE_DIR`, that directory is the store and no
    code sets another; otherwise a CLI uses `<checkout>/.jax_cache` and a
    `CompileCache` its own `DIR/xla/`. The path is part of the cache key,
    so it never moves between runs. `utils/compile_guard.py` counts the
    cache-hit events, so the warm-boot contract is pinnable:
    `tally.uncached == 0` across a full warmup + serve cycle.
  * **AOT artifact export** (`DIR/aot/`): each warmed program's
    `jit(...).lower().compile()` executable is serialized
    (`jax.experimental.serialize_executable`) to a versioned on-disk
    artifact keyed by a BOOT FINGERPRINT (jax version, backend, mesh
    shape, model config, program ladder). Boot validates the artifacts
    against the fingerprint and a per-file checksum and reports a
    warm/cold plan — a mismatch, missing file, or corrupt/truncated
    entry degrades to a full recompile (counted), NEVER to a failed
    boot. The artifacts are the ship-a-warm-cache unit for fleet
    rollouts: rsync `DIR` to a new host and its first boot is warm.

Accounting: `dalle_boot_cache_{hits,misses,rejects}_total` counters and
a `dalle_boot_seconds{phase=}` gauge family (checkpoint / plan / warmup /
export) so dashboards can separate "slow because cold" from "slow
because sick".
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import time
from pathlib import Path
from typing import Dict, Iterable, Optional, Sequence

#: artifact container format — bump on any layout change so an old
#: artifact is a clean miss, not a parse error
FORMAT_VERSION = 1
MAGIC = b"DALLEAOT\n"

#: manifest filename inside DIR/aot/
MANIFEST = "MANIFEST.json"

#: jax reads this variable itself; where it is set, no code of this repo
#: points the persistent cache anywhere else
XLA_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def xla_cache_dir(default=None) -> Path:
    """Where this process keeps jax's persistent compilation cache: the
    environment's directory if it names one, else `default`, else the
    fixed `<checkout>/.jax_cache` (git-ignored)."""
    env = os.environ.get(XLA_CACHE_ENV)
    if env:
        return Path(env)
    if default is not None:
        return Path(default)
    return Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_xla_cache(default=None) -> Path:
    """Turn jax's persistent compilation cache on at `xla_cache_dir()`.
    Every CLI calls this before its first compile. Thresholds are zeroed
    so every program is stored: a second run of the same command then
    compiles nothing."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    directory = xla_cache_dir(default)
    if not os.environ.get(XLA_CACHE_ENV):
        directory.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(directory))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax latches the cache state on the FIRST compile of the process; a
    # compile that ran before this call would otherwise leave it off
    compilation_cache.reset_cache()
    # hits and misses are counted from here on (compile_guard.log_compiles)
    from dalle_pytorch_tpu.utils import compile_guard

    compile_guard.install_listener()
    return directory


def _canonical(obj) -> str:
    """Deterministic JSON for fingerprint hashing (sorted keys, default
    repr for exotic leaves — a config object that can't serialize still
    fingerprints stably as long as its repr is stable)."""
    return json.dumps(obj, sort_keys=True, default=repr, separators=(",", ":"))


def config_payload(cfg) -> object:
    """Best-effort stable serialization of a model/train config for the
    fingerprint: dicts pass through, config objects use their dict
    conversion where available, anything else falls back to repr."""
    if cfg is None or isinstance(cfg, (dict, list, str, int, float, bool)):
        return cfg
    for attr in ("to_dict", "as_dict"):
        fn = getattr(cfg, attr, None)
        if callable(fn):
            try:
                return fn()
            except Exception:
                pass
    try:
        from dalle_pytorch_tpu.training.config import config_to_dict

        return config_to_dict(cfg)
    except Exception:
        return repr(cfg)


def boot_fingerprint(
    backend: Optional[str] = None,
    mesh_shape=None,
    model_config=None,
    programs: Sequence[str] = (),
    jax_version: Optional[str] = None,
    extra=None,
) -> str:
    """Stable identity of one compiled-ladder universe. Any input drift —
    a jax upgrade, a different backend, a resharded mesh, a new model
    config, a program added to the ladder — changes the fingerprint, and
    stale artifacts become misses instead of wrong executables."""
    if jax_version is None:
        import jax

        jax_version = jax.__version__
    payload = {
        "format": FORMAT_VERSION,
        "jax": jax_version,
        "backend": backend,
        "mesh": mesh_shape,
        "model": config_payload(model_config),
        "programs": sorted(str(p) for p in programs),
        "extra": extra,
    }
    return hashlib.sha256(_canonical(payload).encode()).hexdigest()[:32]


def _atomic_write(path: Path, data: bytes) -> None:
    tmp = path.with_suffix(path.suffix + f".tmp{os.getpid()}")
    tmp.write_bytes(data)
    os.replace(tmp, path)


# --------------------------------------------------- artifact container
#
# The self-validating container format — MAGIC + canonical-JSON header
# (format version, fingerprint, payload length, payload sha256) + one
# newline + payload — is shared by the AOT compile-cache artifacts and
# the decode-state checkpoints (serving/migrate.py). ONE pack/unpack
# pair keeps the integrity-critical validation in lockstep: a fix to a
# torn-read edge case reaches both consumers.


def pack_artifact(magic: bytes, fingerprint: str, payload: bytes,
                  format_version: int = FORMAT_VERSION,
                  extra: Optional[Dict] = None) -> bytes:
    """Payload -> self-validating blob (the caller picks MAGIC and
    format version; `extra` adds caller-specific header fields)."""
    header = {
        "format": int(format_version),
        "fingerprint": str(fingerprint),
        "payload_bytes": len(payload),
        "payload_sha256": hashlib.sha256(payload).hexdigest(),
        **(extra or {}),
    }
    return bytes(magic) + _canonical(header).encode() + b"\n" + bytes(payload)


def unpack_artifact(raw: bytes, magic: bytes, fingerprint: str,
                    format_version: int = FORMAT_VERSION):
    """Blob -> (status, reason, payload): "hit" (valid, payload usable),
    "miss" (a DIFFERENT build's artifact — format or fingerprint drift;
    expected after any upgrade), or "reject" (integrity failure: bad
    magic, corrupt header, truncated payload, checksum mismatch —
    investigate the volume/transport). Never raises."""
    if not raw.startswith(magic):
        return "reject", "bad magic", None
    rest = raw[len(magic):]
    try:
        nl = rest.index(b"\n")
        header = json.loads(rest[:nl])
    except Exception as exc:
        return "reject", f"corrupt header: {exc!r}", None
    payload = rest[nl + 1:]
    try:
        if int(header.get("format", -1)) != int(format_version):
            return (
                "miss",
                f"format {header.get('format')} != {format_version}",
                None,
            )
        if header.get("fingerprint") != str(fingerprint):
            return (
                "miss",
                "fingerprint mismatch "
                f"({header.get('fingerprint')!r} != {str(fingerprint)!r})",
                None,
            )
        if len(payload) != int(header.get("payload_bytes", -1)):
            return "reject", "truncated payload", None
        if hashlib.sha256(payload).hexdigest() != header.get(
            "payload_sha256"
        ):
            return "reject", "checksum mismatch", None
    except Exception as exc:
        return "reject", f"corrupt header: {exc!r}", None
    return "hit", None, payload


class CompileCache:
    """One directory holding both compile-persistence layers plus the
    boot accounting. Lifecycle:

        cache = CompileCache(dir, registry=reg, log=log)
        cache.install()                      # jax persistent cache on
        ... build engine ...
        cache.bind(fingerprint, programs)    # identity of this ladder
        plan = cache.plan_boot()             # warm/cold verdict, counted
        engine.compile_cache = cache         # warmup exports artifacts
        with cache.boot_phase("warmup"):
            engine.warmup()

    Every load-side failure is absorbed: a bad cache degrades to a cold
    boot with the reject counted, never to a crashed replica.
    """

    def __init__(self, directory, registry=None, log=None):
        self.dir = Path(directory)
        self.xla_dir = self.dir / "xla"  # made by install(), if used
        self.aot_dir = self.dir / "aot"
        self.aot_dir.mkdir(parents=True, exist_ok=True)
        self.log = log
        self.fingerprint: Optional[str] = None
        self.programs: tuple = ()
        self.plan: Optional[Dict] = None
        #: fault-injection seam (serving/faults.py `corrupt_cache` rules):
        #: called with (program, path) before every artifact read
        self.faults = None
        self._exported: set = set()
        self._errors: Dict[str, str] = {}
        self.boot_seconds: Dict[str, float] = {}
        self._m_hits = self._m_misses = self._m_rejects = None
        self._m_phase = None
        if registry is not None:
            self._m_hits = registry.counter(
                "dalle_boot_cache_hits_total",
                "AOT cache artifacts that validated against the boot "
                "fingerprint (warm-boot evidence)",
            )
            self._m_misses = registry.counter(
                "dalle_boot_cache_misses_total",
                "AOT cache artifacts missing or keyed to a different "
                "fingerprint (cold recompile, expected after any "
                "config/jax/mesh change)",
            )
            self._m_rejects = registry.counter(
                "dalle_boot_cache_rejects_total",
                "AOT cache artifacts rejected as corrupt/truncated "
                "(cold recompile; investigate the cache volume)",
            )
            self._m_phase = registry.gauge_family(
                "dalle_boot_seconds",
                "wall seconds of the most recent boot, by phase",
                label_name="phase",
            )

    # ------------------------------------------------------------ wiring

    def install(self) -> "CompileCache":
        """Turn jax's persistent compilation cache on — process-wide,
        ideally before the first compile — at `DIR/xla`, unless the
        environment placed it (`enable_xla_cache`): then jax's directory
        is left alone and only the AOT artifacts live under `DIR/aot`."""
        enable_xla_cache(default=self.xla_dir)
        return self

    @staticmethod
    def uninstall() -> None:
        """Detach the process from the persistent cache (tests restore
        global state; serving processes never call this). A directory
        the environment chose stays configured."""
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        if not os.environ.get(XLA_CACHE_ENV):
            jax.config.update("jax_compilation_cache_dir", None)
        compilation_cache.reset_cache()

    def bind(self, fingerprint: str, programs: Iterable[str]) -> "CompileCache":
        self.fingerprint = str(fingerprint)
        self.programs = tuple(str(p) for p in programs)
        return self

    # ------------------------------------------------------------- layout

    def artifact_path(self, program: str) -> Path:
        safe = "".join(
            c if c.isalnum() or c in "._-" else "_" for c in str(program)
        )
        return self.aot_dir / f"{safe}.aotx"

    @property
    def manifest_path(self) -> Path:
        return self.aot_dir / MANIFEST

    def _read_manifest(self) -> Optional[Dict]:
        try:
            return json.loads(self.manifest_path.read_text())
        except FileNotFoundError:
            return None
        except Exception:
            return {"corrupt": True}

    def _write_manifest(self, entries: Dict[str, Dict]) -> None:
        _atomic_write(
            self.manifest_path,
            json.dumps(
                {
                    "format": FORMAT_VERSION,
                    "fingerprint": self.fingerprint,
                    "programs": entries,
                    "written_at": time.time(),
                },
                indent=1,
                sort_keys=True,
            ).encode(),
        )

    # -------------------------------------------------------------- plan

    def _count(self, metric, n: int = 1) -> None:
        if metric is not None:
            metric.inc(n)

    def _validate(self, program: str) -> Dict:
        """One artifact's verdict: {"status": "hit"|"miss"|"reject",
        "reason": ...}. Never raises — a bad artifact is a counted
        verdict, not a boot failure."""
        path = self.artifact_path(program)
        if self.faults is not None:
            # corrupt_cache fault seam: the injector may truncate/garble
            # the file on disk before this read, exercising the exact
            # torn-write/bad-volume path the reject branch guards
            self.faults.on_artifact_load(program, path)
        try:
            raw = path.read_bytes()
        except FileNotFoundError:
            return {"status": "miss", "reason": "missing artifact"}
        except Exception as exc:
            return {"status": "reject", "reason": f"unreadable: {exc!r}"}
        status, reason, payload = unpack_artifact(
            raw, MAGIC, self.fingerprint
        )
        if status != "hit":
            return {"status": status, "reason": reason}
        return {"status": "hit", "reason": None, "bytes": len(payload)}

    def plan_boot(self) -> Dict:
        """Validate every ladder artifact against the bound fingerprint
        and return the boot plan: `mode` is "warm" only when EVERY
        program's artifact is a hit (the dispatch path will then load
        from the XLA store without compiling); anything else is "cold"
        with per-program reasons. Hits/misses/rejects are counted into
        the registry here, once per boot."""
        assert self.fingerprint is not None, "bind() before plan_boot()"
        t0 = time.perf_counter()
        verdicts: Dict[str, Dict] = {}
        manifest = self._read_manifest()
        for program in self.programs:
            v = self._validate(program)
            verdicts[program] = v
            self._count(
                {
                    "hit": self._m_hits,
                    "miss": self._m_misses,
                    "reject": self._m_rejects,
                }[v["status"]]
            )
        statuses = {v["status"] for v in verdicts.values()}
        mode = "warm" if statuses == {"hit"} and verdicts else "cold"
        reason = None
        if mode == "cold":
            if manifest is None:
                reason = "no manifest (first boot against this directory)"
            elif manifest.get("corrupt"):
                reason = "corrupt manifest"
            elif manifest.get("fingerprint") != self.fingerprint:
                reason = "fingerprint mismatch (config/jax/mesh/ladder drift)"
            else:
                bad = {
                    p: v["reason"] for p, v in verdicts.items()
                    if v["status"] != "hit"
                }
                reason = f"invalid artifacts: {bad}"
        self.plan = {
            "mode": mode,
            "reason": reason,
            "fingerprint": self.fingerprint,
            "programs": verdicts,
            "plan_s": round(time.perf_counter() - t0, 4),
        }
        if self.log is not None:
            self.log.event(
                "boot_cache_plan", mode=mode, reason=reason,
                fingerprint=self.fingerprint,
                programs={p: v["status"] for p, v in verdicts.items()},
            )
        return self.plan

    # ------------------------------------------------------------- export

    def wants(self, program: str) -> bool:
        """Should warmup export this program? Only when bound, in the
        ladder, not already exported this boot, and not already valid on
        disk (a warm boot re-exports nothing)."""
        if self.fingerprint is None or program in self._exported:
            return False
        if self.programs and program not in self.programs:
            return False
        if self.plan is not None:
            v = self.plan["programs"].get(program)
            if v is not None and v["status"] == "hit":
                return False
        return True

    def _serialize(self, compiled) -> bytes:
        """Executable -> portable bytes. Overridable seam (tests force
        failures/fakes without a real backend): the default pickles the
        `serialize_executable` triple (payload, in_tree, out_tree)."""
        import pickle

        from jax.experimental import serialize_executable

        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        return pickle.dumps(
            {"exe": payload, "trees": (in_tree, out_tree)}, protocol=4
        )

    def export(self, program: str, compiled) -> bool:
        """Serialize one compiled executable into a fingerprint-stamped
        artifact (atomic tmp+rename; the manifest is rewritten after
        every export so an interrupted boot self-heals into partial
        misses next time). Failures are recorded, never raised — a
        backend that can't serialize must not break warmup."""
        try:
            payload = self._serialize(compiled)
            _atomic_write(
                self.artifact_path(program),
                pack_artifact(
                    MAGIC, self.fingerprint, payload,
                    extra={
                        "program": str(program),
                        "written_at": time.time(),
                    },
                ),
            )
        except Exception as exc:
            self._errors[str(program)] = repr(exc)
            if self.log is not None:
                self.log.event(
                    "boot_cache_export_failed", program=str(program),
                    error=repr(exc),
                )
            return False
        self._exported.add(str(program))
        # one validation sweep over ladder ∪ exported (normally equal):
        # still-valid artifacts from earlier boots carry forward so one
        # incremental export can't orphan the rest of the ladder
        entries = {}
        for p in dict.fromkeys(list(self.programs) + sorted(self._exported)):
            v = self._validate(p)
            if v["status"] == "hit":
                entries[p] = {"bytes": v.get("bytes", 0)}
        self._write_manifest(entries)
        return True

    # --------------------------------------------------------------- load

    def _deserialize(self, blob: bytes):
        """Artifact bytes -> loaded executable. Overridable seam for
        tests."""
        import pickle

        from jax.experimental import serialize_executable

        record = pickle.loads(blob)
        in_tree, out_tree = record["trees"]
        return serialize_executable.deserialize_and_load(
            record["exe"], in_tree, out_tree
        )

    def deserialize(self, program: str):
        """Best-effort load of one validated artifact into a callable
        executable. None on any failure (invalid artifact, backend that
        can't deserialize) — callers fall back to the jit dispatch path,
        which the XLA store keeps warm anyway."""
        v = self._validate(program)
        if v["status"] != "hit":
            return None
        try:
            raw = self.artifact_path(program).read_bytes()
            payload = raw[raw.index(b"\n", len(MAGIC)) + 1:]
            return self._deserialize(payload)
        except Exception as exc:
            self._errors[str(program)] = repr(exc)
            return None

    # --------------------------------------------------------- accounting

    def record_error(self, program: str, exc: BaseException) -> None:
        self._errors[str(program)] = repr(exc)

    @contextlib.contextmanager
    def boot_phase(self, phase: str):
        """Time one boot phase into `dalle_boot_seconds{phase=}` (and the
        `boot_seconds` dict the boot_cache log event carries)."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            s = time.perf_counter() - t0
            self.boot_seconds[phase] = round(s, 4)
            if self._m_phase is not None:
                self._m_phase.labels(phase).set(s)

    def detail(self) -> Dict:
        return {
            "dir": str(self.dir),
            "fingerprint": self.fingerprint,
            "programs": list(self.programs),
            "plan": self.plan,
            "exported": sorted(self._exported),
            "errors": dict(self._errors),
            "boot_seconds": dict(self.boot_seconds),
        }
