"""Metrics, throughput, MFU, and profiler hooks.

Covers the reference's observability surface (SURVEY.md §5.1, §5.5):
  * wandb scalar/image logging, root-gated, with `mode=disabled` in debug
    (`train_dalle.py:367-373,543-587`) — degrades to stdout + PNG files
    when wandb isn't installed;
  * samples/sec probe every 10 steps (`train_dalle.py:578-581`);
  * the DeepSpeed flops-profiler equivalent (`train_dalle.py:389-396,
    583-584`): a `jax.profiler` trace captured around a chosen step, plus
    an analytic FLOPs/MFU estimate every log interval.
"""

from __future__ import annotations

import bisect
import json
import re
import threading
import time
from collections import deque
from pathlib import Path
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple


# ------------------------------------------------- Prometheus-style registry
#
# Shared counter/gauge/histogram instruments for the serving layer
# (`dalle_pytorch_tpu/serving/`) and anything else that wants scrapeable
# process metrics. Deliberately tiny and stdlib-only: the serving HTTP
# server renders `registry.render()` at GET /metrics in the Prometheus
# text exposition format. All instruments are thread-safe — the serving
# path observes from request handler threads and the batcher worker.


def _fmt(v: float) -> str:
    """Prometheus number formatting: integers without a trailing .0."""
    f = float(v)
    return str(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


class Counter:
    """Monotonically increasing counter."""

    def __init__(self, name: str, help: str = ""):
        self.name, self.help = name, help
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        assert amount >= 0, "counters only go up"
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        return self._value

    def render(self, exemplars: bool = False) -> List[str]:
        # OpenMetrics (the exemplars exposition) reserves the _total
        # suffix: the counter FAMILY drops it and only the sample keeps
        # it, else the OpenMetrics parser rejects the whole scrape.
        # Classic text keeps the flat name everywhere.
        fam = (
            self.name[: -len("_total")]
            if exemplars and self.name.endswith("_total")
            else self.name
        )
        return [
            f"# HELP {fam} {self.help}",
            f"# TYPE {fam} counter",
            f"{self.name} {_fmt(self._value)}",
        ]


class Gauge:
    """Instantaneous value (queue depth, in-flight requests, ...)."""

    def __init__(self, name: str, help: str = ""):
        self.name, self.help = name, help
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._value += amount

    def dec(self, amount: float = 1.0) -> None:
        self.inc(-amount)

    @property
    def value(self) -> float:
        return self._value

    def render(self, exemplars: bool = False) -> List[str]:
        return [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} gauge",
            f"{self.name} {_fmt(self._value)}",
        ]


# default buckets suit request latencies in seconds AND small occupancy
# counts; instruments that care pass explicit buckets.
_DEFAULT_BUCKETS = (
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)


class Histogram:
    """Cumulative-bucket histogram plus a bounded reservoir for quantiles.

    Prometheus proper computes quantiles server-side from the buckets; the
    reservoir (last `reservoir_size` observations) lets /metrics also expose
    ready-made p50/p95 gauges so a bare `curl` shows latency percentiles
    without a Prometheus deployment.
    """

    def __init__(
        self,
        name: str,
        help: str = "",
        buckets: Sequence[float] = _DEFAULT_BUCKETS,
        reservoir_size: int = 1024,
    ):
        self.name, self.help = name, help
        self.buckets = tuple(sorted(buckets))
        self._counts = [0] * (len(self.buckets) + 1)  # +inf bucket last
        self._sum = 0.0
        self._count = 0
        self._recent: deque = deque(maxlen=reservoir_size)
        # most recent exemplar-carrying observation: (value, trace_id, unix
        # time). Exposed via `render(exemplars=True)` in OpenMetrics
        # exemplar syntax so a scrape can jump from a latency bucket to
        # the exact trace that landed there.
        self._exemplar = None
        self._lock = threading.Lock()

    def observe(self, value: float, exemplar: Optional[str] = None) -> None:
        v = float(value)
        with self._lock:
            self._counts[bisect.bisect_left(self.buckets, v)] += 1
            self._sum += v
            self._count += 1
            self._recent.append(v)
            if exemplar:
                self._exemplar = (v, str(exemplar), time.time())

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def bucket_counts(self):
        """Consistent snapshot for rolling-window readers (the SLO burn-rate
        tracker diffs these between ticks): (bucket bounds, per-bucket
        counts with +Inf last, total count, sum) under the lock."""
        with self._lock:
            return self.buckets, tuple(self._counts), self._count, self._sum

    def percentile(self, q: float) -> float:
        """Nearest-rank percentile over the recent-observation reservoir
        (0.0 when nothing has been observed yet)."""
        with self._lock:
            if not self._recent:
                return 0.0
            ordered = sorted(self._recent)
            idx = min(len(ordered) - 1, max(0, int(q * len(ordered))))
            return ordered[idx]

    def mean(self) -> float:
        with self._lock:
            return self._sum / self._count if self._count else 0.0

    def render(self, exemplars: bool = False) -> List[str]:
        with self._lock:
            lines = [
                f"# HELP {self.name} {self.help}",
                f"# TYPE {self.name} histogram",
            ]
            # OpenMetrics exemplar: appended to the ONE bucket line whose
            # range the exemplar value falls in (cumulative buckets, so
            # that's the first le >= value)
            ex_idx, ex_suffix = None, ""
            if exemplars and self._exemplar is not None:
                ev, etid, ets = self._exemplar
                ex_idx = bisect.bisect_left(self.buckets, ev)
                ex_suffix = (
                    f' # {{trace_id="{etid}"}} {_fmt(ev)} {round(ets, 3)}'
                )
            cum = 0
            for i, (bound, n) in enumerate(zip(self.buckets, self._counts)):
                cum += n
                suffix = ex_suffix if i == ex_idx else ""
                lines.append(
                    f'{self.name}_bucket{{le="{_fmt(bound)}"}} {cum}{suffix}'
                )
            suffix = ex_suffix if ex_idx == len(self.buckets) else ""
            lines.append(
                f'{self.name}_bucket{{le="+Inf"}} {self._count}{suffix}'
            )
            lines.append(f"{self.name}_sum {_fmt(self._sum)}")
            lines.append(f"{self.name}_count {self._count}")
        # convenience percentile gauges from the reservoir (outside the
        # lock: percentile() re-acquires it)
        for q, suffix in ((0.5, "p50"), (0.95, "p95")):
            qn = f"{self.name}_{suffix}"
            lines.append(f"# TYPE {qn} gauge")
            lines.append(f"{qn} {_fmt(self.percentile(q))}")
        return lines


class Family:
    """Labeled instrument family: one metric name, one label, N children.

    Minimal Prometheus label support for the serving layer (per-compiled-
    shape occupancy/batch-seconds series): `labels(value)` get-or-creates a
    child instrument, and `render()` emits ONE HELP/TYPE header followed by
    every child's samples tagged `{label_name="value"}` — the exposition
    shape scrapers expect for labeled series. Children are full instruments
    (Counter/Gauge/Histogram), so observation is lock-protected as usual;
    labeled histograms skip the convenience p50/p95 gauges (Prometheus
    computes quantiles from the buckets server-side).
    """

    def __init__(self, cls, name: str, help: str, label_name: str, **kw):
        self.cls, self.name, self.help = cls, name, help
        self.label_name = label_name
        self._kw = kw
        self._children: Dict[str, object] = {}
        self._lock = threading.Lock()

    def labels(self, value) -> object:
        key = str(value)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self.cls(self.name, self.help, **self._kw)
                child._label_suffix = f'{self.label_name}="{key}"'
                self._children[key] = child
            return child

    def labels_extra(self, value, **extra) -> object:
        """Child carrying the family label PLUS extra label dimensions —
        the per-shard series (`dalle_serving_mfu{program=,device=}`)
        without registering a second family per dimension. Children are
        keyed by the full rendered label set, so plain `labels(value)`
        children and extra-labeled ones coexist under one HELP/TYPE
        header."""
        pairs = [f'{self.label_name}="{value}"'] + [
            f'{k}="{v}"' for k, v in sorted(extra.items())
        ]
        suffix = ",".join(pairs)
        with self._lock:
            child = self._children.get(suffix)
            if child is None:
                child = self.cls(self.name, self.help, **self._kw)
                child._label_suffix = suffix
                self._children[suffix] = child
            return child

    def items(self) -> List:
        """Snapshot of (label value, child instrument) pairs — the public
        read surface for per-label reporting (`render` and the tests'
        per-stage breakdowns read a family through this)."""
        with self._lock:
            return sorted(self._children.items())

    def render(self, exemplars: bool = False) -> List[str]:
        children = self.items()
        type_name = {Counter: "counter", Gauge: "gauge", Histogram: "histogram"}[
            self.cls
        ]
        fam = (
            self.name[: -len("_total")]
            if exemplars and self.cls is Counter
            and self.name.endswith("_total")
            else self.name
        )
        lines = [
            f"# HELP {fam} {self.help}",
            f"# TYPE {fam} {type_name}",
        ]
        for _, child in children:
            lines.extend(_render_samples(child, exemplars=exemplars))
        return lines


def _render_samples(inst, exemplars: bool = False) -> List[str]:
    """Sample lines of an instrument with its family label spliced in."""
    label = getattr(inst, "_label_suffix", "")
    out = []
    for line in inst.render(exemplars=exemplars):
        if line.startswith("#"):
            continue  # family emits HELP/TYPE once
        name, value = line.split(" ", 1)
        if "_p50" in name or "_p95" in name:
            continue  # reservoir quantiles stay on unlabeled instruments
        if "{" in name:  # histogram bucket: merge labels
            base, rest = name.split("{", 1)
            name = f"{base}{{{label},{rest}" if label else name
        elif label:
            name = f"{name}{{{label}}}"
        out.append(f"{name} {value}")
    return out


class MetricsRegistry:
    """Named instrument registry rendering Prometheus text exposition.

    `counter/gauge/histogram` are get-or-create (idempotent by name), so
    independently constructed components can share instruments.
    """

    def __init__(self):
        self._instruments: Dict[str, object] = {}
        self._lock = threading.Lock()

    def _get_or_create(self, cls, name: str, help: str, **kw):
        with self._lock:
            inst = self._instruments.get(name)
            if inst is None:
                inst = cls(name, help, **kw)
                self._instruments[name] = inst
            assert isinstance(inst, cls), (
                f"metric {name!r} already registered as {type(inst).__name__}"
            )
            return inst

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get_or_create(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get_or_create(Gauge, name, help)

    def histogram(
        self, name: str, help: str = "",
        buckets: Sequence[float] = _DEFAULT_BUCKETS,
    ) -> Histogram:
        return self._get_or_create(Histogram, name, help, buckets=buckets)

    def _family(self, cls, name: str, help: str, label_name: str, **kw):
        with self._lock:
            inst = self._instruments.get(name)
            if inst is None:
                inst = Family(cls, name, help, label_name, **kw)
                self._instruments[name] = inst
            assert isinstance(inst, Family) and inst.cls is cls, (
                f"metric {name!r} already registered as {type(inst).__name__}"
            )
            return inst

    def histogram_family(
        self, name: str, help: str = "", label_name: str = "shape",
        buckets: Sequence[float] = _DEFAULT_BUCKETS,
    ) -> Family:
        return self._family(
            Histogram, name, help, label_name, buckets=buckets
        )

    def gauge_family(
        self, name: str, help: str = "", label_name: str = "name"
    ) -> Family:
        """Labeled gauge series (per-program MFU, per-SLO burn rate)."""
        return self._family(Gauge, name, help, label_name)

    def counter_family(
        self, name: str, help: str = "", label_name: str = "name"
    ) -> Family:
        """Labeled counter series (stall events by reason)."""
        return self._family(Counter, name, help, label_name)

    def get(self, name: str):
        return self._instruments.get(name)

    def render(self, exemplars: bool = False) -> str:
        """Prometheus text exposition. `exemplars=True` switches to the
        OpenMetrics flavor: exemplar annotations (`# {trace_id="..."}`)
        on histogram buckets that recorded one, plus the mandatory
        `# EOF` terminator — serve it with the
        `application/openmetrics-text` content type (the HTTP layer
        does); classic Prometheus text parsers reject the syntax."""
        with self._lock:
            instruments = sorted(self._instruments.items())
        lines: List[str] = []
        for _, inst in instruments:
            lines.extend(inst.render(exemplars=exemplars))
        if exemplars:
            lines.append("# EOF")
        return "\n".join(lines) + "\n"


# ----------------------------------------------- exposition text parsing
#
# The inverse of `MetricsRegistry.render()`, for the fleet scraper
# (obs/fleetmetrics.py): a router-side poller pulls each replica's
# GET /metrics body and needs the samples back as typed values to
# federate, delta, and roll up. Tolerates both exposition flavors this
# registry emits — classic text and the OpenMetrics exemplar variant
# (`_total`-stripped counter family names, `# {...}` bucket exemplars,
# trailing `# EOF`) — and the convenience `_p50`/`_p95` gauge lines that
# carry a TYPE header but no HELP.


class ParsedSample(NamedTuple):
    """One exposition sample line: full rendered name (`foo_total`,
    `foo_bucket`, ...), label dict, numeric value."""

    name: str
    labels: Dict[str, str]
    value: float

    def key(self) -> Tuple[str, Tuple[Tuple[str, str], ...]]:
        """Hashable series identity (name + sorted labels) — the join
        key for cross-scrape deltas and cross-replica rollups."""
        return self.name, tuple(sorted(self.labels.items()))


class ParsedFamily:
    """All samples of one metric family plus its TYPE/HELP metadata."""

    __slots__ = ("name", "type", "help", "samples")

    def __init__(self, name: str, type: str = "untyped", help: str = ""):
        self.name, self.type, self.help = name, type, help
        self.samples: List[ParsedSample] = []

    def histogram_series(self) -> Dict[Tuple[Tuple[str, str], ...], Dict]:
        """Reassemble `_bucket`/`_sum`/`_count` samples into per-series
        histogram points keyed by the non-`le` label set: each value is
        `{"bounds": [...], "cum": [...], "count": int, "sum": float}`
        with cumulative bucket counts and `+Inf` folded into `count`."""
        out: Dict[Tuple[Tuple[str, str], ...], Dict] = {}

        def point(labels: Dict[str, str]) -> Dict:
            k = tuple(sorted(
                (n, v) for n, v in labels.items() if n != "le"
            ))
            return out.setdefault(
                k, {"bounds": [], "cum": [], "count": 0, "sum": 0.0}
            )

        for s in self.samples:
            if s.name == f"{self.name}_bucket":
                le = s.labels.get("le", "+Inf")
                if le == "+Inf":
                    point(s.labels)["count"] = int(s.value)
                else:
                    p = point(s.labels)
                    p["bounds"].append(float(le))
                    p["cum"].append(int(s.value))
            elif s.name == f"{self.name}_sum":
                point(s.labels)["sum"] = float(s.value)
            elif s.name == f"{self.name}_count":
                point(s.labels)["count"] = int(s.value)
        for p in out.values():
            order = sorted(range(len(p["bounds"])), key=p["bounds"].__getitem__)
            p["bounds"] = [p["bounds"][i] for i in order]
            p["cum"] = [p["cum"][i] for i in order]
        return out


_SAMPLE_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
#: sample-name suffixes that attach a line to a declared family; classic
#: counters match their family name exactly, OpenMetrics counters add
#: `_total`, histograms fan out into bucket/sum/count
_FAMILY_SUFFIXES = ("", "_total", "_bucket", "_sum", "_count")


def _unescape_label(v: str) -> str:
    return v.replace('\\"', '"').replace("\\n", "\n").replace("\\\\", "\\")


def _parse_sample_line(line: str) -> ParsedSample:
    """`name[{labels}] value[ # exemplar...]` → ParsedSample. Raises
    ValueError on anything malformed (the scraper treats that as a
    failed scrape, not a partial one)."""
    name, labels_part, rest = line, "", ""
    brace = line.find("{")
    if brace >= 0:
        close = line.find("}", brace)
        if close < 0:
            raise ValueError(f"unterminated label block: {line!r}")
        name = line[:brace]
        labels_part = line[brace + 1:close]
        rest = line[close + 1:].strip()
    else:
        try:
            name, rest = line.split(None, 1)
        except ValueError:
            raise ValueError(f"sample line without a value: {line!r}")
    if not _SAMPLE_NAME_RE.match(name):
        raise ValueError(f"bad sample name in line: {line!r}")
    labels: Dict[str, str] = {}
    if labels_part:
        matched = _LABEL_RE.findall(labels_part)
        stripped = _LABEL_RE.sub("", labels_part).replace(",", "").strip()
        if stripped:
            raise ValueError(f"bad label block: {labels_part!r}")
        labels = {k: _unescape_label(v) for k, v in matched}
    # an OpenMetrics exemplar trails the value as ` # {...} v ts`
    value_token = rest.split(" # ", 1)[0].strip().split()
    if len(value_token) != 1:
        raise ValueError(f"bad sample value in line: {line!r}")
    tok = value_token[0]
    try:
        value = float("inf") if tok == "+Inf" else float(tok)
    except ValueError:
        raise ValueError(f"non-numeric sample value {tok!r} in {line!r}")
    return ParsedSample(name, labels, value)


def parse_exposition(text: str) -> Dict[str, ParsedFamily]:
    """Parse Prometheus text exposition (as `MetricsRegistry.render`
    emits it, either flavor) back into `{family name: ParsedFamily}`.

    Strict on sample lines — a truncated or garbage body raises
    ValueError rather than returning half a scrape — but permissive on
    metadata: unknown comment lines are skipped, TYPE without HELP is
    fine (the `_p50`/`_p95` convenience gauges), and samples with no
    declared family land in an `untyped` one.
    """
    families: Dict[str, ParsedFamily] = {}

    def family_for(sample_name: str) -> ParsedFamily:
        for suffix in _FAMILY_SUFFIXES:
            if suffix and not sample_name.endswith(suffix):
                continue
            base = sample_name[: len(sample_name) - len(suffix)] if suffix \
                else sample_name
            fam = families.get(base)
            if fam is not None:
                return fam
        fam = families.setdefault(sample_name, ParsedFamily(sample_name))
        return fam

    for raw_line in text.splitlines():
        line = raw_line.strip()
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 3 and parts[1] == "TYPE":
                fam = families.setdefault(parts[2], ParsedFamily(parts[2]))
                fam.type = parts[3] if len(parts) > 3 else "untyped"
            elif len(parts) >= 3 and parts[1] == "HELP":
                fam = families.setdefault(parts[2], ParsedFamily(parts[2]))
                fam.help = parts[3] if len(parts) > 3 else ""
            # anything else (# EOF, stray comments) is skippable metadata
            continue
        families_sample = _parse_sample_line(line)
        family_for(families_sample.name).samples.append(families_sample)
    return families


def counter_delta(prev: Optional[float], cur: float) -> float:
    """Reset-aware counter delta: a monotonic counter that went DOWN
    means the replica restarted (a supervised crash/recovery) — clamp
    the delta to 0 rather than going negative; the post-restart
    increments land in the following scrapes once the new baseline is
    recorded. `prev=None` (first sight of the series) also reads as 0:
    a scraper joining mid-life must not claim the replica's whole
    counter history as one interval's work."""
    if prev is None or cur < prev:
        return 0.0
    return float(cur - prev)


def merge_histogram_points(points: Iterable[Dict]) -> Dict:
    """Merge per-replica histogram points (the `histogram_series()`
    shape) into one fleet histogram. Identical bucket bounds — the
    common case, every replica runs the same instrument definitions —
    merge exactly (cumulative counts sum). Mismatched bounds merge on
    the union grid, flooring each histogram's cumulative count at
    unknown bounds to its nearest LOWER known bound (an undercount
    bias, never an overcount)."""
    points = [p for p in points if p is not None]
    if not points:
        return {"bounds": [], "cum": [], "count": 0, "sum": 0.0}
    bounds: List[float] = sorted({b for p in points for b in p["bounds"]})

    def cum_at(p: Dict, bound: float) -> int:
        idx = bisect.bisect_right(p["bounds"], bound) - 1
        return int(p["cum"][idx]) if idx >= 0 else 0

    return {
        "bounds": bounds,
        "cum": [sum(cum_at(p, b) for p in points) for b in bounds],
        "count": int(sum(p["count"] for p in points)),
        "sum": float(sum(p["sum"] for p in points)),
    }


def render_histogram_point(name: str, point: Dict,
                           labels: str = "") -> List[str]:
    """Exposition bucket/sum/count lines for one merged histogram point
    (no HELP/TYPE header — the caller owns family metadata). `labels`
    is a pre-rendered `k="v"` list spliced before `le`."""
    prefix = f"{labels}," if labels else ""
    lines = [
        f'{name}_bucket{{{prefix}le="{_fmt(b)}"}} {int(c)}'
        for b, c in zip(point["bounds"], point["cum"])
    ]
    lines.append(f'{name}_bucket{{{prefix}le="+Inf"}} {int(point["count"])}')
    suffix = f"{{{labels}}}" if labels else ""
    lines.append(f'{name}_sum{suffix} {_fmt(point["sum"])}')
    lines.append(f'{name}_count{suffix} {int(point["count"])}')
    return lines


class MetricsLogger:
    def __init__(
        self,
        project: str,
        config: Optional[dict] = None,
        enabled: bool = True,
        debug: bool = False,
        run_name: Optional[str] = None,
        out_dir: str = "logs",
        entity: Optional[str] = None,
    ):
        self.enabled = enabled
        self.out_dir = Path(out_dir)
        self.run = None
        self._jsonl = None
        if not enabled:
            return
        try:
            import wandb

            self.run = wandb.init(
                project=project,
                name=run_name,
                entity=entity,  # --wandb_entity (`train_dalle.py:119-124`)
                config=config or {},
                mode="disabled" if debug else "online",
            )
        except Exception:
            self.out_dir.mkdir(parents=True, exist_ok=True)
            self._jsonl = open(self.out_dir / "metrics.jsonl", "a")

    @property
    def run_name(self) -> str:
        if self.run is not None and getattr(self.run, "name", None):
            return str(self.run.name)
        return "local"

    def log(self, data: dict, step: Optional[int] = None) -> None:
        if not self.enabled:
            return
        scalars = {
            k: (float(v) if hasattr(v, "item") or isinstance(v, (int, float)) else v)
            for k, v in data.items()
        }
        if self.run is not None:
            self.run.log(scalars, step=step)
        elif self._jsonl is not None:
            self._jsonl.write(json.dumps({"step": step, **scalars}) + "\n")
            self._jsonl.flush()

    def log_images(self, images, caption: str, name: str, step: int) -> None:
        if not self.enabled:
            return
        if self.run is not None:
            import wandb

            self.run.log({name: wandb.Image(images, caption=caption)}, step=step)
        else:
            from dalle_pytorch_tpu.utils.images import save_image_grid

            import numpy as np

            imgs = np.asarray(images)
            if imgs.ndim == 3:
                imgs = imgs[None]
            save_image_grid(imgs, self.out_dir / f"{name}_{step}.png")

    def log_model_artifact(self, path, name: str = "trained-dalle") -> None:
        """Upload a checkpoint as a run artifact (the reference's per-epoch
        wandb.save / Artifact upload, `/root/reference/train_dalle.py:
        481-484`, `train_vae.py:305-310`). No-op without a live wandb run
        (the file already sits on disk in that case)."""
        if not self.enabled or self.run is None:
            return
        try:
            import wandb

            art = wandb.Artifact(name, type="model")
            art.add_file(str(path))
            self.run.log_artifact(art)
        except Exception as e:  # artifact upload must never kill training
            print(f"[metrics] artifact upload failed: {e}")

    def finish(self) -> None:
        if self.run is not None:
            self.run.finish()
        if self._jsonl is not None:
            self._jsonl.close()


class ThroughputMeter:
    """samples/sec every `interval` steps (`train_dalle.py:501-502,578-581`)."""

    def __init__(self, interval: int = 10):
        self.interval = interval
        self._t0 = None
        self._step0 = None

    def update(self, step: int, batch_size: int) -> Optional[float]:
        """Fires on interval crossings and scales by the true step delta,
        so it stays correct when the trainer advances multiple steps per
        call (steps_per_dispatch windows)."""
        if self._t0 is None:
            # initialize on the FIRST call, whatever the step: stride>1
            # step sequences may never land on an exact interval multiple
            self._t0 = time.time()
            self._step0 = step
            return None
        if step // self.interval > self._step0 // self.interval:
            now = time.time()
            rate = batch_size * (step - self._step0) / (now - self._t0)
            self._t0 = now
            self._step0 = step
            return rate
        return None


class ProfilerHook:
    """jax.profiler trace around one step (flops-profiler parity: profile
    step 200, stop training at 201, `train_dalle.py:389-396,583-584`)."""

    def __init__(self, enabled: bool, profile_step: int = 200, out_dir: str = "profiles"):
        self.enabled = enabled
        self.profile_step = profile_step
        self.out_dir = out_dir
        self._active = False
        self._done = False

    def before_step(self, step: int) -> None:
        # >= (not ==): a steps_per_dispatch>1 trainer may never land on the
        # exact step index; profile the first dispatch at/after it instead
        # of stopping later without ever having traced
        if self.enabled and not self._done and step >= self.profile_step:
            import jax  # not at module level: MetricsRegistry serves jax-free parents

            Path(self.out_dir).mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(self.out_dir)
            self._active = True

    def after_step(self, step: int) -> bool:
        """Returns True when training should stop (profiler finished)."""
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
            self._done = True
            print(f"[profiler] trace for step {step} written to {self.out_dir}")
        return self.enabled and self._done
