"""From a profiler trace to busy and idle time, kernel time and idle gaps.

Two stages, so that the arithmetic can be checked on a small recorded trace
(`benchmark/tests/data/`) without the profiler:

  `load_xplane(path, platform)`  the `.xplane.pb` the JAX profiler wrote ->
      plain lists: for each device its operation and program events, and the
      benchmark's own host spans (`bench:*` TraceAnnotations), all in
      seconds on one clock;
  `reduce(events)`  those lists -> the traced window, the device's busy
      seconds (the union of the intervals in which an operation ran, averaged
      over the devices), the time of each operation and program by name, and
      the idle gaps attributed to what the host was doing.
"""

from __future__ import annotations

import re

SPAN_PREFIX = "bench:"
TPU_PLANE = re.compile(r"^/device:TPU:\d+$")
# what the profiler calls the lines of a device plane
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
GAP_FLOOR_S = 20e-6  # shorter gaps are the device's own turnaround


def load_xplane(path: str, platform: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans, seen, stats = [], [], [], {}
    for plane in data.planes:
        lines = list(plane.lines)
        seen.append([plane.name, [ln.name for ln in lines][:12]])
        if platform == "tpu" and TPU_PLANE.match(plane.name):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for ln in lines:
                if ln.name in (OPS_LINE, MODULES_LINE):
                    key = "ops" if ln.name == OPS_LINE else "modules"
                    dev[key] = [
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in ln.events
                    ]
                    if key == "ops" and not stats:
                        # the first occurrence's stats of each operation: how
                        # a kernel is told apart when its name says little
                        for e in ln.events:
                            if e.name not in stats and len(stats) < 4000:
                                stats[e.name] = {
                                    str(k): str(v)[:160] for k, v in e.stats
                                }
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            cpu_ops = []
            for ln in lines:
                for e in ln.events:
                    name = e.name
                    if name.startswith(SPAN_PREFIX):
                        spans.append((name[len(SPAN_PREFIX):], e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9))
                    elif (platform != "tpu" and ln.name.startswith("tf_XLAPjRtCpuClient")
                          and e.duration_ns > 0 and not name.startswith(("end: ", "Thread"))):
                        cpu_ops.append((name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
            if platform != "tpu" and cpu_ops:
                # a rehearsal on the CPU: the host's own XLA thread stands in
                devices.append({"name": plane.name, "ops": cpu_ops, "modules": []})
    return {"devices": devices, "spans": spans, "planes_seen": seen, "op_stats": stats}


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """{name: [count, total seconds, self seconds]}; an event's self time is
    its duration less that of the events nested inside it (a loop's body)."""
    table, stack = {}, []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            stack[-1][2][2] -= dur  # take it out of its parent's self time
        row = table.setdefault(name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += dur
        row[2] += dur
        stack.append((name, end, row))
    return table


def short_name(name: str) -> str:
    """The TPU profiler names an operation by its whole HLO line; the part
    before " = " is the instruction's own name."""
    return name.split(" = ")[0].lstrip("%")


def family(name: str) -> str:
    """`attn_3._flash.4` -> `attn_#._flash.#`: the same operation of another
    layer or call site."""
    return re.sub(r"\d+", "#", short_name(name))


def reduce(events: dict) -> dict:
    devices, spans = events["devices"], events["spans"]
    if not devices or not any(d["ops"] for d in devices):
        raise ValueError("the trace holds no device operation")
    window = [s for s in spans if s[0] == "window"]
    if window:
        w0 = min(s[1] for s in window)
        w1 = max(s[1] + s[2] for s in window)
    else:
        w0 = min(e[1] for d in devices for e in d["ops"])
        w1 = max(e[1] + e[2] for d in devices for e in d["ops"])

    n = len(devices)
    busy_total, ops, modules, first_busy = 0.0, {}, {}, None
    for dev in devices:
        inside = [e for e in dev["ops"] if e[1] + e[2] > w0 and e[1] < w1]
        merged = _union([(max(s, w0), min(s + d, w1)) for _, s, d in inside])
        busy_total += sum(e - s for s, e in merged)
        if first_busy is None:
            first_busy = merged
        for name, row in _self_times(inside).items():
            acc = ops.setdefault(name, [0, 0.0, 0.0])
            for i in range(3):
                acc[i] += row[i] / n
        for name, s, d in dev["modules"]:
            if s + d > w0 and s < w1:
                acc = modules.setdefault(name, [0, 0.0])
                acc[0] += 1.0 / n
                acc[1] += d / n

    # idle gaps of the first device, by what the host was doing in them
    host = [s for s in spans if s[0] != "window"]
    gaps, edges = {}, [w0] + [t for iv in first_busy for t in iv] + [w1]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 - g0 < GAP_FLOOR_S:
            continue
        left = g1 - g0
        for name, s, d in host:
            overlap = min(g1, s + d) - max(g0, s)
            if overlap > 0:
                gaps[name] = gaps.get(name, 0.0) + overlap
                left -= overlap
        if left > 0:
            gaps["unattributed"] = gaps.get("unattributed", 0.0) + left

    by_self = sorted(ops.items(), key=lambda kv: -kv[1][2])
    families = {}
    for k, v in ops.items():
        families[family(k)] = families.get(family(k), 0.0) + v[2]
    by_family = sorted(families.items(), key=lambda kv: -kv[1])
    return {
        "window_s": w1 - w0,
        "busy_s": busy_total / n,
        "devices": n,
        "ops": {k: {"count": v[0], "seconds": v[1], "self_seconds": v[2]} for k, v in ops.items()},
        "modules": {k: {"count": v[0], "seconds": v[1]} for k, v in modules.items()},
        "device_ops": [[k, v] for k, v in by_family[:10]],
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()), key=lambda kv: -kv[1])[:10],
        "spans": _span_table(host, w0, w1),
        "planes_seen": events.get("planes_seen", []),
        "op_stats": {k: events.get("op_stats", {}).get(k, {}) for k, _ in by_self[:40]},
    }


def _span_table(host, w0, w1):
    out = {}
    for name, s, d in host:
        if s + d > w0 and s < w1:
            row = out.setdefault(name, {"count": 0, "seconds": 0.0})
            row["count"] += 1
            row["seconds"] += d
    return out
