"""What this process runs on, said once at start-up by every entry point.

A run that measures or proves something names its device in its own
output (`jax.devices()[0].platform`, `.device_kind`, device count), so a
CPU run can never pass for a chip run. Parents that must stay off JAX
(`chip_smoke.py`) read the line back from a child's output.
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional

DEVICE_LINE_PREFIX = "[device] "


def device_info() -> Dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def log_device() -> Dict:
    """Print the parseable device line; returns the same dict."""
    info = device_info()
    print(DEVICE_LINE_PREFIX + json.dumps(info), flush=True)
    return info


def log_placement(mesh_shape) -> None:
    """Print the parseable placement line: the mesh and what each device
    holds once the state is on it."""
    print("[placement] " + json.dumps({
        "mesh": dict(mesh_shape), "bytes_in_use": bytes_in_use_per_device(),
    }), flush=True)


def bytes_in_use_per_device() -> List[Optional[int]]:
    """`memory_stats()["bytes_in_use"]` of every local device (None where
    the backend keeps no such count, as the CPU does) — shows whether a
    sharded placement really spread, or piled up on the first device."""
    import jax

    out = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        out.append(None if not stats else int(stats["bytes_in_use"]))
    return out
