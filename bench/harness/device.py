"""The chip a run is on: refusal without one, the device record, peaks.

A run that finds no TPU, or fewer chips than its cell asks for, raises
:class:`NoChip`; the entry point turns that into a non-zero exit with no
result line.  There is no CPU branch.
"""
from __future__ import annotations

import json
import os
import time
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def process_start_time() -> float:
    """The process's start on the ``time.time()`` clock (Linux
    ``/proc``); the module's import time where ``/proc`` is missing."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = int(fields[19])          # field 22: starttime
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + start_ticks / ticks
    except (OSError, ValueError, IndexError):
        return _IMPORTED_AT


_IMPORTED_AT = time.time()


def require_chips(n: int, platform: str = "tpu"):
    """The first ``n`` devices, all on ``platform``; raises NoChip."""
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"needs a {platform.upper()}; JAX found "
                     f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < n:
        raise NoChip(f"cell needs {n} chips; JAX found {len(devs)}")
    return devs[:n]


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name}; known: {sorted(table['devices'])}")
    return table["devices"][device_kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the
    backend keeps no statistics)."""
    best = 0
    for d in devices:
        stats = d.memory_stats() or {}
        best = max(best, int(stats.get("peak_bytes_in_use", 0)))
    return best


def record(devices) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": memory_peak_bytes(devices)}
