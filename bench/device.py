"""The chip a run measures: presence, identity, peaks and memory."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_chips(count: int):
    """The first ``count`` TPU devices, or NoChip. Never falls back to the
    CPU: a number measured there is not a number of the chip."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0].platform is "
                     f"{devs[0].platform!r}")
    if len(devs) < count:
        raise NoChip(f"{count} chips needed, JAX found {len(devs)}")
    return devs[:count]


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; KeyError for a kind that is
    not in the table (there is no default)."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{Path(path).name}; known: {sorted(table)}")
    return table[device_kind]


def describe(devs) -> dict:
    """The result line's ``device`` entry, as JAX reports it."""
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int | None:
    """``peak_bytes_in_use`` on the fullest chip, where reported."""
    peaks_seen = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                  for d in devs]
    peaks_seen = [p for p in peaks_seen if p is not None]
    return max(peaks_seen) if peaks_seen else None
