"""The chip a run found, and its published peaks.

Peaks live in ``bench/peaks.json``, keyed by JAX's ``device_kind``, each with
its source. A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

from pathlib import Path

from benchlib.spec import BENCH_DIR, load_json


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def peaks(device_kind: str, path: Path = BENCH_DIR / "peaks.json") -> dict:
    table = load_json(path)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def chips_for(cell_chips: int):
    """The devices a run uses: the machine's TPU chips, at least as many as
    the cell asks for. Imports JAX, so call it only once the run is sure to
    need the device."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform is "
                     f"{devices[0].platform!r}); refusing to run")
    if len(devices) < cell_chips:
        raise NoChip(f"the cell needs {cell_chips} chips, found "
                     f"{len(devices)}")
    return devices


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks_in_use = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_in_use.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_in_use) if peaks_in_use else None
