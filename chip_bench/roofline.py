"""Peaks of the chips the benchmark knows, and the byte floor of an SpMV.

The peaks come from ``peaks.json``, keyed by the ``device_kind`` that JAX
reports, each with its source.  A device that is not in the table is an
error, never a default.
"""
from __future__ import annotations

import json
from pathlib import Path

__all__ = ["UnknownDevice", "peak", "floor_bytes"]

PEAKS_FILE = Path(__file__).with_name("peaks.json")


class UnknownDevice(KeyError):
    """The device kind has no entry in ``peaks.json``."""


def peak(device_kind: str, table: Path = PEAKS_FILE) -> dict:
    """The published peaks of one chip of ``device_kind``."""
    peaks = json.loads(table.read_text())
    if device_kind not in peaks:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"{table.name}; known: {sorted(peaks)}")
    return peaks[device_kind]


def floor_bytes(nnz: int, nrows: int, ncols: int, batch: int) -> int:
    """Least bytes one ``y = A x`` request of ``batch`` vectors moves
    through HBM, computed from the matrix alone: the float32 values read
    once, x read once and y written once.

    Column ids and row offsets are left out on purpose.  A layout may
    compress them (a tiled bitmask, delta-coded ids, or none at all for a
    dense block), so any count of index bytes would describe one layout
    and not the work.  Without them the floor is below what every layout
    moves, whatever implementation serves the request, and a share of the
    roofline taken against it can never read over 100%."""
    return 4 * int(nnz) + 4 * int(batch) * (int(nrows) + int(ncols))
