"""Operations and bytes of the work, computed from the shapes alone."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def streaming_bytes_per_round(m: int, n: int, cols: int) -> int:
    """The least HBM traffic of one round of Algorithm 1 with X in fp32:
    X (m, n, cols) read once, B and P (m, cols) each read and written."""
    return 4 * m * n * cols + 4 * (4 * m * cols)


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; an unknown kind is an
    error."""
    table = json.loads(PEAKS.read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add them with their source") from None
