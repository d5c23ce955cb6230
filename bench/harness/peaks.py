"""Published per-chip peaks, keyed by JAX's ``device_kind``."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict[str, float]:
    """The peaks of ``device_kind``; a device missing from the table is an
    error, never a default."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(table)}")
    return {k: float(v) for k, v in table[device_kind].items()}
