"""Peak rates by ``device_kind`` (``peaks.json``) and the bytes a kernel
must move, computed from its shapes."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> dict:
    """The peaks of one chip; a device not in the table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def minplus_bytes(batch: int, n_landmarks: int) -> int:
    """HBM bytes of one sketch contraction ``(B, R) x (R, R) -> (B, R)``
    over int32, unpadded: what the sketch needs, not what the tiles move."""
    return 4 * (2 * batch * n_landmarks + n_landmarks * n_landmarks)
