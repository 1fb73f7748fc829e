"""The table of peaks, keyed by ``device_kind``.  An unknown kind is an
error, never a default."""

from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_TABLE) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        known = sorted(k for k in table if not k.startswith("_"))
        raise KeyError(
            f"device kind {device_kind!r} is not in perf/peaks.json "
            f"(known: {known}): add it with its source"
        )
    return table[device_kind]
