"""The table of peaks, keyed by ``device_kind``. A device that is not in the
table is an error, never a default; there is no override."""

from __future__ import annotations

import json
import os


def peaks_for(device_kind: str, table: dict = None) -> dict:
    if table is None:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
            table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]
