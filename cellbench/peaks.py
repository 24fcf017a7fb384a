"""The table of peaks, keyed by `device_kind`. A device that is not in the
table is an error, never a default."""

from __future__ import annotations

import json
import os


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            "with its source to cellbench/peaks.json"
        )
    return table[device_kind]
