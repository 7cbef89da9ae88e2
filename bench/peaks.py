"""The chips' published peaks (``peaks.json``), keyed by the
``device_kind`` JAX reports.  A kind not in the table is an error: a
roofline share against a guessed peak means nothing."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = PATH) -> dict:
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; {path} has {sorted(table)}")
    return table[device_kind]
