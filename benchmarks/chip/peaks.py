"""Published per-chip peaks, keyed by JAX's ``device_kind``.

The table is ``peaks.json`` beside this file, each entry with its
source.  A device that is not in the table is an error, never a
default: a roofline share against the wrong peak is a wrong number."""

from __future__ import annotations

import json
import os

TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, table: str = TABLE) -> dict:
    with open(table) as f:
        known = json.load(f)
    if device_kind not in known:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(known)}")
    return known[device_kind]
