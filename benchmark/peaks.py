"""The chip's published peaks, keyed by ``device_kind`` (``peaks.json``).

No metric reads them yet: the verify kernels do uint32 Montgomery
arithmetic on the vector units, and none of these peaks (matrix-unit
FLOP/s, HBM bandwidth) bounds that work.  A device kind that is not in
the table is an error, never a default."""

from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(PATH, encoding="utf-8") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
