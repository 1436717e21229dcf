"""Published peaks of each chip, keyed by JAX's ``device_kind``
(``peaks.json``, with its source).  A kind not in the table is an error."""
from __future__ import annotations

import json
import pathlib

TABLE = pathlib.Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    table = json.loads(TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
