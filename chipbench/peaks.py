"""Published peaks of the chips the benchmark may run on, keyed by the
`device_kind` JAX reports. A chip that is not in the table is an error,
never a default."""

from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def lookup(device_kind: str) -> dict:
    table = json.loads(TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {TABLE.name} (known: {sorted(table)})")
    return table[device_kind]
