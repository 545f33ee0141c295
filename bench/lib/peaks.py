"""Published peaks of each accelerator kind the benchmark may run on,
read from ``peaks.json`` beside this file. A kind that is not in the table
is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).with_name("peaks.json")


class UnknownDevice(KeyError):
    """The device kind has no row in the table of peaks."""


def peaks(device_kind: str, table: Path = TABLE) -> dict:
    """The row of ``device_kind``: bf16_flops, int8_ops, hbm_bytes_per_s,
    hbm_bytes."""
    rows = json.loads(table.read_text())["kinds"]
    if device_kind not in rows:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {table.name} "
            f"(known: {sorted(rows)})")
    return dict(rows[device_kind])
