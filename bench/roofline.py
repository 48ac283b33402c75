"""Peaks of the chip and the bytes a lookup needs, for roofline shares.

The peaks live in ``peaks.json`` keyed by JAX's ``device_kind``; a device
that is not in the table is an error, never a default.
"""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


def lookup_row_bytes(table_cfg: dict) -> int:
    """Bytes of one bucket row over every plane a lookup reads, from the
    configuration's own widths: the fingerprint bytes, the two key halves
    and the value of each slot (4 bytes each), and the bucket's metadata
    word (allocation bits)."""
    slots = int(table_cfg["num_slots"])
    fp = slots if table_cfg.get("use_fingerprints", True) else 0
    return fp + 3 * 4 * slots + 4


def lookup_bytes(table_cfg: dict) -> int:
    """Least bytes one lookup needs: two bucket rows (the target bucket and
    its probing neighbour) of every plane it reads."""
    return 2 * lookup_row_bytes(table_cfg)
