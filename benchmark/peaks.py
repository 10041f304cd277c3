"""The table of peaks (``peaks.json``), keyed by JAX's ``device_kind``. A
kind that is not in the table is an error, never a default."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def lookup(device_kind: str) -> dict | None:
    """The peaks of ``device_kind``; None for the CPU of a rehearsal, which
    has no roofline. Raises for an accelerator the table lacks."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind in table:
        return table[device_kind]
    if device_kind.lower() == "cpu":
        return None
    raise KeyError(f"no peaks for device kind {device_kind!r} in benchmark/peaks.json")
