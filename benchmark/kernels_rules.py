"""Operations and bytes of the table-driven secret rule pass
(``stage.rules``), from its shapes and table sizes.

Each execution reads its batch of rows (``rows x chunk_len`` bytes) and
the ruleset's tables once, and writes one result row of bit words per
input row; that is its least HBM traffic, whatever implements it. The
program reports those three sizes per scan as counters
(``secret.rule_row_bytes``, ``secret.rule_table_bytes``,
``secret.rule_out_bytes``), summed over its executions.

The bound taken is HBM bandwidth: the pass is integer work on the VPU
(about ``OPS_PER_BYTE`` operations per input byte in the dense key test:
word packing, case fold, hash, bitmap lookup, candidate count), and
``peaks.json`` states no VPU peak for the chip, so no VPU bound can be
computed; the HBM bound is the one the table supports.
"""

from __future__ import annotations

# integer operations per input byte of the dense key test (packing a
# 4-byte word twice, the A-Z fold, the multiplicative hash, the bitmap
# word gather and bit test, the position mask and the running count)
OPS_PER_BYTE = 24


def least_bytes(counters: dict) -> int | None:
    """HBM bytes the traced executions of the pass had to move, or None
    where the program reports no rule pass."""
    parts = [counters.get(k) for k in ("secret.rule_row_bytes", "secret.rule_table_bytes",
                                        "secret.rule_out_bytes")]
    if any(p is None for p in parts):
        return None
    return int(sum(parts))


def operations(counters: dict) -> int | None:
    rows = counters.get("secret.rule_row_bytes")
    return None if rows is None else int(rows) * OPS_PER_BYTE
