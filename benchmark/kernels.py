"""Operations and bytes of the device kernels, from their shapes.

A ``pallas_call`` whose grid walks blocks of whole rows reads each operand
byte once and writes each result byte once, so its HBM traffic is the size
of its operands plus its results. The shapes come from the op's HLO text
in the profiler trace (result first, then operands)."""

from __future__ import annotations

from benchmark.trace import nbytes, shapes


def pallas_call_bytes(hlo_text: str) -> int:
    """HBM bytes moved by one row-blocked ``pallas_call``: every array
    named in its instruction (its result and its operands) once."""
    head = hlo_text.split(", custom_call_target")[0].split(", metadata")[0]
    return sum(nbytes(t, d) for t, d in shapes(head))


def match_kernel_bytes(rows: int, padded_width: int, rules: int) -> int:
    """The same count from the kernel's own shapes: uint8 input rows of
    ``padded_width`` bytes in, int16 hit rows of ``rules`` columns out."""
    return rows * padded_width + rows * rules * 2
