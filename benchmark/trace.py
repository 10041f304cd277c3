"""Reduction of a JAX profiler trace to the device's busy time, its idle
gaps and the time of named kernels.

Device operations are the events of the ``XLA Ops`` line of each
``/device:*`` plane. Only a rehearsal on the CPU, where no device plane
exists, asks for the host events that carry an ``hlo_op`` stat instead
(``host_ops``); a chip run never reads a CPU event as a device op. Busy time is the union of their
intervals inside the window; the window is the benchmark's own
``bench.window`` annotation. Idle gaps are attributed to the innermost
other ``bench.*`` annotation (one per call into the program) that covers
the gap's middle.
"""

from __future__ import annotations

import re

WINDOW = "bench.window"


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:  # a stat type the reader cannot decode
        return {}


def load(path: str, host_ops: bool = False) -> dict:
    """``{"devices": {plane: [(name, start_ns, end_ns, hlo_text)]},
    "annotations": [(name, start_ns, end_ns)]}`` from an ``.xplane.pb``.
    With ``host_ops`` (rehearsal only) and no device plane, the host's XLA
    ops stand in for the device's."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    annotations: list = []
    cpu_ops: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"]
            evs = []
            for ln in ops:
                for ev in ln.events:
                    # on the TPU the op's name is its HLO instruction text
                    text = ev.name if " = " in ev.name else str(_stats(ev).get("long_name", ""))
                    evs.append((ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns), str(text)))
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith("bench."):
                        annotations.append((ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
                    elif host_ops and ev.duration_ns > 0:
                        st = _stats(ev)
                        if "hlo_op" in st:
                            cpu_ops.append((ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns), ""))
    if host_ops and not devices and cpu_ops:
        devices["/host:CPU (XLA ops)"] = cpu_ops
    return {"devices": devices, "annotations": annotations}


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def window_of(tr: dict) -> tuple[int, int]:
    spans = [(s, e) for n, s, e in tr["annotations"] if n == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW} annotation in the trace")
    return spans[0]


def _clip(iv, w0, w1):
    return [(max(s, w0), min(e, w1)) for s, e in iv if e > w0 and s < w1]


def reduce(tr: dict, n_devices: int, top: int = 10) -> dict:
    """Busy seconds (averaged over ``n_devices``), window seconds, the
    device ops that took most time, and the longest idle gaps by the
    benchmark annotation they fall in."""
    w0, w1 = window_of(tr)
    busy_ns = 0
    op_total: dict[str, int] = {}
    gaps: list[tuple[int, str]] = []
    calls = [(n, s, e) for n, s, e in tr["annotations"] if n != WINDOW]
    for plane in sorted(tr["devices"])[:n_devices]:
        evs = tr["devices"][plane]
        for name, s, e, _t in evs:
            for cs, ce in _clip([(s, e)], w0, w1):
                op_total[name] = op_total.get(name, 0) + (ce - cs)
        merged = union(_clip([(s, e) for _n, s, e, _t in evs], w0, w1))
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                mid = (gs + ge) // 2
                inside = [(e - s, n) for n, s, e in calls if s <= mid < e]
                gaps.append((ge - gs, min(inside)[1] if inside else "bench.window (between calls)"))
    window_s = (w1 - w0) / 1e9
    busy_s = busy_ns / 1e9 / max(1, n_devices)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "op_seconds": {k: v / 1e9 for k, v in op_total.items()},
        "device_ops": [[k, v / 1e9] for k, v in sorted(op_total.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, g / 1e9] for g, n in sorted(gaps, reverse=True)[:top]],
    }


def kernel_events(tr: dict, pattern: str, n_devices: int) -> list[tuple[float, str]]:
    """``[(seconds, hlo_text), ...]`` of the device ops inside the window
    whose name or HLO text matches ``pattern``."""
    w0, w1 = window_of(tr)
    rx = re.compile(pattern)
    out = []
    for plane in sorted(tr["devices"])[:n_devices]:
        for name, s, e, text in tr["devices"][plane]:
            if s >= w0 and e <= w1 and (rx.search(name) or rx.search(text)):
                out.append(((e - s) / 1e9, text))
    return out


_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|f16|bf16|f32|s64|u64|f64)\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


def shapes(hlo_text: str) -> list[tuple[str, tuple[int, ...]]]:
    """Every array shape written in an HLO instruction's text, result
    first, then operands."""
    return [(t, tuple(int(x) for x in dims.split(",") if x))
            for t, dims in _SHAPE.findall(hlo_text)]


def nbytes(dtype: str, dims: tuple[int, ...]) -> int:
    n = _BYTES[dtype]
    for d in dims:
        n *= d
    return n
