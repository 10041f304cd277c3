"""The program's own spans and kernel names on the profiler's clock.

The program's ``obs`` spans are ``jax.profiler.TraceAnnotation``s of their
own names while a profiler session records, on the host plane of the same
trace as the device ops. Every kernel the program registers through
``obs/recorder.instrument_jit`` is jitted under its kernel's name, so each
of its executions is an event ``jit_<kernel>(<fingerprint>)`` on the
``XLA Modules`` line of the device plane (a v5e trace's ops carry no
``op_name``: only their HLO text and device offsets). A kernel jitted
inside another runs in the outer one's module: the fused license gate
(``ops.gram_gate``) runs as the stage ``stage.license``. This module reads
both from a run's ``.xplane.pb`` and gives, over the ``bench.window``:

- the device's idle seconds overlapping each program span name (a piece
  of idle time counts under every name some thread was inside then);
- the idle seconds overlapping no program span;
- device seconds per kernel (the durations of its module executions).

Idle time is taken from the trace as ``benchmark/trace.py`` loaded it,
so it is the idle time of ``device_idle_pct.*``. A program without these
spans or kernel names reads nothing here, and the readers built on it
return None.

    python3 -m benchmark.program_spans <xplane> --cell <name>

prints the table for one trace.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re

from benchmark import common, trace

# an XLA module event's name: ``jit_<function name>(<fingerprint>)``
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
# what the command line takes for a program span: a dotted name whose
# every component starts with a letter (HLO op names end in ``.<n>``),
# other than the benchmark's own ``bench.*`` annotations
_SPAN = re.compile(r"^[a-z_][a-z0-9_]*(?:\.[a-z_][A-Za-z0-9_]*)+$")

_LOADED: dict = {}


def newest_xplane() -> str | None:
    """The newest trace under the fixed home of the cells' traces
    (``profiler_window`` empties a cell's directory every traced run): the
    run's own, since the readers run right after its window."""
    files = glob.glob(os.path.join(common.CACHE, "trace", "*", "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def kernel_of(module: str) -> str:
    """The function name an XLA module event was jitted under: the
    kernel's registered name for an instrumented kernel."""
    return _MODULE.match(module).group(1)


def load(path: str, names: frozenset | None) -> dict:
    """``{"window": (start_ns, end_ns) | None, "spans": {name: [(start_ns,
    end_ns)]}, "modules": {plane: [(start_ns, end_ns, kernel)]}}``: the
    host events named in ``names`` (None: every name shaped like a program
    span), and each device plane's module executions, sorted. Loaded once
    per (path, names)."""
    if names is None:
        keep = lambda n: bool(_SPAN.match(n)) and not n.startswith("bench.")  # noqa: E731
    else:
        keep = names.__contains__
    key = (path, names)
    if key in _LOADED:
        return _LOADED[key]
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window, spans, modules = None, {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            evs = []
            for ln in plane.lines:
                if ln.name == "XLA Modules":
                    for ev in ln.events:
                        s = int(ev.start_ns)
                        evs.append((s, s + int(ev.duration_ns), kernel_of(ev.name)))
            if evs:
                modules[plane.name] = sorted(evs)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == trace.WINDOW:
                        window = (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    elif keep(ev.name):
                        s = int(ev.start_ns)
                        spans.setdefault(ev.name, []).append((s, s + int(ev.duration_ns)))
    _LOADED[key] = out = {"window": window, "spans": spans, "modules": modules}
    return out


def idle_gaps(tr: dict, n_devices: int) -> list[list[tuple[int, int]]]:
    """Per device plane (the first ``n_devices``, as ``trace.reduce``
    takes them), the idle intervals of the window, sorted."""
    w0, w1 = trace.window_of(tr)
    out = []
    for plane in sorted(tr["devices"])[:n_devices]:
        busy = trace.union([(max(s, w0), min(e, w1)) for _n, s, e, _t in tr["devices"][plane]
                            if e > w0 and s < w1])
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        out.append([(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s])
    return out


def overlap_ns(gaps: list[tuple[int, int]], cover: list[tuple[int, int]]) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total = i = j = 0
    while i < len(gaps) and j < len(cover):
        s, e = max(gaps[i][0], cover[j][0]), min(gaps[i][1], cover[j][1])
        if e > s:
            total += e - s
        if gaps[i][1] <= cover[j][1]:
            i += 1
        else:
            j += 1
    return total


def table(tr: dict, prog: dict, n_devices: int) -> dict | None:
    """The idle split by program span and the device seconds per kernel
    for a loaded trace (``trace.load``) and its program spans and module
    executions (:func:`load`); None when the two are not one run's."""
    try:
        w0, w1 = trace.window_of(tr)
    except ValueError:
        return None
    if prog["window"] != (w0, w1):
        return None
    gaps = idle_gaps(tr, n_devices)
    n = max(1, len(gaps))
    spans = {name: trace.union(iv) for name, iv in prog["spans"].items()}
    every = trace.union([iv for ivs in spans.values() for iv in ivs])
    idle_ns = sum(e - s for g in gaps for s, e in g)
    by_kernel: dict[str, int] = {}
    for plane in sorted(prog["modules"])[:n_devices]:
        for s, e, kernel in prog["modules"][plane]:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                by_kernel[kernel] = by_kernel.get(kernel, 0) + (e - s)
    return {
        "window_s": (w1 - w0) / 1e9,
        "idle_s": idle_ns / 1e9 / n,
        "idle_by_span": {name: sum(overlap_ns(g, iv) for g in gaps) / 1e9 / n
                         for name, iv in sorted(spans.items())},
        "idle_unattributed_s": (idle_ns - sum(overlap_ns(g, every) for g in gaps)) / 1e9 / n,
        "span_events": sum(1 for ivs in prog["spans"].values() for s, _e in ivs if w0 <= s < w1),
        "device_s_by_kernel": {k: ns / 1e9 for k, ns in by_kernel.items()},
        "gaps": gaps,
        "spans": spans,
    }


def reading(r: dict) -> dict | None:
    """:func:`table` for a traced run's readings (``r["trace"]``, the
    program's span names ``r["spans"]``), from the newest trace file;
    None without a trace."""
    tr = r.get("trace")
    path = newest_xplane()
    if not tr or not path:
        return None
    return table(tr, load(path, frozenset(r.get("spans") or ())), int(r.get("chips", 1)))


def idle_overlapping(rd: dict, names) -> float | None:
    """Idle seconds during which some thread was inside one of ``names``;
    None when none of them fired."""
    ivs = [iv for name in names for iv in rd["spans"].get(name, ())]
    if not ivs:
        return None
    cover = trace.union(ivs)
    return sum(overlap_ns(g, cover) for g in rd["gaps"]) / 1e9 / max(1, len(rd["gaps"]))


def device_s(rd: dict, kernels) -> float:
    """Device seconds of the module executions of ``kernels``."""
    return sum(rd["device_s_by_kernel"].get(k, 0.0) for k in kernels)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description="Idle time by program span and device time by kernel.")
    ap.add_argument("xplane")
    ap.add_argument("--cell", required=True, help="the workload, for its chip count")
    ap.add_argument("--rehearsal", action="store_true",
                    help="a CPU rehearsal's trace: its host XLA ops stand in for device ops")
    args = ap.parse_args(argv)
    chips = int({w["name"]: w for w in common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
                 ["workloads"]}[args.cell]["chips"])
    tr = trace.load(args.xplane, host_ops=args.rehearsal)
    prog = load(args.xplane, None)
    rd = table(tr, prog, chips)
    if rd is None:
        print(f"no {trace.WINDOW} annotation in {args.xplane}")
        return 1
    doc = {k: rd[k] for k in ("window_s", "idle_s", "idle_unattributed_s", "span_events")}
    doc["idle_by_span"] = dict(sorted(rd["idle_by_span"].items(), key=lambda kv: -kv[1]))
    doc["device_s_by_kernel"] = dict(sorted(rd["device_s_by_kernel"].items(), key=lambda kv: -kv[1]))
    print(json.dumps(doc, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
