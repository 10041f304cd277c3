"""kernels (ops/match_pallas.py): the secret match kernel's share of its
HBM roofline, in percent. Each match ``pallas_call`` reads its padded
input rows once and writes its hit rows once (the grid walks row blocks,
each block holds whole rows), so its least time is those bytes, computed
from the shapes in the op's HLO text (benchmark/kernels.py), over the
chip's HBM bandwidth (peaks.json: 819 GB/s on a v5e). The kernel is bound
by HBM bandwidth on this count; its VPU work is not counted. Kernel time
is the sum of those ops' device durations in the profiler trace.

KERNEL is the pattern of the match kernel's ops as the trace names them.
The kernels have no stable names yet; a v5e trace (PR 22, PERF.md §5)
shows each match group as an op whose name is its HLO text,
``%fn.3 = s16[1024,113]{...} custom-call(u8[1024,8704]{...} %pad.2),
custom_call_target="tpu_custom_call"``: an int16 hit row per built-in rule
(113) out of padded uint8 chunk rows."""

KERNEL = r"= s16\[\d+,113\]\S* custom-call\(u8\["


def read(r):
    from benchmark import kernels, trace

    tr, peaks = r.get("trace"), r.get("peaks")
    if not tr or not peaks:
        return None
    events = trace.kernel_events(tr, KERNEL, r.get("chips", 1))
    seconds = sum(s for s, _t in events)
    moved = sum(kernels.pallas_call_bytes(t) for _s, t in events)
    if seconds <= 0 or moved <= 0:
        return None
    return 100.0 * (moved / peaks["hbm_bytes_per_s"]) / seconds
