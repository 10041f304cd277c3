"""kernels (ops/rules.py): the table-driven rule pass's share of its HBM
roofline, in percent: the least time of its executions (the bytes of
``benchmark/kernels_rules.py`` over the chip's HBM bandwidth, peaks.json:
819 GB/s on a v5e; HBM is the bound taken, as that module says why) over
the device time of its module executions (``stage.rules``) in the
profiler trace. Moves ``fs_scan_mb_per_s``."""

KERNELS = ("stage.rules",)


def read(r):
    from benchmark import kernels_rules, program_spans

    peaks = r.get("peaks")
    moved = kernels_rules.least_bytes(r.get("counters") or {})
    rd = program_spans.reading(r)
    if not peaks or not moved or rd is None:
        return None
    seconds = program_spans.device_s(rd, KERNELS)
    if seconds <= 0:
        return None
    return 100.0 * (moved / peaks["hbm_bytes_per_s"]) / seconds
