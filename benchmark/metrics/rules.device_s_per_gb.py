"""kernels (ops/rules.py): device seconds of the table-driven secret rule
pass per GB scanned. The pass is jitted as the stage ``stage.rules``
(``TpuSecretScanner``), one program per bucket rung whatever the rule
count; its time is that of the stage's module executions in the profiler
trace (benchmark/program_spans.py). Moves ``fs_scan_mb_per_s``."""

KERNELS = ("stage.rules",)


def read(r):
    from benchmark import program_spans

    rd = program_spans.reading(r)
    if rd is None or not r.get("bytes_scanned"):
        return None
    seconds = program_spans.device_s(rd, KERNELS)
    if seconds <= 0:
        return None
    return seconds / (r["bytes_scanned"] / 1e9)
