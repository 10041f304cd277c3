"""license (licensing/fused.py, ops/gram_gate.py): device seconds of the
fused license shingle gate per GB scanned. The gate is the kernel
``ops.gram_gate``; in the fused secret+license pass it runs as the stage
``stage.license`` (``TpuSecretScanner._ensure_license_stage``), whose
module holds the gate alone. Its time is that of those kernels' module
executions in the profiler trace (benchmark/program_spans.py). Moves
``fs_scan_mb_per_s``."""

KERNELS = ("ops.gram_gate", "stage.license")


def read(r):
    from benchmark import program_spans

    rd = program_spans.reading(r)
    if rd is None or not r.get("bytes_scanned"):
        return None
    seconds = program_spans.device_s(rd, KERNELS)
    if seconds <= 0:
        return None
    return seconds / (r["bytes_scanned"] / 1e9)
