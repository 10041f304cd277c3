"""device: the share of the traced window, in percent, in which the device
was idle while some host thread was inside the program's
``commands.setup`` or ``report.write`` span: the fixed host work of each
scan, on the profiler's clock (benchmark/program_spans.py). Moves
``fs_scan_mb_per_s``."""

SPANS = ("commands.setup", "report.write")


def read(r):
    from benchmark import program_spans

    rd = program_spans.reading(r)
    if rd is None or not rd["window_s"]:
        return None
    idle = program_spans.idle_overlapping(rd, SPANS)
    if idle is None:
        return None
    return 100.0 * idle / rd["window_s"]
