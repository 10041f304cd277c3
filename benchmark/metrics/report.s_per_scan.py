"""report (commands._emit, report/): seconds of the program's
``report.write`` span (filter, render, write) per scan, the counter
``commands.scans``. Moves ``fs_scan_mb_per_s``."""


def read(r):
    total = r["spans"].get("report.write")
    scans = r["counters"].get("commands.scans")
    if total is None or not scans:
        return None
    return total / scans
