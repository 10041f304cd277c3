"""CLI and commands (cli.py, commands.py): seconds of the program's
``commands.setup`` span (scan context entry to the artifact's inspect():
ignore policy, options, cache and DB open, driver, artifact and scanner
construction) per scan, the counter ``commands.scans``. Moves
``fs_scan_mb_per_s``."""


def read(r):
    total = r["spans"].get("commands.setup")
    scans = r["counters"].get("commands.scans")
    if total is None or not scans:
        return None
    return total / scans
