"""device: idle share of the traced window, in percent: 1 - busy over the
window, where busy is the union of the device ops' intervals in the
profiler trace (benchmark/trace.py). Moves ``fs_scan_mb_per_s``."""


def read(r):
    red = r.get("reduced")
    if not red or red.get("idle_share") is None:
        return None
    return 100.0 * red["idle_share"]
