"""license (licensing/): seconds in the spans ``license.dispatch``,
``license.device_wait`` and ``license.finalize`` per GB scanned. Moves
``fs_scan_mb_per_s``."""

SPANS = ("license.dispatch", "license.device_wait", "license.finalize")


def read(r):
    got = [r["spans"][s] for s in SPANS if s in r["spans"]]
    if not got or not r.get("bytes_scanned"):
        return None
    return sum(got) / (r["bytes_scanned"] / 1e9)
