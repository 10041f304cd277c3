"""kernels (ops/rules.py): (row, rule) pairs the device rule pass flagged
(counter ``secret.rule_candidates``: anchored hits and keyword
candidates), per MB scanned. Each one costs the host confirm a look, so
it moves ``fs_scan_mb_per_s``."""


def read(r):
    n = r["counters"].get("secret.rule_candidates")
    if n is None or not r.get("bytes_scanned"):
        return None
    return n / (r["bytes_scanned"] / 1e6)
