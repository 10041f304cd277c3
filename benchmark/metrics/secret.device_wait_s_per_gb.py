"""dispatch (parallel/mesh.py StagedDispatch): seconds the host waited on
device results (span ``secret.device_wait``, summed over threads) per GB
scanned. Moves ``fs_scan_mb_per_s``."""


def read(r):
    total = r["spans"].get("secret.device_wait")
    if total is None or not r.get("bytes_scanned"):
        return None
    return total / (r["bytes_scanned"] / 1e9)
