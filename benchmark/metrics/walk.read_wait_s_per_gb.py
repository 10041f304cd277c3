"""walk and read (artifact/local_fs.py): seconds in the program's
``fs.read_wait`` span (summed over threads) per GB scanned, in the traced
run. Moves ``fs_scan_mb_per_s``."""


def read(r):
    total = r["spans"].get("fs.read_wait")
    if total is None or not r.get("bytes_scanned"):
        return None
    return total / (r["bytes_scanned"] / 1e9)
