"""host confirm (secret/engine.py): seconds of exact host confirmation
(span ``secret.confirm``, summed over threads) per GB scanned. Moves
``fs_scan_mb_per_s``."""


def read(r):
    total = r["spans"].get("secret.confirm")
    if total is None or not r.get("bytes_scanned"):
        return None
    return total / (r["bytes_scanned"] / 1e9)
