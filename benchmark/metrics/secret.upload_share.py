"""feed and dedup (secret/feed.py, hitstore.py, compress.py): bytes the
feed uploaded to the device (counter ``secret.bytes_uploaded``) as a share
of the bytes scanned, in percent. Moves ``fs_scan_mb_per_s``."""


def read(r):
    up = r["counters"].get("secret.bytes_uploaded")
    if up is None or not r.get("bytes_scanned"):
        return None
    return 100.0 * up / r["bytes_scanned"]
