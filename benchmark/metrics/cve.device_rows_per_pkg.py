"""CVE join (detector/library.py): constraint rows the resident device
join compared (counter ``cve.resident_rows``) per package scanned; 0 where
every verdict came from the host comparator. Moves
``dep_scan_pkgs_per_s``."""


def read(r):
    if not r.get("packages"):
        return None
    return r["counters"].get("cve.resident_rows", 0) / r["packages"]
