"""CVE join (detector/library.py): milliseconds of the server-side
``cve.join`` span per thousand packages scanned. Moves
``dep_scan_pkgs_per_s``."""


def read(r):
    total = r["spans"].get("cve.join")
    if total is None or not r.get("packages"):
        return None
    return 1e3 * total / (r["packages"] / 1e3)
