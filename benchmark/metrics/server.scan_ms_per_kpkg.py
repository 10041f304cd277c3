"""rpc server (rpc/server.py): milliseconds of the server's ``server.scan``
span, as the server exports it per request, per thousand packages scanned.
Moves ``dep_scan_pkgs_per_s``."""


def read(r):
    total = r["spans"].get("server.scan")
    if total is None or not r.get("packages"):
        return None
    return 1e3 * total / (r["packages"] / 1e3)
