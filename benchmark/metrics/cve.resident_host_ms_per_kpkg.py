"""CVE join (detector/library.py): milliseconds of the server-side
``cve.encode`` (unique-version encode and row index build before the
device dispatch) and ``cve.assemble`` (group reduction, fixed versions,
findings, sort) spans per thousand packages scanned: the resident join's
host side outside ``cve.join`` and ``cve.dispatch``. A program with the
CVE layer's host spans that never took the resident join reads 0. Moves
``dep_scan_pkgs_per_s``."""

SPANS = ("cve.host_compare", "cve.encode", "cve.assemble")


def read(r):
    spans = r["spans"]
    if not r.get("packages") or not any(s in spans for s in SPANS):
        return None
    return 1e3 * (spans.get("cve.encode", 0.0) + spans.get("cve.assemble", 0.0)) / (r["packages"] / 1e3)
