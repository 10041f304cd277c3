"""CVE join (detector/library.py): milliseconds of the server-side
``cve.host_compare`` span (every verdict of the host comparator: whole
applications below the batch threshold or of schemes the resident join
does not hold, and host-only advisories of a resident batch) per thousand
packages scanned. A program with the CVE layer's host spans that never
compared on the host reads 0. Moves ``dep_scan_pkgs_per_s``."""

SPANS = ("cve.host_compare", "cve.encode", "cve.assemble")


def read(r):
    spans = r["spans"]
    if not r.get("packages") or not any(s in spans for s in SPANS):
        return None
    return 1e3 * spans.get("cve.host_compare", 0.0) / (r["packages"] / 1e3)
