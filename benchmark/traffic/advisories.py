"""A trivy-db-shaped advisory DB, and a CycloneDX SBOM drawn from it.

``make_db`` and ``make_sbom`` are copied from ``chip_smoke.py`` (PR 21),
where the resident CVE join agreed with the host comparator on the v5e.
``make_db`` also returns every advisory it wrote, so the reference reads
the advisories from the generator and nothing from the program.
"""

from __future__ import annotations

import json
import os

import numpy as np

# bench_cve's trivy-db bucket plan (real source-bucket names)
DB_BUCKETS = (
    ("npm::GitHub Security Advisory Npm", 30_000),
    ("npm::Node.js Ecosystem Security Working Group", 10_000),
    ("pip::GitHub Security Advisory Pip", 20_000),
    ("pip::OSV/PyPA Advisory Database", 8_000),
    ("go::GitHub Security Advisory Go", 15_000),
    ("go::GitLab Advisory Database Community", 7_000),
    ("composer::GitHub Security Advisory Composer", 6_000),
    ("composer::php-security-advisories", 2_000),
    ("rubygems::ruby-advisory-db", 4_000),
    ("cargo::GitHub Security Advisory Rust", 4_000),
)
# SBOM share per ecosystem: (db prefix, purl type, share of packages)
SBOM_MIX = (
    ("npm", "npm", 0.40),
    ("pip", "pypi", 0.18),
    ("go", "golang", 0.20),
    ("composer", "composer", 0.08),
    ("rubygems", "gem", 0.06),
    ("cargo", "cargo", 0.08),
)
_SUFFIXES = ("", "", "", "-beta.1", "-rc2", "")


def pkg_name(eco: str, i: int) -> str:
    if eco == "go":
        return f"github.com/acme{i % 97}/go-pkg-{i:05d}"
    if eco == "composer":
        return f"acme{i % 31}/composer-pkg-{i:05d}"
    return f"{eco}-pkg-{i:05d}"


def advisories(seed) -> tuple[dict, dict]:
    """The DB's content without writing it: ``(buckets, names)``, where
    ``buckets[bucket][name]`` lists ``{"VulnerabilityID",
    "VulnerableVersions", "PatchedVersions"}`` and ``names[eco]`` is the
    number of distinct names of that ecosystem."""
    rng = np.random.default_rng(seed)
    names: dict[str, int] = {}
    buckets: dict[str, dict] = {}
    n_adv = 0
    for bucket, count in DB_BUCKETS:
        eco = bucket.split("::", 1)[0]
        n_names = count // 2
        names[eco] = max(names.get(eco, 0), n_names)
        rows: dict[str, list] = {}
        majors = rng.integers(0, 9, size=count)
        minors = rng.integers(0, 10, size=count)
        for i in range(count):
            lo = f"{majors[i]}.{minors[i]}.0{_SUFFIXES[i % len(_SUFFIXES)]}"
            hi = f"{majors[i] + 1}.{minors[i]}.0"
            rows.setdefault(pkg_name(eco, i % n_names), []).append({
                "VulnerabilityID": f"CVE-2024-{n_adv:06d}",
                "VulnerableVersions": [f">={lo}, <{hi}"],
                "PatchedVersions": [hi],
            })
            n_adv += 1
        buckets[bucket] = rows
    return buckets, names


def make_db(db_dir: str, seed) -> tuple[dict, dict]:
    """Flattened advisory DB (the program's db/convert.py layout) at
    bench_cve's shape, about 106k advisories. Returns
    ``advisories(seed)``."""
    buckets, names = advisories(seed)
    os.makedirs(os.path.join(db_dir, "advisories"), exist_ok=True)
    manifest: dict[str, str] = {}
    for bi, (bucket, rows) in enumerate(buckets.items()):
        rel = f"advisories/{bi:02d}.json"
        with open(os.path.join(db_dir, rel), "w") as f:
            json.dump({bucket: rows}, f)
        manifest[bucket] = rel
    with open(os.path.join(db_dir, "manifest.json"), "w") as f:
        json.dump({"buckets": manifest}, f)
    with open(os.path.join(db_dir, "metadata.json"), "w") as f:
        json.dump({"Version": 2, "NextUpdate": "2999-01-01T00:00:00Z"}, f)
    return buckets, names


def make_sbom(path: str, seed, names: dict, n_pkgs: int) -> int:
    """CycloneDX JSON with ``n_pkgs`` unique (ecosystem, name, version)
    components; about one name in six has no advisory at all. Kept for the
    `sbom_cli.big` cell that PERF.md lists for later."""
    rng = np.random.default_rng(seed)
    comps = []
    for eco, ptype, share in SBOM_MIX:
        want = int(round(n_pkgs * share))
        seen: set[tuple[str, str]] = set()
        while len(seen) < want:
            k = want - len(seen)
            idx = rng.integers(0, int(names[eco] * 1.2), size=k)
            ver = rng.integers(0, 10, size=(k, 3))
            pre = rng.random(k) < 0.05
            for j in range(k):
                v = f"{ver[j, 0]}.{ver[j, 1]}.{ver[j, 2]}" + ("-rc1" if pre[j] else "")
                seen.add((pkg_name(eco, int(idx[j])), v))
        for name, v in sorted(seen):
            pv = f"v{v}" if eco == "go" else v
            comps.append({
                "type": "library", "name": name, "version": pv,
                "purl": f"pkg:{ptype}/{name}@{pv}",
                "bom-ref": f"pkg:{ptype}/{name}@{pv}",
            })
    with open(path, "w") as f:
        json.dump({"bomFormat": "CycloneDX", "specVersion": "1.5",
                   "version": 1, "components": comps}, f)
    return len(comps)
