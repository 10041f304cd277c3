"""Detection layer: ospkg + library drivers against a fixture DB."""

import json

import pytest

from tests.dbtest import build_db
from trivy_tpu.db import VulnDB
from trivy_tpu.detector import library, ospkg
from trivy_tpu.types import Application, OS, Package
from trivy_tpu.vulnerability import fill_infos


@pytest.fixture
def db(tmp_path):
    return VulnDB.load(build_db(tmp_path))


def test_ospkg_alpine(db):
    os_info = OS(family="alpine", name="3.18")
    pkgs = [
        Package(name="musl", version="1.2.3", release="r0"),
        Package(name="busybox", version="1.36.1", release="r0"),
        Package(name="zlib", version="1.3", release="r0"),
    ]
    vulns = ospkg.detect(db, os_info, pkgs)
    by_id = {v.vulnerability_id: v for v in vulns}
    # musl 1.2.3-r0 < 1.2.4-r1 -> vulnerable
    assert "CVE-2023-0001" in by_id
    assert by_id["CVE-2023-0001"].fixed_version == "1.2.4-r1"
    # busybox: fixed advisory 1.36.1-r1 (vulnerable at r0) + unfixed advisory
    assert "CVE-2023-0002" in by_id
    assert by_id["CVE-2023-0003"].status == "affected"
    assert "zlib" not in {v.pkg_name for v in vulns}


def test_ospkg_alpine_full_version_normalizes_to_major_minor(db):
    # os-release VERSION_ID is the full "3.18.4" but advisories are bucketed
    # by major.minor; the driver must normalize or every lookup misses
    os_info = OS(family="alpine", name="3.18.4")
    pkgs = [Package(name="musl", version="1.2.3", release="r0")]
    vulns = ospkg.detect(db, os_info, pkgs)
    assert [v.vulnerability_id for v in vulns] == ["CVE-2023-0001"]


def test_ospkg_wolfi_rolling_versionless_bucket(db):
    # rolling distros key advisories on a versionless bucket ("wolfi"),
    # whatever the reported os version is
    os_info = OS(family="wolfi", name="20230201")
    pkgs = [Package(name="git", version="2.39.0", release="r0")]
    vulns = ospkg.detect(db, os_info, pkgs)
    assert [v.vulnerability_id for v in vulns] == ["CVE-2023-9999"]


def test_ospkg_fixed_version_not_vulnerable(db):
    os_info = OS(family="alpine", name="3.18")
    pkgs = [Package(name="musl", version="1.2.4", release="r1")]
    vulns = ospkg.detect(db, os_info, pkgs)
    assert [v.vulnerability_id for v in vulns] == []


def test_ospkg_debian_epoch(db):
    os_info = OS(family="debian", name="12.4")  # bucket keyed by major
    pkgs = [Package(name="openssl", version="3.0.9", release="1")]
    vulns = ospkg.detect(db, os_info, pkgs)
    assert [v.vulnerability_id for v in vulns] == ["CVE-2023-1111"]


def test_library_npm(db):
    app = Application(
        type="npm",
        file_path="package-lock.json",
        packages=[
            Package(name="lodash", version="4.17.20"),
            Package(name="lodash", version="4.17.21"),
            Package(name="minimist", version="1.2.0"),
            Package(name="minimist", version="0.2.4"),
        ],
    )
    vulns = library.detect(db, app)
    got = {(v.pkg_name, v.installed_version): v for v in vulns}
    assert ("lodash", "4.17.20") in got
    assert got[("lodash", "4.17.20")].fixed_version == "4.17.21"
    assert ("lodash", "4.17.21") not in got
    assert ("minimist", "1.2.0") in got  # in >=1.0.0,<1.2.3 range
    assert ("minimist", "0.2.4") not in got  # between the two ranges


def test_fill_infos(db):
    app = Application(type="npm", packages=[Package(name="lodash", version="4.0.0")])
    vulns = library.detect(db, app)
    fill_infos(db, vulns)
    v = vulns[0]
    assert v.title == "lodash command injection"
    assert v.severity == "HIGH"
    assert v.cwe_ids == ["CWE-77"]
    assert v.primary_url.endswith("cve-2021-23337")


def test_vendor_severity_priority(db):
    os_info = OS(family="alpine", name="3.18")
    vulns = ospkg.detect(db, os_info, [Package(name="busybox", version="1.0", release="r0")])
    fill_infos(db, vulns)
    v = {x.vulnerability_id: x for x in vulns}["CVE-2023-0002"]
    assert v.severity == "MEDIUM"  # nvd rank 2, preferred over alpine
    assert v.severity_source == "nvd"


def test_batch_detect_parity(db, monkeypatch):
    """Device-batched constraint evaluation == host evaluation on a large
    synthetic npm application (the 50k-package SBOM path)."""
    import random

    from trivy_tpu.detector import library as lib

    rng = random.Random(9)
    pkgs = []
    for i in range(1200):
        name = rng.choice(["lodash", "minimist", "other-pkg"])
        ver = f"{rng.randint(0,4)}.{rng.randint(0,20)}.{rng.randint(0,25)}"
        pkgs.append(Package(name=name, version=ver, id=f"p{i}"))
    app = Application(type="npm", file_path="package-lock.json", packages=pkgs)

    batched = lib.detect(db, app)  # >= BATCH_THRESHOLD -> device path
    monkeypatch.setattr(lib, "BATCH_THRESHOLD", 10**9)
    host = lib.detect(db, app)
    key = lambda v: (v.pkg_id, v.vulnerability_id)
    assert sorted(map(key, batched)) == sorted(map(key, host))
    assert {(v.pkg_id, v.fixed_version) for v in batched} == {
        (v.pkg_id, v.fixed_version) for v in host
    }
    assert len(batched) > 0


def test_bounds_device_widest_only_eviction(db):
    """Satellite: `_bounds_dev` keeps exactly ONE resident copy — a wider
    scan evicts the narrower upload, and later narrower requests reuse the
    wide buffer without re-uploading."""
    index = db.prefix_advisories("npm::")
    cp = library._compile_prefix(index, "semver")
    dev8, w8 = cp.bounds_device(8)
    first = cp.upload_bytes
    assert first > 0 and cp._bounds_dev[0] == w8
    dev24, w24 = cp.bounds_device(24)
    assert w24 >= 24
    assert cp._bounds_dev == (w24, dev24)  # single slot: narrower evicted
    assert dev24.shape[1] == w24
    assert cp.upload_bytes > first
    after_wide = cp.upload_bytes
    dev_again, w_again = cp.bounds_device(8)  # narrower req -> wide buffer
    assert dev_again is dev24 and w_again == w24
    assert cp.upload_bytes == after_wide  # and no re-upload


def _sbom(n_apps: int = 6, per_app: int = 120):
    """Multi-ecosystem SBOM: alternating npm/pip apps plus one app of an
    unsupported type."""
    import random

    rng = random.Random(11)
    apps = []
    for ai in range(n_apps):
        pkgs = []
        for i in range(per_app):
            if ai % 2 == 0:
                name = rng.choice(["lodash", "minimist", "no-such-pkg"])
                ver = (
                    f"{rng.randint(0, 4)}.{rng.randint(0, 20)}"
                    f".{rng.randint(0, 25)}"
                )
            else:
                name = rng.choice(["django", "flask"])
                ver = (
                    f"{rng.randint(3, 5)}.{rng.randint(0, 2)}"
                    f".{rng.randint(0, 9)}"
                )
            pkgs.append(Package(name=name, version=ver, id=f"a{ai}p{i}"))
        apps.append(
            Application(
                type="npm" if ai % 2 == 0 else "pip",
                file_path=f"app{ai}/lock",
                packages=pkgs,
            )
        )
    apps.append(
        Application(
            type="obscure-eco",
            file_path="x",
            packages=[Package(name="z", version="1.0")],
        )
    )
    return apps


def test_detect_batch_multi_ecosystem_parity(db, monkeypatch):
    """One-pass resident join over a multi-app multi-ecosystem SBOM ==
    per-app host detection, and the WHOLE SBOM rides one device dispatch."""
    from trivy_tpu.detector import library as lib

    apps = _sbom()
    out = lib.detect_batch(db, apps)
    rj = db._lib_resident
    assert rj.dispatch_count == 1  # npm + pip apps in one dispatch
    assert out[-1] == []  # unsupported ecosystem contributes nothing
    monkeypatch.setattr(lib, "BATCH_THRESHOLD", 10**9)
    key = lambda v: (v.pkg_id, v.vulnerability_id, v.fixed_version)
    for app, got in zip(apps, out):
        want = lib.detect(db, app)
        assert sorted(map(key, got)) == sorted(map(key, want))
    assert sum(len(v) for v in out) > 0


def test_detect_batch_small_sbom_falls_back_per_app(db, monkeypatch):
    """Below BATCH_THRESHOLD the per-app host path runs — no join builds."""
    from trivy_tpu.detector import library as lib

    apps = [
        Application(
            type="npm",
            file_path="lock",
            packages=[Package(name="lodash", version="4.17.20", id="p0")],
        )
    ]
    out = lib.detect_batch(db, apps)
    assert [v.vulnerability_id for v in out[0]] == ["CVE-2021-23337"]
    assert getattr(db, "_lib_resident", None) is None


def test_resident_join_second_scan_zero_upload(db):
    """The global bound matrix stays device-resident across scans: the
    second detect_batch call moves ZERO bound-table bytes over the link."""
    from trivy_tpu import obs
    from trivy_tpu.detector import library as lib

    apps = _sbom()
    with obs.scan_context(name="scan1", enabled=True) as ctx:
        lib.detect_batch(db, apps)
        first = ctx.counters.get("cve.bounds_bytes_uploaded", 0)
    assert first > 0  # first scan pays the upload once
    rj = db._lib_resident
    resident_bytes = rj.upload_bytes
    with obs.scan_context(name="scan2", enabled=True) as ctx:
        out2 = lib.detect_batch(db, apps)
        assert ctx.counters.get("cve.bounds_bytes_uploaded", 0) == 0
    assert rj.upload_bytes == resident_bytes
    assert db._lib_resident is rj  # join object cached on the db
    assert sum(len(v) for v in out2) > 0


def test_resident_join_fresh_after_db_swap(tmp_path):
    """A DBReloader hot swap installs a FRESH db object — the new db has no
    resident join, so stale bounds cannot leak through a feed update."""
    path = build_db(tmp_path)
    db1 = VulnDB.load(path)
    apps = _sbom()
    library.detect_batch(db1, apps)
    rj1 = db1._lib_resident
    db2 = VulnDB.load(path)  # what DBReloader.reload() swaps in
    assert getattr(db2, "_lib_resident", None) is None
    out = library.detect_batch(db2, apps)
    assert db2._lib_resident is not rj1
    assert sum(len(v) for v in out) > 0


def test_detect_batch_device_fault_is_loud_without_host_fallback(db):
    """--no-host-fallback reaches the resident join through ScanOptions:
    a device failure fails detection instead of degrading to the host
    comparator."""
    from trivy_tpu import faults
    from trivy_tpu.detector import detect_all
    from trivy_tpu.scanner import ScanOptions
    from trivy_tpu.types import ArtifactDetail

    detail = ArtifactDetail(applications=_sbom())
    faults.configure("device.dispatch@cve:times=-1")
    try:
        with pytest.raises(faults.InjectedFault):
            detect_all(db, "sbom", detail, ScanOptions(host_fallback=False))
        assert detect_all(db, "sbom", detail, ScanOptions())  # degrades
    finally:
        faults.clear()


def test_detect_batch_device_fault_degrades_to_host(db):
    """A device.dispatch@cve fault degrades the WHOLE batch to the host
    comparator with identical findings (the parity oracle), and the
    degradation is visible in scan health."""
    from trivy_tpu import faults, obs
    from trivy_tpu.detector import library as lib

    apps = _sbom()
    want = lib.detect_batch(db, apps)  # healthy pass (also builds the join)
    faults.configure("device.dispatch@cve:times=-1")
    try:
        with obs.scan_context(name="chaos", enabled=True) as ctx:
            got = lib.detect_batch(db, apps)
            assert ctx.counters.get("cve.degraded", 0) >= 1
            assert ctx.health_snapshot().get("cve.degraded", 0) >= 1
    finally:
        faults.clear()
    key = lambda v: (v.pkg_id, v.vulnerability_id, v.fixed_version)
    for g, w in zip(got, want):
        assert sorted(map(key, g)) == sorted(map(key, w))
    assert sum(len(v) for v in got) > 0
