"""Spans of the layers the per-layer metrics read: the CLI run's set-up and
report, the scanner's inspect and detect, and the CVE layer's host
comparator, encode and assembly around the unchanged ``cve.join``."""

import json
import os
import subprocess
import sys

import pytest

from tests.dbtest import build_db
from trivy_tpu import obs
from trivy_tpu.db import VulnDB
from trivy_tpu.detector import library
from trivy_tpu.types import Application, Package

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cli_fs_scan_spans_its_layers(tmp_path):
    tree = tmp_path / "tree"
    (tree / "src").mkdir(parents=True)
    (tree / "src" / "gh.txt").write_text("token ghp_" + "A1b2C3d4E5f6G7h8I9j0K1l2M3n4O5p6Q7r8" + " end\n")
    (tree / "LICENSE").write_text("Permission is hereby granted, free of charge, to any person\n")
    metrics = tmp_path / "metrics.json"
    p = subprocess.run(
        [sys.executable, "-m", "trivy_tpu.cli", "fs", "--scanners", "secret,license",
         "--backend", "cpu", "--format", "json", "-o", str(tmp_path / "out.json"),
         "--cache-dir", str(tmp_path / "cache"), "--metrics-out", str(metrics), str(tree)],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        timeout=600,
    )
    assert p.returncode == 0, p.stderr
    doc = json.loads(metrics.read_text())
    spans = doc["spans"]
    for name in ("commands.setup", "scan.inspect", "scan.detect", "report.write"):
        assert spans[name]["count"] == 1, name
    assert doc["counters"]["commands.scans"] == 1
    # set-up ends where the inspect begins: the walk is not set-up
    assert spans["scan.inspect"]["total"] >= spans["fs.batch_analyze"]["total"] > 0


@pytest.fixture
def db(tmp_path):
    return VulnDB.load(build_db(tmp_path))


def _spans(db, apps) -> dict:
    with obs.scan_context(name="cve", enabled=True) as ctx:
        out = library.detect_batch(db, apps)
        return {"out": out, "stats": ctx.stage_stats()}


def test_host_comparator_is_spanned(db):
    app = Application(type="poetry", file_path="poetry.lock",
                      packages=[Package(name="django", version="3.2.0", id="p0")])
    got = _spans(db, [app])
    assert got["stats"]["cve.host_compare"]["count"] == 1
    assert not {"cve.join", "cve.encode", "cve.assemble"} & set(got["stats"])
    assert got["out"][0] == library.detect(db, app)


def test_resident_join_host_side_is_spanned(db, monkeypatch):
    pkgs = [Package(name=["lodash", "minimist"][i % 2], version=f"4.17.{i % 25}", id=f"p{i}")
            for i in range(library.BATCH_THRESHOLD + 8)]
    app = Application(type="npm", file_path="package-lock.json", packages=pkgs)
    got = _spans(db, [app])
    stats = got["stats"]
    assert stats["cve.join"]["count"] == 1
    assert stats["cve.encode"]["count"] == 1
    assert stats["cve.assemble"]["count"] == 2  # verdict reduction, then findings
    assert stats["cve.dispatch"]["count"] == 1
    monkeypatch.setattr(library, "BATCH_THRESHOLD", 10**9)
    key = lambda v: (v.pkg_id, v.vulnerability_id, v.fixed_version)  # noqa: E731
    assert sorted(map(key, got["out"][0])) == sorted(map(key, library.detect(db, app)))
    assert got["out"][0]
