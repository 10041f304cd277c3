"""Aux subsystem tests: post-scan hooks, tracing, compliance, plugins
(ref: pkg/scanner/post, pkg/compliance, pkg/plugin tests)."""

from __future__ import annotations

import io

import pytest

from trivy_tpu import obs, plugin
from trivy_tpu.compliance import apply_spec, load_spec, write_report
from trivy_tpu.scanner.post import (
    PostScanner,
    deregister_post_scanner,
    post_scan,
    register_post_scanner,
    scanner_versions,
)
from trivy_tpu.types import MisconfResult, Report, Result


class TestPostScan:
    def test_hook_rewrites_results(self):
        class Dropper(PostScanner):
            name = "dropper"
            version = 3

            def post_scan(self, results):
                return [r for r in results if r.target != "drop-me"]

        register_post_scanner(Dropper())
        try:
            assert scanner_versions() == {"dropper": 3}
            out = post_scan([Result(target="drop-me"), Result(target="keep")])
            assert [r.target for r in out] == ["keep"]
        finally:
            deregister_post_scanner("dropper")

    def test_hook_error_not_fatal(self):
        class Boom(PostScanner):
            name = "boom"

            def post_scan(self, results):
                raise RuntimeError("x")

        register_post_scanner(Boom())
        try:
            out = post_scan([Result(target="t")])
            assert [r.target for r in out] == ["t"]
        finally:
            deregister_post_scanner("boom")

    def test_driver_runs_hooks(self):
        from trivy_tpu.cache import new_cache
        from trivy_tpu.scanner import ScanOptions
        from trivy_tpu.scanner.local_driver import LocalDriver
        from trivy_tpu.types import BlobInfo

        class Tagger(PostScanner):
            name = "tagger"

            def post_scan(self, results):
                for r in results:
                    r.target = "tagged:" + r.target
                return results

        cache = new_cache("memory", None)
        cache.put_blob("b1", BlobInfo(
            secrets=[],
        ).to_dict())
        register_post_scanner(Tagger())
        try:
            driver = LocalDriver(cache)
            results, _ = driver.scan("t", "a1", ["b1"], ScanOptions(scanners=["secret"]))
            assert all(r.target.startswith("tagged:") for r in results)
        finally:
            deregister_post_scanner("tagger")


class TestTrace:
    def test_spans_report(self):
        obs.current().reset()
        obs.enable()
        with obs.span("unit.test.span"):
            pass
        obs.add("unit.test.add", 0.5)
        buf = io.StringIO()
        obs.report(buf)
        out = buf.getvalue()
        assert "unit.test.span" in out and "unit.test.add" in out
        obs.current().reset()


class TestCompliance:
    def test_builtin_spec_pass_fail(self):
        report = Report(results=[Result(
            target="d.yaml", cls="config",
            misconfigurations=[
                MisconfResult(status="FAIL", id="KSV017", avd_id="AVD-KSV-0017"),
                MisconfResult(status="PASS", id="KSV012", avd_id="AVD-KSV-0012"),
            ],
        )])
        creport = apply_spec(load_spec("k8s-nsa-1.0"), report)
        by_id = {r.control.id: r for r in creport.results}
        assert by_id["1.2"].status == "FAIL"       # privileged: KSV017 failed
        assert by_id["1.0"].status == "PASS"       # non-root: KSV012 passed
        assert by_id["2.0"].status == "MANUAL"
        assert creport.summary["FAIL"] == 1

    def test_custom_spec_file(self, tmp_path):
        spec_yaml = """\
spec:
  id: my-spec
  title: My Spec
  controls:
    - id: C1
      name: no privileged pods
      severity: HIGH
      checks:
        - id: KSV017
"""
        p = tmp_path / "spec.yaml"
        p.write_text(spec_yaml)
        spec = load_spec(f"@{p}")
        assert spec.id == "my-spec"
        assert spec.controls[0].checks == ["KSV017"]

    def test_unknown_spec(self):
        with pytest.raises(ValueError):
            load_spec("nope")

    def test_report_renderers(self):
        creport = apply_spec(load_spec("docker-cis-1.6.0"), Report(results=[]))
        table = io.StringIO()
        write_report(creport, table, "table")
        assert "CIS Docker" in table.getvalue()
        import json

        jout = io.StringIO()
        write_report(creport, jout, "json")
        doc = json.loads(jout.getvalue())
        assert doc["ID"] == "docker-cis-1.6.0"
        assert all(r["Status"] in ("PASS", "FAIL", "MANUAL") for r in doc["Results"])


@pytest.fixture
def plugin_src(tmp_path):
    src = tmp_path / "hello"
    src.mkdir()
    (src / "plugin.yaml").write_text(
        "name: hello\nversion: 1.0.0\nsummary: say hello\n"
        "platforms:\n  - bin: ./hello.sh\n"
    )
    binf = src / "hello.sh"
    binf.write_text("#!/bin/sh\necho hello-from-plugin $1\nexit 7\n")
    binf.chmod(0o755)
    return src


class TestPlugin:
    def test_install_list_run_uninstall(self, tmp_path, plugin_src, capfd):
        root = str(tmp_path / "plugins")
        manifest = plugin.install(str(plugin_src), root=root)
        assert manifest["name"] == "hello"
        assert [m["name"] for m in plugin.list_installed(root=root)] == ["hello"]
        rc = plugin.run("hello", ["world"], root=root)
        assert rc == 7
        assert "hello-from-plugin world" in capfd.readouterr().out
        assert plugin.uninstall("hello", root=root)
        assert plugin.list_installed(root=root) == []

    def test_install_archive(self, tmp_path, plugin_src):
        import tarfile

        archive = tmp_path / "hello.tar.gz"
        with tarfile.open(archive, "w:gz") as tf:
            tf.add(plugin_src, arcname="hello")
        root = str(tmp_path / "plugins2")
        manifest = plugin.install(str(archive), root=root)
        assert manifest["name"] == "hello"
        assert plugin.run("hello", [], root=root) == 7

    def test_missing_plugin(self, tmp_path):
        with pytest.raises(plugin.PluginError):
            plugin.run("ghost", [], root=str(tmp_path / "empty"))

    def test_platform_selector_mismatch(self, tmp_path, plugin_src):
        (plugin_src / "plugin.yaml").write_text(
            "name: hello\nversion: 1.0.0\n"
            "platforms:\n  - selector: {os: plan9}\n    bin: ./hello.sh\n"
        )
        root = str(tmp_path / "plugins3")
        plugin.install(str(plugin_src), root=root)
        with pytest.raises(plugin.PluginError):
            plugin.run("hello", [], root=root)


K8S_DUMP = """\
apiVersion: v1
kind: List
items:
  - apiVersion: apps/v1
    kind: Deployment
    metadata: {name: web, namespace: prod}
    spec:
      template:
        spec:
          containers:
            - name: app
              image: nginx:latest
              securityContext: {privileged: true}
  - apiVersion: v1
    kind: ConfigMap
    metadata: {name: cfg, namespace: prod}
    data: {k: v}
"""


class TestK8s:
    def test_manifest_dump_scan(self, tmp_path):
        from trivy_tpu import k8s

        p = tmp_path / "dump.yaml"
        p.write_text(K8S_DUMP)
        docs = k8s.load_manifests(str(p))
        assert len(docs) == 2  # List flattened
        rows = k8s.scan_workloads(docs)
        assert len(rows) == 1  # ConfigMap is not a workload
        row = rows[0]
        assert (row["namespace"], row["kind"], row["name"]) == ("prod", "Deployment", "web")
        assert any(f.id == "KSV017" for f in row["failures"])  # privileged
        assert row["severities"]["HIGH"] >= 1

    def test_summary_writers(self, tmp_path):
        from trivy_tpu import k8s

        p = tmp_path / "dump.yaml"
        p.write_text(K8S_DUMP)
        rows = k8s.scan_workloads(k8s.load_manifests(str(p)))
        table = io.StringIO()
        k8s.write_summary(rows, table, "table")
        assert "Workload Assessment" in table.getvalue()
        jout = io.StringIO()
        k8s.write_summary(rows, jout, "json")
        import json as _json

        doc = _json.loads(jout.getvalue())
        assert doc["Resources"][0]["Kind"] == "Deployment"
        assert doc["Resources"][0]["Misconfigurations"]

    def test_manifest_dir_and_plain_docs(self, tmp_path):
        from trivy_tpu import k8s

        d = tmp_path / "manifests"
        d.mkdir()
        (d / "pod.yaml").write_text(
            "apiVersion: v1\nkind: Pod\nmetadata: {name: p}\n"
            "spec: {containers: [{name: c, image: x}]}\n"
        )
        (d / "notes.txt").write_text("ignored")
        rows = k8s.scan_workloads(k8s.load_manifests(str(d)))
        assert [r["name"] for r in rows] == ["p"]


class TestSbomFileAnalyzer:
    def test_bitnami_style_spdx(self):
        import json as _json

        from trivy_tpu.fanal.analyzer import AnalysisInput, AnalyzerOptions
        from trivy_tpu.fanal.analyzers.sbom_file import SbomFileAnalyzer
        from trivy_tpu.fanal.walker import FileInfo

        bom = _json.dumps({
            "bomFormat": "CycloneDX", "specVersion": "1.5",
            "components": [{"type": "library", "name": "lodash",
                            "version": "4.17.20",
                            "purl": "pkg:npm/lodash@4.17.20"}],
        }).encode()
        a = SbomFileAnalyzer(AnalyzerOptions())
        assert a.required("opt/bitnami/app/.spdx-app.spdx",
                          FileInfo(size=10, mode=0o644))
        assert not a.required("src/main.py", FileInfo(size=10, mode=0o644))
        res = a.analyze(AnalysisInput(
            dir="", file_path="opt/app/bom.json",
            info=FileInfo(size=len(bom), mode=0o644), content=bom,
        ))
        pkg = res.applications[0].packages[0]
        assert (pkg.name, pkg.version) == ("lodash", "4.17.20")

    def test_garbage_sbom_ignored(self):
        from trivy_tpu.fanal.analyzer import AnalysisInput, AnalyzerOptions
        from trivy_tpu.fanal.analyzers.sbom_file import SbomFileAnalyzer
        from trivy_tpu.fanal.walker import FileInfo

        a = SbomFileAnalyzer(AnalyzerOptions())
        res = a.analyze(AnalysisInput(
            dir="", file_path="bom.json",
            info=FileInfo(size=3, mode=0o644), content=b"not json",
        ))
        assert res is None
