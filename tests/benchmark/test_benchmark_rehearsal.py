"""Each cell rehearsed end to end on the CPU at a tiny size, through the
benchmark's rehearsal mode; the refusals (no TPU, no program in the
checkout); and the faults that ``correct`` must catch, planted under the
timed path while the rest of the run goes as on the chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import common, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FS_SEED = 2  # the first repository of its tiny pool holds planted secrets


@pytest.fixture(autouse=True)
def cache_in_tmp(tmp_path_factory, monkeypatch):
    monkeypatch.setattr(common, "CACHE", str(tmp_path_factory.getbasetemp() / "bench-cache"))


@pytest.fixture(autouse=True)
def restore_program_logging():
    """The CLI configures the program's logger (its own handler, no
    propagation); later tests in this worker read logs through caplog."""
    import logging

    from trivy_tpu import log

    root = logging.getLogger("trivy_tpu")
    saved = (list(root.handlers), root.level, root.propagate, log._configured)
    yield
    root.handlers[:] = saved[0]
    root.setLevel(saved[1])
    root.propagate = saved[2]
    log._configured = saved[3]


def _rehearse(capsys, workload, seed, *extra):
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds", "0.2",
                   "--rehearse", *extra])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-5:]
    last = out[-1]
    prefix = "rehearsal (CPU, not a chip result): "
    assert last.startswith(prefix)
    with pytest.raises(ValueError):
        json.loads(last)  # the driver can never take it for a result
    return json.loads(last[len(prefix):]), out


def _said(out, key):
    return json.loads([ln for ln in out if ln.startswith(key + ": ")][-1][len(key) + 2:])


@pytest.mark.parametrize("workload", ["ci_tree.fresh", "dep_server.node_go", "dep_server.py_ruby"])
def test_cell_rehearsal(capsys, workload):
    body, _out = _rehearse(capsys, workload, FS_SEED)
    assert body["correct"] is True
    assert body["attempted"] > 0 and body["failed"] == 0
    assert "setup_s" in body["metrics"]
    assert list(body)[-1] == "checks" and all(c["limit"] == 0 for c in body["checks"].values())


def test_traced_rehearsal_reads_per_layer_metrics(capsys):
    body, _out = _rehearse(capsys, "ci_tree.fresh", FS_SEED, "--trace", "1")
    assert body["correct"] is True
    assert "walk.read_wait_s_per_gb" in body["metrics"] and "setup_s" not in body["metrics"]
    assert body["device"]["window_s"] > 0
    assert len(body["breakdown"]["device_ops"]) <= 10


def test_refuses_a_host_without_a_tpu(capsys):
    assert run.main(["--workload", "ci_tree.fresh", "--seed", "1", "--seconds", "1",
                     "--trace", "0"]) == 3
    out, err = capsys.readouterr()
    assert "correct" not in out and "not a TPU" in err


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "ci_tree.fresh",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True, timeout=60,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "correct" not in p.stdout


# -- faults under the timed path: correct must come out false ---------------


def _shift_line(orig):
    def render(rule, loc, lines):
        f = orig(rule, loc, lines)
        f.start_line += 1
        return f
    return render


def _lower_confidence(orig):
    def rank(self, found):
        return orig(self, [(c - 0.01, w, n) for c, w, n in found])
    return rank


def _half_the_files(orig):
    def finalize(self):
        self._files = self._files[::2]
        return orig(self)
    return finalize


def test_fs_pool_has_planted_secrets(capsys):
    _body, out = _rehearse(capsys, "ci_tree.fresh", FS_SEED)
    assert _said(out, "reference")["secret_findings_checked"] > 0


@pytest.mark.parametrize("fault,check", [
    ("secret line altered where produced", "secret_mismatch"),
    ("license confidence altered where produced", "license_mismatch"),
    ("half of the license batch left out", "license_mismatch"),
])
def test_fs_faults_fail_correct(capsys, monkeypatch, fault, check):
    from trivy_tpu.fanal.analyzers import license as license_analyzer
    from trivy_tpu.licensing import classify
    from trivy_tpu.secret import engine

    if fault.startswith("secret"):
        monkeypatch.setattr(engine, "_render_finding", _shift_line(engine._render_finding))
    elif fault.startswith("license confidence"):
        monkeypatch.setattr(classify.LicenseClassifier, "_rank_findings",
                            _lower_confidence(classify.LicenseClassifier._rank_findings))
    else:
        cls = license_analyzer._LicenseBatchAnalyzer
        monkeypatch.setattr(cls, "finalize", _half_the_files(cls.finalize))
    body, _out = _rehearse(capsys, "ci_tree.fresh", FS_SEED)
    assert body["correct"] is False
    assert body["checks"][check]["value"] > body["checks"][check]["limit"]


@pytest.mark.parametrize("fault", ["fixed version altered where produced",
                                   "half of each request's packages left out"])
def test_dep_faults_fail_correct(capsys, monkeypatch, fault):
    from trivy_tpu.detector import library

    if fault.startswith("fixed"):
        orig = library._finding

        def finding(scheme, pkg, adv, fixed_version=None):
            f = orig(scheme, pkg, adv, fixed_version)
            f.fixed_version = "0.0.1"
            return f
        monkeypatch.setattr(library, "_finding", finding)
    else:
        orig = library.detect_batch

        def detect_batch(db, apps, *a, **kw):
            for app in apps:
                app.packages = app.packages[::2]
            return orig(db, apps, *a, **kw)
        monkeypatch.setattr(library, "detect_batch", detect_batch)
    body, _out = _rehearse(capsys, "dep_server.node_go", 5)
    assert body["correct"] is False
    assert body["checks"]["vuln_mismatch"]["value"] > 0


def test_fs_control_fails(capsys):
    assert run.main(["--workload", "ci_tree.fresh", "--seed", str(FS_SEED), "--rehearse",
                     "--control"]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    line = json.loads(last[len("control: "):])
    assert line["correct"] is False
    assert line["checks"]["license_mismatch"]["value"] > 0


def test_dep_control_fails(capsys):
    """The control (pre-release tags dropped) over a pool of realistic
    lockfile sizes; the tiny rehearsal pool holds too few release
    candidates at an advisory's bounds to show it."""
    from benchmark.harness import rpc_server

    bench, cell, config, traffic = common.load_cell("dep_server.node_go")
    traffic = dict(traffic, name="node_go", pool=48, clients=1)
    body, checks = rpc_server.control(bench, cell, config, traffic, 9, {})
    assert body["correct"] is False
    checks = {n: v for n, v, _lim in checks}
    assert checks["vuln_mismatch"] > 0 and checks["inventory_mismatch"] == 0


def test_coarse_order_drops_prereleases():
    from benchmark.harness.rpc_server import coarse_compare
    from benchmark.reference.vulns import compare

    assert compare("semver", "1.2.0-rc1", "1.2.0") == -1
    assert coarse_compare("semver", "1.2.0-rc1", "1.2.0") == 0
    assert compare("pep440", "1.2.0rc1", "1.2.0b1") == 1
    assert compare("gem", "1.2.0.rc1", "1.2.0-rc2") == 1  # "1.2.0.pre.rc2" < "1.2.0.rc1"
