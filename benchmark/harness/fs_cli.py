"""Cells of `trivy fs` CI jobs: back-to-back scans through the program's
CLI entry (``trivy_tpu.cli.main``) in the process that holds the chip, one
CI runner in a closed loop, over the seed's pool of repositories
(traffic kind ``fs_trees``).

Each scan gets a fresh ``--cache-dir`` and an emptied in-process secret
dedup store, so it sees what a new `trivy fs` process would see; compiled
programs and device-resident tables stay, as set-up.
"""

from __future__ import annotations

import json
import logging
import os
import random

from benchmark import common
from benchmark.reference.licenses import LicenseReference
from benchmark.reference.secrets import SecretReference
from benchmark.traffic.trees import tree_pool

# the sample of files whose secret findings the reference recomputes per
# repository, besides every file with a planted secret and every file the
# program reported a secret in (license findings are checked for every file)
SECRET_SAMPLE = 48


class _Contained(logging.Handler):
    """Analyzer errors are logged and contained by design (one bad file
    must not kill a scan); here any such error fails the scan."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.messages: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.messages.append(record.getMessage())


def _secret_scanners() -> list:
    from trivy_tpu.fanal.analyzers import secret as secret_analyzer

    return [s for s, _d in secret_analyzer._scanner_cache.values()
            if hasattr(s, "clear_hit_cache")]


def _compiles() -> dict:
    """The program's compile ledger: compiles per kernel."""
    from trivy_tpu.obs import recorder

    doc = recorder.device_doc() or {}
    return {k: int(v.get("count", 0)) for k, v in (doc.get("compiles") or {}).items()}


class Scan:
    def __init__(self, index: int, repo: int, rc: int, seconds: float,
                 out: str, metrics: str | None, errors: list[str]):
        self.index, self.repo, self.rc, self.seconds = index, repo, rc, seconds
        self.out, self.metrics, self.errors = out, metrics, errors
        self.failure = None


def run_scan(args: list[str], index: int, repo: dict, repo_i: int, work: str,
             traced: bool) -> Scan:
    from trivy_tpu import cli

    for scanner in _secret_scanners():
        scanner.clear_hit_cache()
    out = os.path.join(work, f"out{index}.json")
    metrics = os.path.join(work, f"metrics{index}.json") if traced else None
    argv = list(args) + ["--cache-dir", os.path.join(work, f"cache{index}"), "-o", out]
    if metrics:
        argv += ["--metrics-out", metrics]
    contained = _Contained()
    watched = logging.getLogger("trivy_tpu.analyzer")
    watched.addHandler(contained)
    t0 = common.wall()
    try:
        with common.annotate(f"bench.fs_scan.repo{repo_i}"):
            rc = cli.main(argv + [repo["path"]])
    except Exception as e:  # a scan that raises is a failed request
        rc = f"raised {type(e).__name__}: {e}"
    finally:
        watched.removeHandler(contained)
    return Scan(index, repo_i, rc, common.wall() - t0, out, metrics, contained.messages)


def _findings(report: dict) -> tuple[dict, dict]:
    """``(secrets, licenses)`` per file from a report's Results."""
    secrets: dict[str, list] = {}
    licenses: dict[str, list] = {}
    for r in report.get("Results") or []:
        for f in r.get("Secrets") or []:
            secrets.setdefault(r.get("Target"), []).append(
                (f.get("RuleID"), f.get("StartLine"), f.get("EndLine"), f.get("Match")))
        for f in r.get("Licenses") or []:
            licenses.setdefault(f.get("FilePath"), []).append((f.get("Name"), f.get("Confidence")))
    return ({k: sorted(v) for k, v in secrets.items()},
            {k: sorted(v) for k, v in licenses.items()})


def _tree_files(root: str) -> dict[str, str]:
    out = {}
    for d, _dirs, names in os.walk(root):
        for n in names:
            full = os.path.join(d, n)
            out[os.path.relpath(full, root)] = full
    return out


class Checker:
    """The comparison that decides ``correct``: the program's findings in
    every scan of the window against the plain references, per file, for
    every license finding and for the secret findings of a seeded sample
    of files (with every file that holds a planted secret and every file
    the program reported a secret in)."""

    def __init__(self, seed: int, license_ref: LicenseReference | None = None):
        self.seed = seed
        self.secret_ref = SecretReference()
        self.license_ref = license_ref or LicenseReference()
        self._want: dict[int, tuple[dict, dict]] = {}

    def reference(self, repo_i: int, repo: dict, secret_files: set[str]) -> tuple[dict, dict]:
        files = _tree_files(repo["path"])
        rng = random.Random(f"{self.seed}:{repo_i}")
        sample = set(rng.sample(sorted(files), min(SECRET_SAMPLE, len(files))))
        sample |= set(repo.get("planted", [])) | secret_files
        secrets, licenses = {}, {}
        for rel, full in sorted(files.items()):
            with open(full, "rb") as f:
                data = f.read()
            if rel in sample:
                secrets[rel] = self.secret_ref.scan(rel, data)
            lic = self.license_ref.scan(rel, data)
            if lic is not None:
                licenses[rel] = sorted(lic[1])
        return secrets, licenses

    def compare(self, scans: list[Scan], pool: list[dict]) -> dict:
        secret_bad = license_bad = checked_secret = checked_license = 0
        examples: list = []
        for sc in scans:
            if sc.failure is not None:
                continue
            secrets, licenses = sc.report_findings
            if sc.repo not in self._want:
                self._want[sc.repo] = self.reference(sc.repo, pool[sc.repo], set(secrets))
            want_s, want_l = self._want[sc.repo]
            for rel in sorted(set(want_s) | set(secrets)):
                if rel not in want_s:
                    # reported in a file outside this repo's first sample:
                    # the reference scans it now
                    with open(os.path.join(pool[sc.repo]["path"], rel), "rb") as f:
                        want_s[rel] = self.secret_ref.scan(rel, f.read())
                got, want = secrets.get(rel, []), want_s[rel]
                checked_secret += len(want)
                if got != want:
                    secret_bad += len(set(got) ^ set(want)) or 1
                    examples.append(["secret", rel, got[:2], want[:2]])
            for rel in sorted(set(want_l) | set(licenses)):
                got, want = licenses.get(rel, []), want_l.get(rel, [])
                checked_license += len(want)
                if got != want:
                    license_bad += len(set(got) ^ set(want)) or 1
                    examples.append(["license", rel, got, want])
        return {"secret_mismatch": secret_bad, "license_mismatch": license_bad,
                "secret_findings_checked": checked_secret,
                "license_findings_checked": checked_license,
                "examples": examples[:6]}


def judge(scan: Scan) -> None:
    """Reads the scan's report; sets ``failure`` where the scan errored,
    exited non-zero, degraded, or contained an analyzer error."""
    if scan.rc != 0:
        scan.failure = f"exit {scan.rc}"
        return
    if scan.errors:
        scan.failure = f"contained analyzer errors: {scan.errors[:2]}"
        return
    try:
        with open(scan.out) as f:
            report = json.load(f)
    except (OSError, ValueError) as e:
        scan.failure = f"no report: {e}"
        return
    if report.get("Degraded"):
        scan.failure = "report is Degraded"
        return
    if scan.metrics:
        with open(scan.metrics) as f:
            counters = json.load(f).get("counters") or {}
        bad = {k: v for k, v in counters.items() if k.endswith(".degraded") and v}
        if bad:
            scan.failure = f"degraded counters {bad}"
            return
    scan.report_findings = _findings(report)


def run(bench: dict, cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        traced: bool, rehearse: bool, t_start: float, device: dict,
        license_ref: LicenseReference | None = None) -> tuple[dict, list]:
    """One run of the cell. Returns the result line's body and the checks."""
    if traffic["kind"] != "fs_trees":
        raise common.Refused(f"fs_cli drives fs_trees traffic, not {traffic['kind']!r}")
    t0 = common.wall()
    pool = tree_pool(common.inputs_dir(traffic), traffic, seed)
    common.say("inputs", {"repos": len(pool), "bytes": [r["bytes"] for r in pool],
                          "files": sum(r["files"] for r in pool),
                          "seconds": common.wall() - t0})
    work = common.fresh_dir(os.path.join(common.CACHE, "work", cell["name"]))
    args = list(config["command"])
    # warm-up: one untimed scan of every repository of the pool, the same
    # repositories the window scans in the same order, so that each shape
    # the program compiles lazily per batch is compiled before the window
    warm_s = []
    for w, repo in enumerate(pool):
        warm = run_scan(args, -1 - w, repo, w, work, traced)
        judge(warm)
        if warm.failure:
            raise RuntimeError(f"warm-up scan of repo{w} failed: {warm.failure}")
        warm_s.append(round(warm.seconds, 3))
    compiles0 = _compiles()
    setup_s = common.wall() - t_start
    common.say("setup", {"setup_s": setup_s, "warmup_scan_s": warm_s})

    scans: list[Scan] = []
    log_dir = os.path.join(common.CACHE, "trace", cell["name"])
    with common.profiler_window(traced, log_dir) as prof:
        with common.annotate("bench.window"):
            w0 = common.wall()
            i = 0
            while common.wall() - w0 < seconds:
                scans.append(run_scan(args, i, pool[i % len(pool)], i % len(pool), work, traced))
                i += 1
            window_s = common.wall() - w0
    compiles_in_window = {k: n - compiles0.get(k, 0) for k, n in _compiles().items()
                          if n != compiles0.get(k, 0)}
    peak = common.memory_peak_bytes(int(cell["chips"])) if not rehearse else 0
    for sc in scans:
        judge(sc)
    done = [sc for sc in scans if sc.failure is None]
    scanned = sum(pool[sc.repo]["bytes"] for sc in done)
    common.say("window", {"scans": len(scans), "failed": len(scans) - len(done),
                          "window_s": window_s, "bytes": scanned,
                          "compiles_in_window": compiles_in_window,
                          "scan_s": [round(sc.seconds, 3) for sc in scans],
                          "failures": [sc.failure for sc in scans if sc.failure][:3]})

    checker = Checker(seed, license_ref)
    t_ref = common.wall()
    cmp = checker.compare(done, pool)
    common.say("reference", dict(cmp, seconds=common.wall() - t_ref))
    checks = [("secret_mismatch", cmp["secret_mismatch"], 0),
              ("license_mismatch", cmp["license_mismatch"], 0)]
    body = {"correct": bool(done) and common.correct(checks), "attempted": len(scans),
            "failed": len(scans) - len(done)}
    device = dict(device, memory_peak_bytes=peak)
    if not traced:
        body["metrics"] = {
            "fs_scan_mb_per_s": {"value": scanned / 1e6 / window_s, "unit": "MB/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    else:
        readings = dict(program_totals(done), bytes_scanned=scanned, window_s=window_s)
        common.traced_result(bench, cell, body, device, readings, prof.get("xplane"), rehearse)
    body["device"] = device
    return body, checks


def program_totals(done: list[Scan]) -> dict:
    """The program's span totals and counters, summed over the traced
    scans' ``--metrics-out`` files."""
    spans: dict[str, float] = {}
    counters: dict[str, int] = {}
    for sc in done:
        with open(sc.metrics) as f:
            doc = json.load(f)
        for name, s in (doc.get("spans") or {}).items():
            spans[name] = spans.get(name, 0.0) + float(s.get("total", 0.0))
        for name, v in (doc.get("counters") or {}).items():
            if isinstance(v, (int, float)):
                counters[name] = counters.get(name, 0) + v
    return {"spans": spans, "counters": counters}


def bf16_sum(weights: list[float]) -> float:
    """The control's accumulation: the weights summed in bfloat16, the
    precision below the float32 that the device scorer states."""
    import ml_dtypes
    import numpy as np

    if not weights:
        return 0.0
    return float(np.sum(np.asarray(weights, dtype=ml_dtypes.bfloat16), dtype=ml_dtypes.bfloat16))


def control(bench: dict, cell: dict, config: dict, traffic: dict, seed: int,
            device: dict) -> tuple[dict, list]:
    """The control in the program's place: the reference itself, with the
    license scores accumulated in bfloat16, over every repository of the
    seed's pool, judged by the comparison and the rule of ``run``."""
    pool = tree_pool(common.inputs_dir(traffic), traffic, seed)
    low = Checker(seed, LicenseReference(accumulate=bf16_sum))
    scans = []
    for i, repo in enumerate(pool):
        files = _tree_files(repo["path"])
        secrets, licenses = {}, {}
        for rel, full in files.items():
            with open(full, "rb") as f:
                data = f.read()
            got = low.secret_ref.scan(rel, data)
            if got:
                secrets[rel] = got
            lic = low.license_ref.scan(rel, data)
            if lic and lic[1]:
                licenses[rel] = sorted(lic[1])
        sc = Scan(i, i, 0, 0.0, "", None, [])
        sc.report_findings = (secrets, licenses)
        scans.append(sc)
    cmp = Checker(seed).compare(scans, pool)
    common.say("control", cmp)
    checks = [("secret_mismatch", cmp["secret_mismatch"], 0),
              ("license_mismatch", cmp["license_mismatch"], 0)]
    return {"correct": bool(scans) and common.correct(checks), "attempted": len(scans)}, checks
