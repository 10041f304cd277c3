"""Cells of `trivy fs --scanners secret --secret-config <org ruleset>` CI
jobs (traffic kind ``fs_rule_trees``): the ``fs_cli`` loop, scans and
judgement, with the configuration's custom rules generated from its rules
seed and written as the `trivy-secret.yaml` each scan is given, and the
secret reference (``reference/custom_rules.py``) reading the merged
ruleset: the built-ins and the custom rules.

Set-up has a deadline (the traffic's ``setup_deadline_s``, from the start
of the process): past it the harness names the stage it was in and exits
1, so that a program that cannot build or compile the ruleset fails
rather than hangs.
"""

from __future__ import annotations

import os
import sys
import threading

from benchmark import common
from benchmark.harness.fs_cli import Checker, Scan, _compiles, _tree_files, judge, program_totals, run_scan
from benchmark.reference import custom_rules
from benchmark.traffic.rule_trees import rules_pool


class _NoLicenses:
    """The license side of ``Checker`` for a secret-only scan: no file has
    a license finding to compare."""

    def scan(self, rel: str, data: bytes):
        return None


class Deadline:
    """Exits the process with status 1, naming the stage, if set-up has
    not finished ``seconds`` after ``t_start``."""

    def __init__(self, seconds: float, t_start: float):
        self.stage = "start"
        self._done = threading.Event()
        remaining = max(0.0, seconds - (common.wall() - t_start))
        self._seconds = seconds
        self._thread = threading.Thread(target=self._watch, args=(remaining,), daemon=True)
        self._thread.start()

    def _watch(self, remaining: float) -> None:
        if self._done.wait(remaining):
            return
        print(f"benchmark: set-up passed its deadline of {self._seconds} s "
              f"in stage {self.stage!r}", file=sys.stderr, flush=True)
        os._exit(1)

    def enter(self, stage: str) -> None:
        self.stage = stage
        common.say("setup_stage", {"stage": stage})

    def done(self) -> None:
        self._done.set()


def ruleset(config: dict, traffic: dict, work: str) -> tuple[list, str, str]:
    """``(custom rules, config path, merged document path)``: the custom
    rules of the configuration (``traffic["custom_rules"]`` of them where a
    rehearsal cuts the count) written where the scans and the reference
    read them."""
    n = int(traffic.get("custom_rules", config["custom_rules"]))
    custom = custom_rules.generate(n, int(config["rules_seed"]))
    cfg_path = os.path.join(work, "trivy-secret.yaml")
    custom_rules.write_config(cfg_path, custom)
    merged_path = os.path.join(work, "merged_rules.json")
    import json

    with open(merged_path, "w") as f:
        json.dump(custom_rules.merged_document(custom), f)
    return custom, cfg_path, merged_path


def _custom_checked(checker: Checker) -> int:
    """Custom-rule findings among those the reference computed."""
    return sum(1 for want_s, _l in checker._want.values() for got in want_s.values()
               for f in got if f[0].startswith("org-"))


def run(bench: dict, cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        traced: bool, rehearse: bool, t_start: float, device: dict) -> tuple[dict, list]:
    if traffic["kind"] != "fs_rule_trees":
        raise common.Refused(f"fs_rules drives fs_rule_trees traffic, not {traffic['kind']!r}")
    deadline = Deadline(float(traffic["setup_deadline_s"]), t_start)
    deadline.enter("inputs")
    t0 = common.wall()
    work = common.fresh_dir(os.path.join(common.CACHE, "work", cell["name"]))
    custom, cfg_path, merged_path = ruleset(config, traffic, work)
    pool = rules_pool(common.inputs_dir(traffic), traffic, seed, custom)
    common.say("inputs", {"repos": len(pool), "bytes": [r["bytes"] for r in pool],
                          "files": sum(r["files"] for r in pool),
                          "custom_rules": len(custom),
                          "custom_planted": sum(r["custom_planted"] for r in pool),
                          "seconds": common.wall() - t0})
    args = list(config["command"]) + ["--secret-config", cfg_path]
    warm_s = []
    for w, repo in enumerate(pool):
        deadline.enter(f"warm-up scan of repo{w}")
        warm = run_scan(args, -1 - w, repo, w, work, traced)
        judge(warm)
        if warm.failure:
            raise RuntimeError(f"warm-up scan of repo{w} failed: {warm.failure}")
        warm_s.append(round(warm.seconds, 3))
    deadline.done()
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

    checker = Checker(seed, _NoLicenses())
    checker.secret_ref = custom_rules.RulesReference(merged_path)
    t_ref = common.wall()
    cmp = checker.compare(done, pool)
    custom_checked = _custom_checked(checker)
    common.say("reference", dict(cmp, custom_findings_checked=custom_checked,
                                 seconds=common.wall() - t_ref))
    checks = [("secret_mismatch", cmp["secret_mismatch"], 0),
              ("custom_findings_unchecked", 0 if custom_checked else 1, 0)]
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


def control(bench: dict, cell: dict, config: dict, traffic: dict, seed: int,
            device: dict) -> tuple[dict, list]:
    """The control in the program's place: the reference with its keyword
    test made case-sensitive (the content's ASCII fold dropped), over every
    repository of the seed's pool, judged by the comparison and the rule of
    ``run``."""
    work = common.fresh_dir(os.path.join(common.CACHE, "work", cell["name"] + ".control"))
    custom, _cfg, merged_path = ruleset(config, traffic, work)
    pool = rules_pool(common.inputs_dir(traffic), traffic, seed, custom)
    unfolded = custom_rules.RulesReference(merged_path, fold=False)
    scans = []
    for i, repo in enumerate(pool):
        secrets = {}
        for rel, full in _tree_files(repo["path"]).items():
            with open(full, "rb") as f:
                got = unfolded.scan(rel, f.read())
            if got:
                secrets[rel] = got
        sc = Scan(i, i, 0, 0.0, "", None, [])
        sc.report_findings = (secrets, {})
        scans.append(sc)
    checker = Checker(seed, _NoLicenses())
    checker.secret_ref = custom_rules.RulesReference(merged_path)
    cmp = checker.compare(scans, pool)
    custom_checked = _custom_checked(checker)
    common.say("control", dict(cmp, custom_findings_checked=custom_checked))
    checks = [("secret_mismatch", cmp["secret_mismatch"], 0),
              ("custom_findings_unchecked", 0 if custom_checked else 1, 0)]
    return {"correct": bool(scans) and common.correct(checks), "attempted": len(scans)}, checks
