"""The ``custom_rules.5k`` cell on the CPU: its rehearsal through the
benchmark's entry (at the traffic file's rehearsal sizes: fewer custom
rules), the rule generator's determinism, the control, and a fault in the
program's device tables that ``correct`` must catch."""

import json
from dataclasses import replace

import pytest

from benchmark import common, run
from benchmark.reference import custom_rules

SEED = 2147500101


@pytest.fixture(autouse=True)
def cache_in_tmp(tmp_path_factory, monkeypatch):
    monkeypatch.setattr(common, "CACHE", str(tmp_path_factory.getbasetemp() / "bench-cache"))


@pytest.fixture(autouse=True)
def restore_program_logging():
    import logging

    from trivy_tpu import log

    root = logging.getLogger("trivy_tpu")
    saved = (list(root.handlers), root.level, root.propagate, log._configured)
    yield
    root.handlers[:] = saved[0]
    root.setLevel(saved[1])
    root.propagate = saved[2]
    log._configured = saved[3]


def _rehearse(capsys, *extra):
    rc = run.main(["--workload", "custom_rules.5k", "--seed", str(SEED), "--seconds", "0.2",
                   "--rehearse", *extra])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-5:]
    prefix = "rehearsal (CPU, not a chip result): "
    assert out[-1].startswith(prefix)
    said = {ln.split(": ", 1)[0]: ln.split(": ", 1)[1] for ln in out if ": " in ln}
    return json.loads(out[-1][len(prefix):]), said


def test_custom_rules_rehearsal(capsys):
    body, said = _rehearse(capsys)
    assert body["correct"] is True
    assert body["attempted"] > 0 and body["failed"] == 0
    assert "setup_s" in body["metrics"] and "fs_scan_mb_per_s" in body["metrics"]
    ref = json.loads(said["reference"])
    assert ref["custom_findings_checked"] > 0 and ref["secret_mismatch"] == 0
    inputs = json.loads(said["inputs"])
    assert inputs["custom_rules"] == 400 and inputs["custom_planted"] > 0


@pytest.mark.parametrize("seed", [5113, 1, 2147483647 + 99])
def test_generator_is_deterministic(seed):
    a = custom_rules.generate(500, seed)
    b = custom_rules.generate(500, seed)
    assert [r.doc for r in a] == [r.doc for r in b]
    assert len({r.id for r in a}) == 500
    other = custom_rules.generate(500, seed + 1)
    assert [r.doc["regex"] for r in a] != [r.doc["regex"] for r in other]
    shapes = {r.id.split("-")[1] for r in a}
    assert shapes == {"vendor", "assign", "multi", "pem", "bare"}


def test_generated_secrets_are_found_by_the_reference(tmp_path):
    rules = custom_rules.generate(200, 7)
    path = tmp_path / "merged.json"
    path.write_text(json.dumps(custom_rules.merged_document(rules)))
    ref = custom_rules.RulesReference(str(path))
    import random

    rng = random.Random(1)
    for rule in rules:
        data = ("x = 1\n" + rule.make_secret(rng) + "\n").encode()
        got = {f[0] for f in ref.scan("src/app/main.py", data)}
        assert rule.id in got, rule.doc["regex"]


def test_custom_rules_control_fails(capsys):
    assert run.main(["--workload", "custom_rules.5k", "--seed", str(SEED), "--rehearse",
                     "--control"]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    line = json.loads(last[len("control: "):])
    assert line["correct"] is False
    assert line["checks"]["secret_mismatch"]["value"] > 0


def _planted_custom_rule(tmp_path) -> str:
    """A custom rule whose secret the seed's rehearsal pool holds: the
    pool made again, its planted files read by the reference."""
    from benchmark.harness import fs_rules
    from benchmark.traffic.rule_trees import rules_pool

    bench, cell, config, traffic = common.load_cell("custom_rules.5k")
    traffic = dict(traffic, name=cell["traffic"])
    traffic.update(traffic["rehearsal"])
    custom, _cfg, merged = fs_rules.ruleset(config, traffic, str(tmp_path))
    pool = rules_pool(str(tmp_path / "pool"), traffic, SEED, custom)
    ref = custom_rules.RulesReference(merged)
    for repo in pool:
        for rel in repo["planted"]:
            with open(f"{repo['path']}/{rel}", "rb") as f:
                for finding in ref.scan(rel, f.read()):
                    if finding[0].startswith("org-"):
                        return finding[0]
    raise AssertionError("no custom secret planted in the rehearsal pool")


def test_literal_left_out_of_device_table_fails_correct(capsys, monkeypatch, tmp_path):
    """One planted custom rule's literals dropped from the device tables:
    the rule never reaches the host confirm, its finding goes missing."""
    from trivy_tpu.fanal.analyzers import secret as secret_analyzer
    from trivy_tpu.secret import tpu_scanner

    # a scanner built by an earlier test for the same ruleset is not reused
    monkeypatch.setattr(secret_analyzer, "_scanner_cache", {})
    target = _planted_custom_rule(tmp_path)
    orig = tpu_scanner.build_tables

    def without_target(compiled, **kw):
        r = compiled.rule_ids.index(target)
        return orig(replace(
            compiled,
            variants=[(i, v) for i, v in compiled.variants if i != r],
            keywords=[(i, k) for i, k in compiled.keywords if i != r],
            prefilter_keywords=[(i, k) for i, k in compiled.prefilter_keywords if i != r],
        ), **kw)

    monkeypatch.setattr(tpu_scanner, "build_tables", without_target)
    body, _said = _rehearse(capsys)
    assert body["correct"] is False
    assert body["checks"]["secret_mismatch"]["value"] > 0


def test_setup_deadline_names_the_stage(capsys, monkeypatch):
    """Set-up past its deadline exits 1 and names the stage it was in."""
    from benchmark.harness import fs_rules

    exits = []
    monkeypatch.setattr(fs_rules.os, "_exit", exits.append)
    d = fs_rules.Deadline(0.05, common.wall())
    d.enter("warm-up scan of repo0")
    d._thread.join(5)
    assert exits == [1]
    assert "warm-up scan of repo0" in capsys.readouterr().err
