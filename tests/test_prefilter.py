"""Fused device pass: on-device keyword prefilter (ISSUE 9 tentpole).

Correctness contract: findings are byte-identical to the exact CPU engine
— across dedup + packing + the single- and multi-stream async feed, the
8-device mesh, and the degraded host-fallback path. The
prefilter's whole-file candidate semantics mirror the reference's
MatchKeywords (keyword anywhere in the file), so a rule whose keyword and
match sit in different chunks — or different batches — must still confirm.
"""

import numpy as np
import pytest

from tests.secret_samples import SAMPLES
from trivy_tpu import faults
from trivy_tpu.secret.engine import ScannerConfig, SecretScanner
from trivy_tpu.secret.tpu_scanner import TpuSecretScanner

RESTRICTED = {"enable-builtin-rules": ["github-pat", "slack-access-token"]}


@pytest.fixture(scope="module")
def cpu():
    return SecretScanner(ScannerConfig.from_dict(RESTRICTED))


def build(**kw):
    kw.setdefault("chunk_len", 2048)
    kw.setdefault("batch_size", 8)
    return TpuSecretScanner(ScannerConfig.from_dict(RESTRICTED), **kw)


def mixed_corpus():
    """Lures (no keywords anywhere), planted secrets, mixed-case keyword
    bytes, packed small files, and multi-chunk files."""
    files = [
        (f"lure_{i}.txt", b"plain text, no token-shaped bytes at all\n" * 80)
        for i in range(6)
    ]
    files.append(("gh.txt", f"x\n{SAMPLES['github-pat']}\ny\n".encode()))
    files.append(
        (
            "slack.c",
            (b"int x;\n" * 700)
            + SAMPLES["slack-access-token"].encode()
            + b"\n"
            + (b"int y;\n" * 500),
        )
    )
    # mixed-case keyword with no real secret: prefilter must still flag
    # (case-fold parity) and the exact confirm must still reject
    files.append(("upper.txt", b"SEE GHP_NOT_A_REAL_TOKEN HERE\n" * 40))
    files += [
        (f"small_{i}.cfg", f"tiny file {i}\n".encode()) for i in range(5)
    ]
    return files


def assert_parity(cpu, scanner, files, **scan_kw):
    got = list(scanner.scan_files(iter(files), **scan_kw))
    assert len(got) == len(files)
    for (path, data), secret in zip(files, got):
        assert secret.to_dict() == cpu.scan_bytes(path, data).to_dict(), path
    return got


def test_prefilter_parity_mixed_corpus(cpu):
    files = mixed_corpus()
    multi = build(feed_streams=3, inflight=2)
    single = build(feed_streams=1, inflight=1)
    got_multi = assert_parity(cpu, multi, files)
    got_single = assert_parity(cpu, single, files)
    assert [s.to_dict() for s in got_multi] == [s.to_dict() for s in got_single]
    for scanner in (multi, single):
        s = scanner.stats.snapshot()
        assert s["rows_prefiltered"] > 0
        assert 0 < s["rows_prefilter_hit"] < s["rows_prefiltered"]


def test_lure_corpus_skips_nfa_dispatch(cpu):
    scanner = build()
    files = [
        (f"l{i}.txt", b"boring bytes without any rule keyword\n" * 100)
        for i in range(8)
    ]
    got = assert_parity(cpu, scanner, files)
    assert all(not s.findings for s in got)
    # the one rule pass sees every lure row and flags no rule in any
    s = scanner.stats.snapshot()
    assert s["rows_prefiltered"] > 0
    assert s["rows_prefilter_hit"] == 0


def test_keyword_and_match_in_different_chunks(cpu):
    """Whole-file MatchKeywords semantics: an anchored+keyword rule whose
    keyword sits thousands of bytes (and possibly several batches) away
    from its regex match must still produce the finding — via the
    unchecked/full-scan confirm rung — and a keywordless twin of the match
    must stay suppressed."""
    cfg = {
        "enable-builtin-rules": [],
        "rules": [
            {"id": "far-kw", "regex": r"zqt_[0-9a-f]{10}",
             "keywords": ["farmarkerkw"], "severity": "HIGH"},
        ],
    }
    host = SecretScanner(ScannerConfig.from_dict(cfg))
    dev = TpuSecretScanner(
        ScannerConfig.from_dict(cfg), chunk_len=1024, batch_size=4
    )
    data = (
        b"x zqt_0123456789 x\n"
        + b"filler line of text\n" * 600
        + b"here is farmarkerkw ok\n"
        + b"tail\n" * 200
    )
    files = [
        ("far.txt", data),
        # anchored pattern present but keyword absent: the device kernel
        # may flag it, the candidate gate must drop the confirm
        ("nokw.txt", b"x zqt_aaaabbbbcc x\n" + b"pad\n" * 800),
    ]
    got = assert_parity(host, dev, files)
    assert len(got[0].findings) == 1
    assert not got[1].findings


def test_case_fold_parity_mixed_case():
    """Device prefilter and host pre-lowering must share the byte A-Z
    fold: mixed-case keyword occurrences gate identically, and non-ASCII
    letters are NOT folded on either side."""
    from trivy_tpu.secret.rules import ascii_lower, ascii_lower_any

    assert ascii_lower("GHP_Token") == "ghp_token"
    assert ascii_lower("\xc0caf\xe9") == "\xc0caf\xe9"  # 'À'/'é' untouched
    assert ascii_lower_any("TokenX") == "tokenx"
    cfg = {
        "enable-builtin-rules": [],
        "rules": [
            {"id": "cased", "regex": r"MiXtOk[0-9]{6}",
             "keywords": ["MiXtOk"], "severity": "HIGH"},
        ],
    }
    host = SecretScanner(ScannerConfig.from_dict(cfg))
    dev = TpuSecretScanner(
        ScannerConfig.from_dict(cfg), chunk_len=1024, batch_size=4
    )
    files = [
        ("a.txt", b"x MiXtOk123456 y\n" + b"pad\n" * 400),
        ("b.txt", b"x MIXTOK999999 y\n" + b"pad\n" * 400),  # kw matches,
        # regex (case-sensitive) does not: candidate but zero findings
        ("c.txt", b"x mixtok highlighted but no digits\n" + b"pad\n" * 400),
    ]
    got = assert_parity(host, dev, files)
    assert len(got[0].findings) == 1
    assert not got[1].findings and not got[2].findings


def test_prefilter_parity_8_device_mesh(cpu):
    from trivy_tpu.parallel.mesh import get_mesh

    mesh = get_mesh(8)
    dev = TpuSecretScanner(
        ScannerConfig.from_dict(RESTRICTED),
        chunk_len=1024, batch_size=16, mesh=mesh,
    )
    assert dev.compiled.prefilter_keywords
    files = mixed_corpus()
    assert_parity(cpu, dev, files)
    assert dev.stats.snapshot()["rows_prefiltered"] > 0


def test_degraded_host_fallback_with_prefilter(cpu):
    """Device dies mid-scan: prefilter-skipped rows (and every other
    unresolved file) must confirm identically on the exact host path."""
    # dedup off: duplicate rows would collapse to too few dispatches for
    # the scripted Nth-hit fault to land on live traffic
    scanner = build(feed_streams=2, inflight=2, dedup=False)
    files = mixed_corpus() * 2
    faults.configure("device.dispatch:at=3:times=-1")
    try:
        got = list(scanner.scan_files(iter(files)))
    finally:
        faults.clear()
    assert len(got) == len(files)
    for (path, data), secret in zip(files, got):
        assert secret.to_dict() == cpu.scan_bytes(path, data).to_dict(), path
    assert scanner.stats.snapshot()["degraded"] >= 1


def test_dedup_replay_preserves_prefilter_verdicts(cpu):
    """Warm-cache re-scan: cached row verdicts carry candidate masks and
    the nfa_ran flag, so replayed rows confirm identically with zero
    uploads."""
    scanner = build()
    files = mixed_corpus()
    list(scanner.scan_files(iter(files)))  # warm the verdict cache
    before = scanner.stats.snapshot()
    assert_parity(cpu, scanner, files)
    after = scanner.stats.snapshot()
    assert after["chunks_uploaded"] == before["chunks_uploaded"]
    assert after["bytes_uploaded"] == before["bytes_uploaded"]


def test_profile_records_prefilter_attribution():
    from trivy_tpu import obs

    scanner = build()
    files = mixed_corpus()
    with obs.scan_context(name="prefilter-test", enabled=True) as ctx:
        list(scanner.scan_files(iter(files)))
    doc = ctx.profile().to_dict()
    pre = doc.get("prefilter")
    assert pre and pre["rows"] > 0
    assert 0.0 < pre["selectivity"] < 1.0
    # the planted github-pat rule must attribute prefilter candidates
    gh = doc["rules"].get("github-pat")
    assert gh and gh["prefilter_hits"] > 0
    assert 0.0 < gh["prefilter_selectivity"] <= 1.0


def test_prefilter_stage_span_recorded():
    from trivy_tpu import obs

    scanner = build()
    with obs.scan_context(name="prefilter-span", enabled=True) as ctx:
        list(scanner.scan_files(iter(mixed_corpus())))
    recorded = {name for name, durs in ctx.snapshot().items() if durs}
    # the rule pass's fetch: keyword candidates and anchored hits together
    assert "secret.device_wait" in recorded
