"""Chunk-dedup hit cache + small-file row packing (ISSUE 2 tentpole).

Correctness contract: findings stay byte-identical to the CPU backend
whether a row was uploaded, served from the hit cache, coalesced onto an
identical in-flight row, or shared with other files via packing.

Scanners here run a RESTRICTED ruleset (two builtin rules) to keep device
compiles cheap — full-ruleset packing/dedup parity is already exercised by
test_tpu_scanner.py, whose small sample files ride packed rows.
"""

import io

import pytest

from tests.secret_samples import SAMPLES
from trivy_tpu.cache import new_cache
from trivy_tpu.secret.engine import ScannerConfig, SecretScanner
from trivy_tpu.secret.tpu_scanner import TpuSecretScanner

RESTRICTED = {"enable-builtin-rules": ["github-pat", "slack-access-token"]}


@pytest.fixture(scope="module")
def cpu():
    return SecretScanner(ScannerConfig.from_dict(RESTRICTED))


@pytest.fixture(scope="module")
def tpu():
    # small chunks force multi-chunk files; batch 8 forces partial batches
    return TpuSecretScanner(
        ScannerConfig.from_dict(RESTRICTED), chunk_len=2048, batch_size=8
    )


def dup_fixture():
    """A 'vendored' dir copied twice under different roots: small files
    exercise row packing, the multi-chunk file exercises chunk dedup."""
    small = [
        (f"pkg/h_{i}.h", (f"// header {i}\n" * 30).encode()) for i in range(8)
    ]
    small[2] = ("pkg/token.h", f"a\n{SAMPLES['github-pat']}\nb\n".encode())
    big = (
        (b"int x;\n" * 800)
        + SAMPLES["slack-access-token"].encode()
        + b"\n"
        + (b"int y;\n" * 400)
    )
    base = small + [("pkg/gen.c", big)]
    files = []
    for root in ("first", "second"):
        files.extend((f"{root}/{p}", d) for p, d in base)
    files.append(("unique.txt", b"nothing secret\n" * 40))
    return files


def assert_parity(cpu, scanner, files):
    got = list(scanner.scan_files(files))
    assert len(got) == len(files)
    for (path, data), secret in zip(files, got):
        assert secret.to_dict() == cpu.scan_bytes(path, data).to_dict(), path
    return got


def test_packed_row_parity_duplicate_fixture(cpu, tpu):
    before = tpu.stats.snapshot()
    got = assert_parity(cpu, tpu, dup_fixture())
    d = {k: v - before[k] for k, v in tpu.stats.snapshot().items()}
    assert d["rows_packed"] > 0 and d["files_packed"] > 1
    assert d["chunks_dedup_hit"] > 0  # second copy's big-file chunks
    assert sum(len(s.findings) for s in got) == 4  # 2 secrets x 2 copies


def test_dedup_warm_scan_uploads_nothing(cpu, tpu):
    files = dup_fixture()
    list(tpu.scan_files(files))  # warm the hit cache
    before = tpu.stats.snapshot()
    assert_parity(cpu, tpu, files)
    after = tpu.stats.snapshot()
    assert after["chunks_uploaded"] - before["chunks_uploaded"] == 0
    assert after["bytes_uploaded"] - before["bytes_uploaded"] == 0


def test_ruleset_fingerprint_invalidation():
    base = dict(
        RESTRICTED,
        rules=[
            {"id": "r1", "regex": r"tok_[0-9a-f]{12}", "keywords": ["tok_"],
             "severity": "HIGH"},
        ],
    )
    plus = dict(
        RESTRICTED,
        rules=base["rules"] + [
            {"id": "r2", "regex": r"sec_[0-9a-f]{12}", "keywords": ["sec_"],
             "severity": "HIGH"},
        ],
    )
    minus = dict(base, **{"disable-rules": ["github-pat"]})
    def build(cfg, **kw):
        return TpuSecretScanner(
            ScannerConfig.from_dict(cfg), chunk_len=1024, batch_size=4, **kw
        )

    a, b, c, d = build(base), build(plus), build(base), build(minus)
    e = TpuSecretScanner(
        ScannerConfig.from_dict(base), chunk_len=2048, batch_size=4
    )
    assert a.ruleset_fingerprint != b.ruleset_fingerprint  # rule added
    assert a.ruleset_fingerprint == c.ruleset_fingerprint  # same ruleset
    assert a.ruleset_fingerprint != d.ruleset_fingerprint  # rule removed
    assert a.ruleset_fingerprint != e.ruleset_fingerprint  # row shape differs
    # the keyword table rides the fingerprint: editing a rule's KEYWORDS
    # alone (same id/regex order would once have collided in the keyword
    # table) must flip every dedup key
    kw_edit = dict(
        RESTRICTED,
        rules=[
            {"id": "r1", "regex": r"tok_[0-9a-f]{12}",
             "keywords": ["tok_", "Tok2_"], "severity": "HIGH"},
        ],
    )
    g = build(kw_edit)
    assert a.ruleset_fingerprint != g.ruleset_fingerprint
    # and the prefilter table digest itself sees the keyword edit (ascii
    # fold applied): same table -> same digest, edited table -> new digest
    assert (
        a.compiled.prefilter_fingerprint()
        == c.compiled.prefilter_fingerprint()
    )
    assert (
        a.compiled.prefilter_fingerprint()
        != g.compiled.prefilter_fingerprint()
    )


def test_persisted_cache_isolated_by_fingerprint():
    """A persisted hit-vector store shared between scanners with different
    rulesets must never cross-serve entries (rule indices differ)."""
    shared = new_cache("memory")
    with_rule = dict(
        RESTRICTED,
        rules=[
            {"id": "zzz-token", "regex": r"zzz_[0-9a-f]{8}",
             "keywords": ["zzz_"], "severity": "HIGH"},
        ],
    )
    files = [("src/t.txt", b"x zzz_0123abcd y\n" + b"pad\n" * 40)]
    a = TpuSecretScanner(
        ScannerConfig.from_dict(with_rule), chunk_len=1024, batch_size=4,
        hit_cache=shared,
    )
    got_a = list(a.scan_files(files))
    assert any(f.rule_id == "zzz-token" for f in got_a[0].findings)
    # same persisted store, ruleset WITHOUT the rule: must miss (upload)
    # and stay byte-identical to its own CPU oracle
    without = SecretScanner(ScannerConfig.from_dict(RESTRICTED))
    b = TpuSecretScanner(
        ScannerConfig.from_dict(RESTRICTED), chunk_len=1024, batch_size=4,
        hit_cache=shared,
    )
    got_b = assert_parity(without, b, files)
    assert not got_b[0].findings
    assert b.stats.snapshot()["chunks_dedup_hit"] == 0
    # a second scanner with b's ruleset DOES reuse b's persisted vectors
    c = TpuSecretScanner(
        ScannerConfig.from_dict(RESTRICTED), chunk_len=1024, batch_size=4,
        hit_cache=shared,
    )
    assert_parity(without, c, files)
    s = c.stats.snapshot()
    assert s["chunks_uploaded"] == 0 and s["chunks_dedup_hit"] > 0


def test_lone_small_file_does_not_stall_emission(tpu):
    """A lone packed small file must resolve within ~one batch of big-file
    traffic, not at end-of-input: its unresolved state would stall in-order
    emission and grow the results backlog on a streaming scan."""
    consumed = []
    big = b"filler line\n" * 2000  # multi-chunk at chunk_len=2048

    def gen():
        yield ("src/tiny.cfg", b"just a small file\n")
        for i in range(64):
            consumed.append(i)
            yield (f"src/big_{i}.dat", big + str(i).encode())

    it = tpu.scan_files(gen())
    first = next(it)
    assert first.file_path == "src/tiny.cfg"
    assert len(consumed) < 64  # resolved mid-stream, not at final drain
    it.close()


def test_generator_close_early_with_cache(cpu, tpu):
    files = dup_fixture()
    it = tpu.scan_files(iter(files))
    first = next(it)
    it.close()  # device thread must shut down cleanly mid-scan
    assert first.to_dict() == cpu.scan_bytes(*files[0]).to_dict()
    # scanner (and its populated hit cache) must keep working afterwards
    assert_parity(cpu, tpu, files)


def test_empty_file_skips_device(cpu, tpu):
    files = [("e.txt", b""), ("f.txt", b"hello world, nothing secret\n")]
    before = tpu.stats.snapshot()["chunks"]
    assert_parity(cpu, tpu, files)
    assert tpu.stats.snapshot()["chunks"] - before == 1  # only f.txt fed


def test_dedup_disabled_still_parity(cpu):
    t = TpuSecretScanner(
        ScannerConfig.from_dict(RESTRICTED), chunk_len=2048, batch_size=8,
        dedup=False, pack_small=False,
    )
    assert_parity(cpu, t, dup_fixture())
    s = t.stats.snapshot()
    assert s["chunks_dedup_hit"] == 0 and s["rows_packed"] == 0
    assert s["chunks_uploaded"] == s["chunks"]


def test_trace_counters_surface_in_report(tpu):
    from trivy_tpu import obs

    with obs.scan_context(name="dedup-test", enabled=True) as ctx:
        # identical multi-chunk files: the second's rows dedup/coalesce
        files = [
            ("src/a.txt", b"plain text content\n" * 400),
            ("src/b.txt", b"plain text content\n" * 400),
        ]
        list(tpu.scan_files(files))
        out = io.StringIO()
        ctx.report(out)
    text = out.getvalue()
    assert "secret.bytes_uploaded" in text
    assert "secret.bytes_dedup_hit" in text
