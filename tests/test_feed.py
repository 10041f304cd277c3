"""Async zero-copy feed path (ISSUE 7 tentpole).

Covers the pipeline rebuild of ``TpuSecretScanner.scan_files``: arena-slab
reuse with findings parity (packing + dedup on), in-order emission under a
deliberately slow reader, fault injection with the async in-flight window
live, the empty/partial-final-slab guard (padding rows must not leak into
dedup keys or retain arena slabs), the walk→device streaming handoff
(:class:`trivy_tpu.secret.feed.FileStream`), and the raw-slab upload
accounting (``secret.bytes_uploaded``) across dispatch, packing, the fused
license gate and the dedup stores.

Scanners here run a RESTRICTED ruleset (two builtin rules) to keep device
compiles cheap — full-ruleset feed parity is already exercised by
test_tpu_scanner.py through the same pipeline.
"""

import threading
import time

import jax
import numpy as np
import pytest

from tests.secret_samples import SAMPLES
from trivy_tpu import faults, obs
from trivy_tpu.cache import new_cache
from trivy_tpu.licensing.fused import FusedLicenseGate
from trivy_tpu.secret.engine import ScannerConfig, SecretScanner
from trivy_tpu.secret.feed import ChunkArena, FileStream
from trivy_tpu.secret.tpu_scanner import TpuSecretScanner

RESTRICTED = {"enable-builtin-rules": ["github-pat", "slack-access-token"]}


@pytest.fixture(scope="module")
def cfg():
    return ScannerConfig.from_dict(RESTRICTED)


@pytest.fixture(scope="module")
def cpu(cfg):
    return SecretScanner(cfg)


@pytest.fixture(autouse=True)
def _clear_faults():
    faults.clear()
    yield
    faults.clear()


def corpus(n_big=12, n_small=6):
    """Multi-chunk files + packable small files, secrets sprinkled in."""
    rng = np.random.default_rng(11)
    files = []
    for i in range(n_big):
        pad = rng.integers(97, 123, size=6000, dtype=np.uint8).tobytes()
        body = pad
        if i % 3 == 0:
            body = SAMPLES["github-pat"].encode() + b"\n" + pad
        files.append((f"big_{i}.txt", body))
    for i in range(n_small):
        files.append((f"small_{i}.h", f"// header {i}\n".encode() * 20))
    files.append(("tok.h", f"a\n{SAMPLES['slack-access-token']}\nb\n".encode()))
    return files


def mixed_corpus(n=24, seed=7):
    """Run-heavy printable text, random printable noise, zero pages and
    binary blobs — with secrets sprinkled in."""
    rng = np.random.default_rng(seed)
    files = []
    for i in range(n):
        kind = i % 4
        if kind == 0:  # run-heavy printable text with a secret
            rid = ("github-pat", "slack-access-token")[i % 2]
            body = (
                b"#" * 120 + b"\n"
                + SAMPLES[rid].encode() + b"\n"
                + b"the rate that the land sent was on and in their line\n" * 60
            )
        elif kind == 1:  # random printable noise
            body = rng.integers(0x20, 0x7F, size=5000, dtype=np.uint8).tobytes()
        elif kind == 2:  # zero page + trailing text
            body = b"\x00" * 4096 + b"tail text after the hole\n"
        else:  # binary (top-bit set)
            body = rng.integers(0, 256, size=3000, dtype=np.uint8).tobytes()
            body = body.replace(b"\x00", b"\x01")
        files.append((f"f{i}.dat", body))
    return files


LICENSE_TEXT = (
    b"Permission is hereby granted, free of charge, to any person "
    b"obtaining a copy of this software and associated documentation "
    b"files, to deal in the Software without restriction.\n" * 4
)


def assert_parity(cpu, scanner, files):
    got = list(scanner.scan_files(files))
    assert len(got) == len(files)
    for (path, data), secret in zip(files, got):
        assert secret.to_dict() == cpu.scan_bytes(path, data).to_dict(), path
    return got


# -- arena ------------------------------------------------------------------


def test_arena_reuse_parity(cfg, cpu):
    """Far more batches than arena slabs: every slab is recycled many
    times, findings stay byte-identical (pack + dedup on), and after the
    scan every slab is back in the free list — an arena leak would walk
    straight into the streaming-RSS gate."""
    scanner = TpuSecretScanner(
        cfg, chunk_len=1024, batch_size=4, feed_streams=2, inflight=2
    )
    assert_parity(cpu, scanner, corpus())
    st = scanner._last_feed_stats
    assert st["arena_free"] == st["arena_slabs"]  # nothing retained
    assert st["arena_acquires"] > st["arena_slabs"]  # slabs were reused
    assert st["streams"] == 2


def test_arena_acquire_release_contract():
    a = ChunkArena(2, rows=4, row_len=16)
    i0, s0 = a.acquire()
    i1, s1 = a.acquire()
    assert {i0, i1} == {0, 1} and s0.shape == (4, 16)
    # exhausted arena + abort predicate: returns None instead of hanging
    assert a.acquire(abort=lambda: True, poll=0.01) is None
    a.release(i0)
    assert a.acquire()[0] == i0
    with pytest.raises(ValueError):
        a.release(i1)  # still held is fine ...
        a.release(i1)  # ... double release is not


def test_partial_final_slab_no_padding_leak(cfg, cpu):
    """A final partial slab is bucket-padded with stale rows; those
    padding rows must not acquire dedup keys (satellite fix). Every live
    row's digest — and ONLY live rows' digests — lands in the hit LRU."""
    chunk = 1024
    scanner = TpuSecretScanner(
        cfg, chunk_len=chunk, batch_size=4, pack_small=False,
        feed_streams=1, inflight=1,
    )
    rng = np.random.default_rng(5)
    # 6 one-row files -> one full batch of 4 + a partial batch of 2
    files = [
        (f"f{i}.bin", rng.integers(32, 127, chunk, np.uint8).tobytes())
        for i in range(6)
    ]
    assert_parity(cpu, scanner, files)
    s = scanner.stats.snapshot()
    assert s["chunks"] == 6 and s["chunks_uploaded"] == 6
    # exactly the 6 live rows were hashed into the dedup cache — a leak of
    # the 2 stale padding rows of the final slab would add extra entries
    assert len(scanner._hit_lru) == 6
    assert scanner._last_feed_stats["arena_free"] == (
        scanner._last_feed_stats["arena_slabs"]
    )


def test_empty_final_slab_never_dispatched(cfg, cpu):
    """Input an exact multiple of the batch size: the trailing slab holds
    zero live rows and must not be dispatched (no padding-only upload)."""
    chunk = 1024
    scanner = TpuSecretScanner(
        cfg, chunk_len=chunk, batch_size=4, pack_small=False,
        feed_streams=1, inflight=1,
    )
    rng = np.random.default_rng(6)
    files = [
        (f"g{i}.bin", rng.integers(32, 127, chunk, np.uint8).tobytes())
        for i in range(4)
    ]
    assert_parity(cpu, scanner, files)
    s = scanner.stats.snapshot()
    assert s["bytes_uploaded"] == 4 * chunk  # one bucket, no empty batch
    assert scanner._last_feed_stats["arena_acquires"] == 1


# -- emission order ---------------------------------------------------------


def test_inorder_emission_slow_reader(cfg, cpu):
    """A deliberately slow reader (the input trickles in) must not break
    in-order emission or parity — the feeder consumes the iterable on its
    own thread and the reorder buffer holds completions."""
    scanner = TpuSecretScanner(
        cfg, chunk_len=1024, batch_size=4, feed_streams=2, inflight=2
    )
    files = corpus(n_big=8, n_small=4)

    def slow():
        for f in files:
            time.sleep(0.005)
            yield f

    got = list(scanner.scan_files(slow()))
    assert [s.file_path for s in got] == [p for p, _ in files]
    for (path, data), secret in zip(files, got):
        assert secret.to_dict() == cpu.scan_bytes(path, data).to_dict(), path


def test_slow_consumer_does_not_stall_feeder(cfg):
    """The generator's consumer sleeping on a head-of-line result must not
    stop the feeder: by the time the slow first next() returns, the
    pipeline should have progressed well past the first file."""
    scanner = TpuSecretScanner(
        cfg, chunk_len=1024, batch_size=4, feed_streams=2, inflight=2
    )
    consumed = []
    files = corpus(n_big=10, n_small=2)

    def tracking():
        for i, f in enumerate(files):
            consumed.append(i)
            yield f

    it = scanner.scan_files(tracking())
    first = next(it)
    time.sleep(0.3)  # consumer dawdles; feeder keeps running
    assert len(consumed) == len(files)  # fully ingested despite no next()
    rest = list(it)
    assert first.file_path == files[0][0]
    assert len(rest) == len(files) - 1
    assert scanner._last_feed_stats["arena_free"] == (
        scanner._last_feed_stats["arena_slabs"]
    )


# -- faults with the async window in flight ---------------------------------


def test_dispatch_fault_recovers_with_async_window(cfg, cpu):
    scanner = TpuSecretScanner(
        cfg, chunk_len=1024, batch_size=4, feed_streams=2, inflight=2
    )
    s0 = scanner.stats.snapshot()
    faults.configure("device.dispatch:at=2")
    assert_parity(cpu, scanner, corpus())
    s1 = scanner.stats.snapshot()
    assert s1["batch_retries"] - s0["batch_retries"] >= 1
    assert s1["degraded"] == s0["degraded"]


def test_oom_split_with_async_window(cfg, cpu):
    scanner = TpuSecretScanner(
        cfg, chunk_len=1024, batch_size=8, feed_streams=2, inflight=2
    )
    faults.configure("device.dispatch:at=1:error=oom")
    assert_parity(cpu, scanner, corpus())
    assert scanner.stats.snapshot()["batch_splits"] >= 1


def test_permanent_fault_degrades_mid_stream(cfg, cpu):
    """Device dies while the async window is full and the input is half
    read: every file still emits, in order, byte-identical (host
    fallback), and the arena comes back whole."""
    scanner = TpuSecretScanner(
        cfg, chunk_len=1024, batch_size=4, feed_streams=2, inflight=2
    )
    files = corpus(n_big=16, n_small=4)
    faults.configure("device.dispatch:at=3:times=-1")
    got = list(scanner.scan_files(iter(files)))
    assert len(got) == len(files)
    for (path, data), secret in zip(files, got):
        assert secret.to_dict() == cpu.scan_bytes(path, data).to_dict(), path
    assert scanner.stats.snapshot()["degraded"] >= 1
    assert scanner._last_feed_stats["arena_free"] == (
        scanner._last_feed_stats["arena_slabs"]
    )


def test_input_iterator_error_propagates(cfg):
    """An exception thrown by the input iterable (a dying reader) must
    surface to the consumer, not vanish behind a truncated-but-"complete"
    file count."""
    scanner = TpuSecretScanner(
        cfg, chunk_len=1024, batch_size=4, feed_streams=2, inflight=2
    )

    def bad():
        yield ("a.txt", b"x" * 3000)
        raise OSError("reader blew up")

    with pytest.raises(OSError, match="reader blew up"):
        list(scanner.scan_files(bad()))


def test_no_host_fallback_raises_through_pipeline(cfg):
    scanner = TpuSecretScanner(
        cfg, chunk_len=1024, batch_size=4, host_fallback=False,
        feed_streams=2, inflight=2,
    )
    faults.configure("device.dispatch:times=-1")
    with pytest.raises(faults.InjectedFault):
        list(scanner.scan_files(corpus()))


# -- FileStream (walk → device handoff) -------------------------------------


def test_file_stream_round_trip_and_backpressure():
    stream = FileStream(max_bytes=64)  # tiny budget: forces backpressure
    items = [(f"f{i}", bytes([65 + i]) * 40) for i in range(8)]
    got = []
    consumer = threading.Thread(
        target=lambda: got.extend(stream), daemon=True
    )
    consumer.start()
    for p, d in items:
        stream.put(p, d)  # blocks whenever >64 bytes are queued
    stream.close()
    consumer.join(timeout=10)
    assert got == items


def test_file_stream_fail_unblocks_producer():
    stream = FileStream(max_bytes=16)
    stream.put("a", b"x" * 16)  # budget now full
    boom = RuntimeError("scan thread died")

    def poison():
        time.sleep(0.05)
        stream.fail(boom)

    threading.Thread(target=poison, daemon=True).start()
    with pytest.raises(RuntimeError, match="scan thread died"):
        stream.put("b", b"y" * 16)  # would block forever without fail()


def test_streaming_analyzer_parity(cfg, cpu, tmp_path):
    """The analyzer's streaming handoff (collect → FileStream → background
    scan_files) yields the same findings as scanning the bytes directly."""
    from trivy_tpu import obs
    from trivy_tpu.fanal.analyzers.secret import _StreamScan

    scanner = TpuSecretScanner(
        cfg, chunk_len=1024, batch_size=4, feed_streams=2, inflight=2
    )
    files = corpus(n_big=6, n_small=3)
    scan = _StreamScan(scanner, obs.current())
    for p, d in files:
        scan.put(p, d)
    found = scan.finish()
    want = {
        p: cpu.scan_bytes(p, d).to_dict()
        for p, d in files
        if cpu.scan_bytes(p, d).findings
    }
    assert {s.file_path: s.to_dict() for s in found} == want


def test_no_fallback_analyzer_failure_is_loud(cfg, tmp_path, monkeypatch):
    """--no-host-fallback through the ANALYZER surface: the device failure
    must fail the artifact scan (FatalAnalyzerError re-raised by the
    group's containment layers), not degrade into a warning plus a
    'clean' report with every finding dropped."""
    from trivy_tpu.artifact.local_fs import ArtifactOption, LocalFSArtifact
    from trivy_tpu.cache import new_cache
    from trivy_tpu.fanal.analyzers import secret as secret_analyzer

    (tmp_path / "cred.txt").write_text(
        f"token {SAMPLES['github-pat']}\n" + "pad\n" * 400
    )
    monkeypatch.setattr(secret_analyzer, "_scanner_cache", {})
    faults.configure("device.dispatch:times=-1")
    opt = ArtifactOption(analyzer_extra={
        "host_fallback": False, "secret_streams": 2, "secret_inflight": 2,
    })
    art = LocalFSArtifact(str(tmp_path), new_cache("memory"), opt)
    with pytest.raises(faults.InjectedFault):
        art.inspect()


def test_streaming_analyzer_abort_releases_pipeline(cfg):
    """A walk that dies mid-scan aborts the streaming scan: the consumer
    thread exits and every arena slab returns (no leak into a long-lived
    server process)."""
    from trivy_tpu import obs
    from trivy_tpu.fanal.analyzers.secret import _StreamScan

    scanner = TpuSecretScanner(
        cfg, chunk_len=1024, batch_size=4, feed_streams=2, inflight=2
    )
    scan = _StreamScan(scanner, obs.current())
    for p, d in corpus(n_big=4, n_small=2):
        scan.put(p, d)
    scan.abort()
    assert not scan.thread.is_alive()
    assert scan.found == []
    st = scanner._last_feed_stats
    assert st["arena_free"] == st["arena_slabs"]
    # the scanner stays usable for the next scan
    assert list(scanner.scan_files([("ok.txt", b"clean enough\n" * 10)]))


# -- knobs ------------------------------------------------------------------


def test_feed_knobs_resolve(cfg, monkeypatch):
    s = TpuSecretScanner(cfg, chunk_len=1024, batch_size=4,
                         feed_streams=3, inflight=5)
    assert s.feed_streams == 3 and s.inflight == 5
    monkeypatch.setenv("TRIVY_TPU_FEED_STREAMS", "6")
    monkeypatch.setenv("TRIVY_TPU_FEED_INFLIGHT", "7")
    s = TpuSecretScanner(cfg, chunk_len=1024, batch_size=4)
    assert s.feed_streams == 6 and s.inflight == 7


def test_scanner_rejects_bad_knobs(cfg):
    with pytest.raises(ValueError):
        TpuSecretScanner(cfg, chunk_len=1024, dispatch="sideways")
    # the widest device window needs chunk_len >= 2 * overlap
    with pytest.raises(ValueError):
        TpuSecretScanner(cfg, chunk_len=100)
    # raw slabs are the only wire format: there is no codec to select
    with pytest.raises(TypeError):
        TpuSecretScanner(cfg, chunk_len=1024, compress="on")


# -- raw-slab feed: dispatch, dedup and upload accounting --------------------


def raw_scanner(cfg, dispatch="single", **kw):
    kw.setdefault("chunk_len", 1024)
    kw.setdefault("batch_size", 8)
    if dispatch == "round_robin":
        kw["devices"] = jax.devices()[:4]
    return TpuSecretScanner(cfg, dispatch=dispatch, **kw)


def test_dedup_keys_are_dispatch_invariant(cfg, cpu):
    """A hit cache warmed by a single-device scan serves a round-robin
    scan whole (and the converse): row keys hash raw row content under
    the ruleset fingerprint, never the dispatch path."""
    files = mixed_corpus(8)
    for first, second in (("single", "round_robin"),
                          ("round_robin", "single")):
        shared = new_cache("memory")
        a = raw_scanner(cfg, first, hit_cache=shared)
        b = raw_scanner(cfg, second, hit_cache=shared)
        assert a.ruleset_fingerprint == b.ruleset_fingerprint
        assert_parity(cpu, a, files)
        before = b.stats.snapshot()
        assert_parity(cpu, b, files)
        d = {k: v - before[k] for k, v in b.stats.snapshot().items()}
        assert d["chunks_uploaded"] == 0 and d["chunks_dedup_hit"] > 0
        assert d["bytes_uploaded"] == 0


def test_warm_rescan_uploads_nothing(cfg, cpu):
    t = raw_scanner(cfg, chunk_len=2048)
    files = mixed_corpus(8)
    list(t.scan_files(files))
    before = t.stats.snapshot()
    assert_parity(cpu, t, files)
    d = {k: v - before[k] for k, v in t.stats.snapshot().items()}
    assert d["bytes_uploaded"] == 0 and d["chunks_uploaded"] == 0


def test_round_robin_multi_stream_parity(cfg, cpu):
    t = raw_scanner(cfg, "round_robin", dedup=False)
    assert t._match.n_streams == 4 and t.feed_streams == 4
    assert_parity(cpu, t, mixed_corpus(16, seed=3))
    s = t.stats.snapshot()
    assert s["chunks_uploaded"] == s["chunks"] > 0
    assert s["bytes_uploaded"] >= s["chunks_uploaded"] * t.chunk_len


def test_pcie_auto_round_robin_ships_raw_with_license_gate(
    cfg, cpu, monkeypatch
):
    """A PCIe link with four devices: raw slabs round-robin with the
    fused license gate on. Findings match the CPU engine, every row
    (all-zero rows included) crosses the link exactly once, and the gate
    verdicts match a single-device scan's."""
    monkeypatch.setenv("TRIVY_TPU_LINK_CLASS", "pcie")
    # odd files become C sources, which --license-full sends to the gate
    files = [
        (p[:-4] + ".c" if i % 2 else p, d)
        for i, (p, d) in enumerate(mixed_corpus(16, seed=3))
    ]
    files.append(("zeros.bin", b"\x00" * 5000))
    files.append(("LICENSE", LICENSE_TEXT))

    def scan(dispatch):
        t = raw_scanner(cfg, dispatch, dedup=False)
        assert t.tuning.topology.endswith(":pcie")
        gate = FusedLicenseGate(license_full=True)
        got = list(t.scan_files(iter(files), license_gate=gate))
        assert len(got) == len(files)
        for (path, data), secret in zip(files, got):
            assert secret.to_dict() == cpu.scan_bytes(path, data).to_dict(), (
                path
            )
        return t, gate

    rr, rr_gate = scan("round_robin")
    assert rr._match.n_streams == 4
    s = rr.stats.snapshot()
    assert s["chunks_uploaded"] == s["chunks"]
    assert 0 < s["license_rows_gated"] <= s["chunks_uploaded"]
    one, one_gate = scan("single")
    assert one.stats.snapshot()["chunks_uploaded"] == s["chunks_uploaded"]
    verdicts = {p: rr_gate.should_classify(p) for p, _ in files}
    assert verdicts == {p: one_gate.should_classify(p) for p, _ in files}
    assert verdicts["LICENSE"] and not all(verdicts.values())


def test_mid_scan_degraded_fallback_parity(cfg, cpu):
    """All devices die mid-stream: the scan finishes on the exact host
    engine, in order, byte-identical."""
    t = raw_scanner(cfg, batch_size=4)
    faults.configure("device.dispatch:at=3:times=-1")
    files = mixed_corpus(20, seed=13)
    got = list(t.scan_files(iter(files)))
    assert len(got) == len(files)
    for (path, data), secret in zip(files, got):
        assert secret.to_dict() == cpu.scan_bytes(path, data).to_dict(), path
    assert t.stats.snapshot()["degraded"] >= 1


def raw_feed_corpus():
    """Mixed big files, copied content (dedup material), small
    packable files and a license header, secrets sprinkled in."""
    files = mixed_corpus(8, seed=21)
    files += [(f"lib/copy/{p}", d) for p, d in files[:3]]
    files += [
        (f"src/h_{i}.h", f"// header {i}\n".encode() * 12) for i in range(6)
    ]
    files.append(("src/tok.h", f"a\n{SAMPLES['github-pat']}\nb\n".encode()))
    files.append(("LICENSE", LICENSE_TEXT))
    return files


def scan_accounted(cpu, t, files, license_gate):
    """Scan under an enabled trace context; returns the ScanStats delta
    and the context's ``secret.bytes_uploaded`` counter."""
    gate = FusedLicenseGate(license_full=True) if license_gate else None
    before = t.stats.snapshot()
    with obs.scan_context(name="raw-feed", enabled=True) as ctx:
        got = list(t.scan_files(iter(files), license_gate=gate))
        booked = ctx.counters.get("secret.bytes_uploaded", 0)
    assert len(got) == len(files)
    for (path, data), secret in zip(files, got):
        assert secret.to_dict() == cpu.scan_bytes(path, data).to_dict(), path
    d = {k: v - before[k] for k, v in t.stats.snapshot().items()}
    return d, booked


@pytest.mark.parametrize("store", ["lru", "persisted"])
@pytest.mark.parametrize("license_gate", [False, True],
                         ids=["no_license", "license_gate"])
@pytest.mark.parametrize("pack_small", [True, False],
                         ids=["pack", "no_pack"])
@pytest.mark.parametrize("dispatch", ["single", "round_robin"])
def test_raw_feed_upload_accounting(cfg, cpu, dispatch, pack_small,
                                    license_gate, store):
    """The counter ``secret.upload_share`` reads: every raw batch books
    its padded slab (``n * chunk_len``) to the scan context and to
    ScanStats alike, the booked bytes cover every corpus byte dedup did
    not resolve, and a warm re-scan — from the same scanner's LRU or a
    fresh scanner's persisted store — books nothing."""
    kw = {"pack_small": pack_small}
    if store == "persisted":
        kw["hit_cache"] = new_cache("memory")
    t = raw_scanner(cfg, dispatch, **kw)
    files = raw_feed_corpus()
    d, booked = scan_accounted(cpu, t, files, license_gate)
    assert booked == d["bytes_uploaded"] > 0
    assert booked % t.chunk_len == 0
    assert booked >= d["chunks_uploaded"] * t.chunk_len
    assert d["bytes_in"] == sum(len(b) for _, b in files)
    assert d["bytes_in"] - d["bytes_dedup_hit"] <= booked
    assert d["chunks_dedup_hit"] > 0  # the copies under lib/
    assert (d["rows_packed"] > 0) == pack_small
    if license_gate:
        assert 0 < d["license_rows_gated"] <= d["chunks_uploaded"]
    else:
        assert d["license_rows_gated"] == 0

    if store == "persisted":
        t = raw_scanner(cfg, dispatch, **kw)  # same backend, cold LRU
    d, booked = scan_accounted(cpu, t, files, license_gate)
    assert booked == d["bytes_uploaded"] == 0
    assert d["chunks_uploaded"] == 0
    if store == "persisted":
        assert d["chunks_warm_hit"] > 0
