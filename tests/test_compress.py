"""Compressed slab wire format (secret/compress.py + ops/decompress.py).

Two layers of contract:

1. **Codec** — encode → host-reference-decode → device-kernel-decode must
   be byte-identical for every mode (RAW / PACK7 / TOKEN), including
   pathological inputs (all-run rows, binary rows inside compressed
   frames, empty pad rows).
2. **Pipeline** — findings stay byte-identical to the CPU oracle whether
   rows rode the wire compressed or raw, composed with dedup + packing +
   warm hits + multi-stream dispatch + mid-scan degraded fallback; dedup
   keys hash UNCOMPRESSED content so toggling the codec never flips a key.

Scanners run a RESTRICTED ruleset (cheap device compiles); full-ruleset
parity is test_tpu_scanner.py's job.
"""

import io

import jax
import numpy as np
import pytest

from tests.secret_samples import SAMPLES
from trivy_tpu import faults, obs
from trivy_tpu.cache import new_cache
from trivy_tpu.secret import compress as C
from trivy_tpu.secret.engine import ScannerConfig, SecretScanner
from trivy_tpu.secret.tpu_scanner import TpuSecretScanner

RESTRICTED = {"enable-builtin-rules": ["github-pat", "slack-access-token"]}
RULE_IDS = ["github-pat", "slack-access-token"]


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.clear()


@pytest.fixture(scope="module")
def cpu():
    return SecretScanner(ScannerConfig.from_dict(RESTRICTED))


def build(compress="on", **kw):
    kw.setdefault("chunk_len", 2048)
    kw.setdefault("batch_size", 8)
    return TpuSecretScanner(
        ScannerConfig.from_dict(RESTRICTED), compress=compress, **kw
    )


def assert_parity(cpu, scanner, files):
    got = list(scanner.scan_files(files))
    assert len(got) == len(files)
    for (path, data), secret in zip(files, got):
        assert secret.to_dict() == cpu.scan_bytes(path, data).to_dict(), path
    return got


def mixed_corpus(n=24, seed=7):
    """Printable text (PACK7/TOKEN material), zero pages (gate material),
    binary blobs (raw-inside-frame material) — with secrets sprinkled in."""
    rng = np.random.default_rng(seed)
    files = []
    for i in range(n):
        kind = i % 4
        if kind == 0:  # run-heavy printable text with a secret
            body = (
                b"#" * 120 + b"\n"
                + SAMPLES[RULE_IDS[i % 2]].encode() + b"\n"
                + b"the rate that the land sent was on and in their line\n" * 60
            )
        elif kind == 1:  # random printable noise (PACK7 floor)
            body = rng.integers(0x20, 0x7F, size=5000, dtype=np.uint8).tobytes()
        elif kind == 2:  # zero page + trailing text (zero-gate rows)
            body = b"\x00" * 4096 + b"tail text after the hole\n"
        else:  # binary (top-bit set): must ride RAW inside the frame
            body = rng.integers(0, 256, size=3000, dtype=np.uint8).tobytes()
            body = body.replace(b"\x00", b"\x01")
        files.append((f"f{i}.dat", body))
    return files


# -- layer 1: the codec itself -----------------------------------------------


def _device_decode(codec, cs):
    from trivy_tpu.ops.decompress import build_decompress_fn

    fn = build_decompress_fn(codec.chunk_len, codec.tab_bytes, codec.tab_len)
    return np.asarray(fn(*(jax.numpy.asarray(a) for a in cs.arrays())))


def _round_trip(codec, rows, rows_pad=None):
    rows_pad = rows_pad or len(rows)
    plan = codec.plan(rows)
    out = np.zeros(max(plan.total(), 1) + 256, dtype=np.uint8)
    cs = codec.emit(plan, rows_pad, out.size, out)
    host = codec.decode_slab(cs)
    want = np.zeros((rows_pad, codec.chunk_len), dtype=np.uint8)
    want[: len(rows)] = rows
    np.testing.assert_array_equal(host, want)
    np.testing.assert_array_equal(_device_decode(codec, cs), want)
    return cs


def test_codec_mode_selection_and_ratios():
    codec = C.SlabCodec(1024)
    rng = np.random.default_rng(0)
    printable = rng.integers(0x20, 0x7F, size=(4, 1024), dtype=np.uint8)
    zeros = np.zeros((4, 1024), dtype=np.uint8)
    binary = rng.integers(0, 256, size=(4, 1024), dtype=np.uint8)
    binary[:, 0] = 0xFF  # guarantee a top-bit byte per row
    p_pr, p_z, p_b = (codec.plan(r) for r in (printable, zeros, binary))
    # uniform random printable has no runs/pairs: PACK7 floor, exactly 7/8
    assert all(m == C.MODE_PACK7 for m in p_pr.mode)
    assert p_pr.total() == 4 * 896
    # zero pages are one long run: TOKEN crushes them 8x
    assert all(m == C.MODE_TOKEN for m in p_z.mode)
    assert p_z.total() == 4 * 128
    # binary rows never expand: RAW inside the frame, exactly 1.0
    assert all(m == C.MODE_RAW for m in p_b.mode)
    assert p_b.total() == 4 * 1024
    for rows in (printable, zeros, binary):
        _round_trip(codec, rows)


def test_codec_pathological_rle_and_pad_rows():
    codec = C.SlabCodec(512)
    rows = np.zeros((6, 512), dtype=np.uint8)
    for i, b in enumerate(C.RUN_BYTES[:6]):  # maximal single-byte runs
        rows[i] = b
    _round_trip(codec, rows, rows_pad=8)  # 2 pad rows decode to zeros
    # alternating run/literal boundaries (worst case for block cut points)
    row = np.tile(
        np.r_[np.full(8, 0x20, np.uint8), np.frombuffer(b"abcdefgh", np.uint8)],
        512 // 16,
    )
    _round_trip(codec, np.stack([row] * 3))


def test_codec_fuzz_round_trip():
    rng = np.random.default_rng(42)
    codec = C.SlabCodec(1024)
    makers = [
        lambda n: rng.integers(0, 256, size=(n, 1024), dtype=np.uint8),
        lambda n: rng.integers(0x20, 0x7F, size=(n, 1024), dtype=np.uint8),
        lambda n: np.repeat(  # run-heavy: long stretches of run bytes
            np.array(C.RUN_BYTES, np.uint8)[
                rng.integers(0, 8, size=(n, 64))
            ],
            16, axis=1,
        ),
        lambda n: np.frombuffer(  # english-ish text hits the pair table
            (b"the secret token rate stands on the line; // == -- ##\n" * 200)
            [: n * 1024], np.uint8,
        ).reshape(n, 1024).copy(),
    ]
    for trial in range(12):
        n = int(rng.integers(1, 9))
        rows = makers[trial % 4](n)
        # splice random zero spans so run/literal boundaries move per trial
        if trial % 3 == 0:
            s = int(rng.integers(0, 900))
            rows[rng.integers(0, n)][s : s + 100] = 0
        _round_trip(codec, rows, rows_pad=n + int(rng.integers(0, 3)))


def test_codec_rejects_rung_overflow():
    codec = C.SlabCodec(512)
    rows = np.full((2, 512), 0xFF, dtype=np.uint8)  # binary: total = 1024
    plan = codec.plan(rows)
    out = np.zeros(2048, dtype=np.uint8)
    with pytest.raises(ValueError):
        codec.emit(plan, 2, 512, out)  # rung smaller than the plan


def test_chunk_len_must_be_multiple_of_8():
    with pytest.raises(ValueError):
        C.SlabCodec(1020)


# -- layer 2: pipeline parity ------------------------------------------------


def test_compressed_scan_parity_and_counters(cpu):
    t = build("on")
    before = t.stats.snapshot()
    assert_parity(cpu, t, mixed_corpus())
    d = {k: v - before[k] for k, v in t.stats.snapshot().items()}
    assert d["batches_compressed"] > 0
    assert d["bytes_compressed"] > 0
    # the actual link traffic beat raw for the whole run
    assert d["bytes_uploaded"] < d["bytes_raw_equiv"] + d["bytes_raw_fallback"]
    # zero pages were gated off the wire entirely...
    assert d["chunks_gated_zero"] > 0 and d["bytes_gated"] > 0
    # ...and binary rows rode RAW inside compressed frames
    assert d["bytes_gated_binary"] > 0


def test_compress_off_is_zero_cost(cpu):
    t = build("off")
    assert t._codec is None and not t.compress_on
    assert "decompress" not in t._staged._stages
    assert t._wire_rungs == {}
    before = t.stats.snapshot()
    assert_parity(cpu, t, mixed_corpus())
    d = {k: v - before[k] for k, v in t.stats.snapshot().items()}
    assert d["batches_compressed"] == 0 and d["bytes_compressed"] == 0
    assert d["chunks_gated_zero"] == 0  # zero gate rides the codec
    # raw slabs only: nothing booked against the codec accounting
    assert d["bytes_uploaded"] > 0 and d["bytes_raw_equiv"] == 0
    assert d["bytes_raw_fallback"] == 0 and d["bytes_gated"] == 0


@pytest.mark.parametrize(
    "link, want",
    [("host", False), ("pcie", False), ("wan", True)],
    ids=["host-raw", "pcie-raw", "slower-link-compresses"],
)
def test_auto_mode_resolves_by_link_class(monkeypatch, link, want):
    """``auto`` compresses only on a link class slower than PCIe: on the
    host bus and on PCIe the device decode costs more than the wire bytes
    it saves, so those scanners carry no codec state at all."""
    from trivy_tpu.parallel.mesh import link_class

    monkeypatch.setenv("TRIVY_TPU_LINK_CLASS", link)
    assert link_class() == link
    t = build("auto")
    assert t.compress_on == want
    assert t.tuning_snapshot()["compress"] == want
    assert ("decompress" in t._staged._stages) == want
    assert bool(t._wire_rungs) == want
    assert (t._codec is not None) == want
    warmed = []
    run = t._staged.run

    def record(name, *a, **kw):
        warmed.append(name)
        return run(name, *a, **kw)

    monkeypatch.setattr(t._staged, "run", record)
    t.warm_buckets()
    assert ("decompress" in warmed) == want
    assert "rules" in warmed


def test_dedup_keys_are_codec_invariant(cpu):
    """A hit cache warmed by a compressed scan must serve a raw scan (and
    vice versa): keys hash uncompressed content."""
    shared = new_cache("memory")
    files = mixed_corpus(8)
    a = build("on", hit_cache=shared)
    assert_parity(cpu, a, files)
    b = build("off", hit_cache=shared)
    before = b.stats.snapshot()
    assert_parity(cpu, b, files)
    d = {k: v - before[k] for k, v in b.stats.snapshot().items()}
    assert d["chunks_uploaded"] == 0 and d["chunks_dedup_hit"] > 0


def test_warm_rescan_uploads_nothing(cpu):
    t = build("on")
    files = mixed_corpus(8)
    list(t.scan_files(files))
    before = t.stats.snapshot()
    assert_parity(cpu, t, files)
    d = {k: v - before[k] for k, v in t.stats.snapshot().items()}
    assert d["bytes_uploaded"] == 0 and d["batches_compressed"] == 0


def test_round_robin_multi_stream_parity(cpu):
    t = build(
        "on", chunk_len=1024, dispatch="round_robin",
        devices=jax.devices()[:4], dedup=False,
    )
    assert t._match.n_streams == 4 and t.compress_on
    assert_parity(cpu, t, mixed_corpus(16, seed=3))
    assert t.stats.snapshot()["batches_compressed"] > 0


def test_pcie_auto_round_robin_ships_raw_with_license_gate(cpu, monkeypatch):
    """The default on a PCIe link: ``auto`` ships raw slabs round-robin
    over four devices with the fused license gate on. Findings and gate
    verdicts match the codec path, and the all-zero rows the codec's zero
    gate kept off the wire now upload and resolve clean."""
    from trivy_tpu.licensing.fused import FusedLicenseGate

    monkeypatch.setenv("TRIVY_TPU_LINK_CLASS", "pcie")
    files = mixed_corpus(16, seed=3)
    files.append(("zeros.bin", b"\x00" * 5000))

    def scan(compress):
        t = build(
            compress, chunk_len=1024, dispatch="round_robin",
            devices=jax.devices()[:4], dedup=False,
        )
        gate = FusedLicenseGate(license_full=True)
        got = list(t.scan_files(iter(files), license_gate=gate))
        assert len(got) == len(files)
        for (path, data), secret in zip(files, got):
            assert secret.to_dict() == cpu.scan_bytes(path, data).to_dict(), (
                path
            )
        return t, gate

    raw, raw_gate = scan("auto")
    assert raw._match.n_streams == 4 and not raw.compress_on
    s = raw.stats.snapshot()
    assert s["batches_compressed"] == 0 and s["bytes_compressed"] == 0
    assert s["chunks_gated_zero"] == 0 and s["bytes_gated"] == 0
    packed, packed_gate = scan("on")
    p = packed.stats.snapshot()
    assert p["batches_compressed"] > 0 and p["chunks_gated_zero"] > 0
    # every row the zero gate resolved on the codec path rides the wire raw
    assert s["chunks"] == p["chunks"]
    assert s["chunks_uploaded"] == p["chunks_uploaded"] + p["chunks_gated_zero"]
    for path, _ in files:
        assert raw_gate.should_classify(path) == packed_gate.should_classify(
            path
        ), path


def test_mesh_forces_compress_off(cpu):
    """Sharded mesh: the flat wire buffer can't shard, so compression is
    forced off (loudly) and parity holds on the plain path."""
    from trivy_tpu.parallel.mesh import get_mesh

    t = build("on", chunk_len=1024, batch_size=16, mesh=get_mesh(8))
    assert not t.compress_on and t._codec is None
    assert_parity(cpu, t, mixed_corpus(8, seed=5))
    assert t.stats.snapshot()["batches_compressed"] == 0


def test_dispatch_fault_recovers_compressed_batch(cpu):
    """Retry ladder: a compressed batch that faults on dispatch degrades
    to raw rows host-side FIRST, then retries — findings stay exact."""
    t = build("on", chunk_len=1024)
    s0 = t.stats.snapshot()
    faults.configure("device.dispatch:at=2")
    assert_parity(cpu, t, mixed_corpus(16, seed=9))
    s1 = t.stats.snapshot()
    assert s1["batch_retries"] - s0["batch_retries"] >= 1
    assert s1["degraded"] == s0["degraded"]


def test_mid_scan_degraded_fallback_parity(cpu):
    """All devices die mid-stream with the codec on: the scan finishes on
    the exact host engine, in order, byte-identical."""
    t = build("on", chunk_len=1024, batch_size=4)
    faults.configure("device.dispatch:at=3:times=-1")
    files = mixed_corpus(20, seed=13)
    got = list(t.scan_files(iter(files)))
    assert len(got) == len(files)
    for (path, data), secret in zip(files, got):
        assert secret.to_dict() == cpu.scan_bytes(path, data).to_dict(), path
    assert t.stats.snapshot()["degraded"] >= 1


# -- observability -----------------------------------------------------------


def test_trace_counters_gauge_and_wire_block(cpu):
    from trivy_tpu.obs import export
    from trivy_tpu.obs.metrics import REGISTRY

    t = build("on")
    with obs.scan_context(name="compress-test", enabled=True) as ctx:
        assert_parity(cpu, t, mixed_corpus())
        out = io.StringIO()
        ctx.report(out)
        doc = export.metrics_dict(ctx)
    text = out.getvalue()
    assert "secret.bytes_compressed" in text
    assert "secret.bytes_gated" in text
    assert "secret.bytes_gated_binary" in text
    wire = doc["wire"]
    assert wire["compress"] is True
    assert 0.0 < wire["compression_ratio"] < 1.0
    assert wire["bytes_compressed"] > 0
    assert "trivy_tpu_wire_compression_ratio" in REGISTRY.render()
    # stall verdict maps the codec spans to their own bucket
    from trivy_tpu.obs import stall

    assert stall.BUCKETS["compress"] == "codec-bound"
    assert stall.BUCKETS["decompress"] == "codec-bound"
    assert "codec-bound" in stall.ORDER


def test_wire_block_absent_on_uncompressed_scan(cpu):
    from trivy_tpu.obs import export

    t = build("off")
    with obs.scan_context(name="raw-test", enabled=True) as ctx:
        assert_parity(cpu, t, mixed_corpus(4))
        doc = export.metrics_dict(ctx)
    assert "wire" not in doc


# -- knob resolution ---------------------------------------------------------


def test_tuning_resolution_precedence():
    from trivy_tpu.tuning import resolve_tuning

    cfg = resolve_tuning(opts={}, env={}, autotune_path="")
    assert cfg.compress == "" and cfg.source["compress"] == "default"
    cfg = resolve_tuning(
        opts={}, env={"TRIVY_TPU_SECRET_COMPRESS": "1"}, autotune_path=""
    )
    assert cfg.compress == "on" and cfg.source["compress"] == "env"
    cfg = resolve_tuning(
        opts={"secret_compress": "off",
              "secret_compress_min_ratio": 0.5},
        env={"TRIVY_TPU_SECRET_COMPRESS": "on",
             "TRIVY_TPU_SECRET_COMPRESS_MIN_RATIO": "0.9"},
        autotune_path="",
    )
    assert cfg.compress == "off" and cfg.source["compress"] == "cli"
    assert cfg.compress_min_ratio == 0.5
    assert cfg.source["compress_min_ratio"] == "cli"
    cfg = resolve_tuning(
        opts={}, env={"TRIVY_TPU_SECRET_COMPRESS_MIN_RATIO": "0.75"},
        autotune_path="",
    )
    assert cfg.compress_min_ratio == 0.75
    assert cfg.source["compress_min_ratio"] == "env"
    with pytest.raises(ValueError):
        resolve_tuning(
            opts={}, env={"TRIVY_TPU_SECRET_COMPRESS": "sideways"},
            autotune_path="",
        )
    for bad in ("0", "1.5", "nan"):
        with pytest.raises(ValueError):
            resolve_tuning(
                opts={}, env={"TRIVY_TPU_SECRET_COMPRESS_MIN_RATIO": bad},
                autotune_path="",
            )


def test_scanner_rejects_bad_knobs():
    with pytest.raises(ValueError):
        build("sideways")
    with pytest.raises(ValueError):
        build("on", compress_min_ratio=1.5)
    # chunk_len % 8 != 0 breaks 7-bit packing: compression degrades to
    # off (loud warning) instead of refusing the scan
    t = build("on", chunk_len=1020)
    assert not t.compress_on and t._codec is None
