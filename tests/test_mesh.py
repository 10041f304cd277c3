"""Multi-chip sharding tests on the 8-device virtual CPU mesh (conftest)."""

import jax
import numpy as np
import pytest

from tests.secret_samples import SAMPLES
from trivy_tpu.ops.match import build_match_fn
from trivy_tpu.parallel.mesh import get_mesh, hit_counts_psum, pad_batch
from trivy_tpu.secret.device_compile import compile_rules
from trivy_tpu.secret.engine import SecretScanner
from trivy_tpu.secret.rules import builtin_rules
from trivy_tpu.secret.tpu_scanner import TpuSecretScanner


def test_virtual_devices_present():
    assert len(jax.devices()) == 8


def test_sharded_scan_parity():
    mesh = get_mesh(8)
    cpu = SecretScanner()
    tpu = TpuSecretScanner(chunk_len=1024, batch_size=16, mesh=mesh)
    files = [
        (f"f{i}.txt", f"head\n{text}\ntail\n".encode())
        for i, (rid, text) in enumerate(sorted(SAMPLES.items())[:10])
    ]
    for (path, data), secret in zip(files, tpu.scan_files(files)):
        want = cpu.scan_bytes(path, data)
        assert secret.to_dict() == want.to_dict()


def test_mesh_2d_shapes():
    mesh = get_mesh(8, model=2)
    assert mesh.shape["data"] == 4 and mesh.shape["model"] == 2


def test_hit_counts_psum():
    compiled = compile_rules(builtin_rules())
    mesh = get_mesh(8)
    fn = build_match_fn(compiled, 1024)
    counts_fn = hit_counts_psum(fn, mesh)
    sample = SAMPLES["github-pat"].encode()
    chunk = np.zeros(1024, dtype=np.uint8)
    chunk[: len(sample)] = np.frombuffer(sample, dtype=np.uint8)
    batch = np.stack([chunk] * 3 + [np.zeros(1024, dtype=np.uint8)] * 5)
    counts = np.asarray(counts_fn(pad_batch(batch, 8)))
    ridx = compiled.rule_ids.index("github-pat")
    assert counts[ridx] == 3
    assert counts.sum() == 3


def test_round_robin_dispatch_parity():
    """Multi-stream round-robin dispatch (whole batches to each device in
    turn, no collectives) must match the host oracle. Restricted ruleset +
    4 devices: jit compiles one executable per device placement."""
    from trivy_tpu.secret.engine import ScannerConfig

    ids = ["github-pat", "slack-access-token", "jwt-token", "private-key"]
    cfg = ScannerConfig.from_dict({"enable-builtin-rules": ids})
    cpu = SecretScanner(cfg)
    rr = TpuSecretScanner(
        cfg, chunk_len=1024, batch_size=8,
        dispatch="round_robin", devices=jax.devices()[:4],
    )
    assert rr._match.n_streams == 4
    # warm-up compiles every (rung, device) placement without counting as
    # traffic: per-device batch counts only ever see real batches
    rr.warm_buckets()
    assert rr._staged.busy.batches() == [0, 0, 0, 0]
    files = [
        (f"f{i}.txt", f"head\n{SAMPLES[r]}\ntail\n".encode()
         + b"".join(b"pad line %d.%d\n" % (i, j) for j in range(400)))
        for i, r in enumerate(ids * 3)
    ]
    for (path, data), secret in zip(files, rr.scan_files(files)):
        want = cpu.scan_bytes(path, data)
        assert secret.to_dict() == want.to_dict()
    assert all(n > 0 for n in rr._staged.busy.batches())


def test_round_robin_auto_stays_single_on_cpu():
    """dispatch='auto' must not fan out over virtual CPU devices (they
    share one memory bus; multi-stream there only adds copies)."""
    t = TpuSecretScanner(chunk_len=1024, batch_size=8)
    assert not hasattr(t._match, "n_streams")


# -- license n-gram scoring on the 'model' axis ------------------------------


def _license_texts():
    from trivy_tpu.licensing.corpus_texts import FULL_TEXTS

    rng = np.random.default_rng(3)
    texts = [FULL_TEXTS[k] for k in sorted(FULL_TEXTS)]
    texts += [
        "Server Side Public License VERSION 1, OCTOBER 16, 2018",
        "no license content at all",
    ]
    for _ in range(24):
        texts.append(
            " ".join(
                "".join(chr(c) for c in rng.integers(97, 123, size=7))
                for _ in range(250)
            )
        )
    return texts


def test_sharded_license_scoring_parity():
    """License scoring sharded over the mesh 'model' axis (corpus slabs)
    and 'data' axis (gram rows) must match the host oracle exactly."""
    from trivy_tpu.licensing.classify import LicenseClassifier

    mesh = get_mesh(8, model=2)
    texts = _license_texts()
    host = LicenseClassifier(backend="cpu").classify_batch(texts)
    dev = LicenseClassifier(backend="device", mesh=mesh).classify_batch(texts)
    for i, (a, b) in enumerate(zip(host, dev)):
        assert [(f.name, f.confidence) for f in a] == [
            (f.name, f.confidence) for f in b
        ], f"text {i}"


def test_sharded_license_corpus_device_resident():
    """The corpus table commits to the mesh once ('model'-axis sharded,
    spanning every device) and is reused across calls and classifier
    instances — no per-scan corpus re-upload."""
    from trivy_tpu.licensing.classify import LicenseClassifier

    mesh = get_mesh(8, model=2)
    texts = _license_texts()
    clf = LicenseClassifier(backend="device", mesh=mesh)
    clf.classify_batch(texts)
    scorer = clf._scorer
    keys, credit = scorer.corpus_device
    # sharded over 'model' (leading axis), replicated over 'data'
    assert set(keys.sharding.device_set) == set(mesh.devices.flat)
    assert keys.sharding.spec[0] == "model"
    assert credit.sharding.spec[0] == "model"
    # corpus stays resident across calls and across instances
    first_dispatches = scorer.dispatch_count
    buffers_before = scorer.corpus_device
    clf.classify_batch(texts)
    assert clf._scorer is scorer
    assert scorer.corpus_device is buffers_before  # same buffers, no re-upload
    assert clf._scorer.corpus_device[0] is keys
    assert scorer.dispatch_count > first_dispatches  # work happened
    other = LicenseClassifier(backend="device", mesh=get_mesh(8, model=2))
    other.classify_batch(texts)
    assert other._scorer is scorer  # same mesh identity -> same table


def test_sharded_license_scores_match_unsharded_kernel():
    """Kernel-level: m=2 shard tables reassemble to the m=1 scores."""
    from trivy_tpu.licensing.classify import LicenseClassifier
    from trivy_tpu.ops import ngram_score as ng

    clf = LicenseClassifier(backend="device")
    clf._build_scoring()

    def build(m):
        return ng.build_corpus_table(
            clf.licenses, clf._full_keys, clf._full_weights,
            clf._phrase_keys, clf._phrase_short, model_shards=m,
        )

    whashes, word_text, keys, gt = clf._batch_hashes(_license_texts())
    groups, overflow = ng.pack_gram_rows(ng.fold32(keys), gt, 200)
    assert not overflow
    single = ng.DeviceScorer(build(1))
    mesh = get_mesh(8, model=2)
    sharded = ng.DeviceScorer(build(2), mesh=mesh)
    L = single.table.n_licenses
    dp = sharded.data_parallelism
    any_hit = False
    for rows, _tis in groups:
        rows = rows[:16]
        pad = (-len(rows)) % dp
        if pad:
            rows = np.concatenate(
                [rows, np.full((pad, rows.shape[1]), ng.PAD_KEY, np.int32)]
            )
        fw1, pp1 = (np.asarray(x)[:, :L] for x in single(rows))
        fw2, pp2 = (np.asarray(x)[:, :L] for x in sharded(rows))
        np.testing.assert_allclose(fw1, fw2, rtol=1e-6)
        np.testing.assert_array_equal(pp1, pp2)
        g1 = np.asarray(single.gate(rows))
        g2 = np.asarray(sharded.gate(rows))
        # counts are per-shard sums (a gram in both slabs counts twice
        # under m=2); only the >0 candidacy boolean is load-bearing
        np.testing.assert_array_equal(g1 > 0, g2 > 0)
        assert (g2 >= g1).all()
        any_hit |= bool((g1 > 0).any())
    assert any_hit  # license texts intersect their own corpus
