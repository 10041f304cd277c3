"""Multi-chip sharding tests on the 8-device virtual CPU mesh (conftest)."""

import jax
import numpy as np
import pytest

from tests.secret_samples import SAMPLES
from trivy_tpu.ops.rules import build_rules_fn
from trivy_tpu.parallel.mesh import get_mesh, hit_counts_psum, pad_batch
from trivy_tpu.secret.device_compile import build_tables, compile_rules
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
    tables = build_tables(compiled)
    mesh = get_mesh(8)
    fn = build_rules_fn(tables.margin, tables.kw_bits)
    counts_fn = hit_counts_psum(fn, mesh, len(tables.operands()))
    sample = SAMPLES["github-pat"].encode()
    chunk = np.zeros(1024, dtype=np.uint8)
    chunk[: len(sample)] = np.frombuffer(sample, dtype=np.uint8)
    batch = pad_batch(np.stack([chunk] * 3 + [np.zeros(1024, dtype=np.uint8)] * 5), 8)
    counts = np.asarray(counts_fn(batch, *tables.operands()))
    # the psum'd bit counts equal those of the unsharded pass, row by row
    out = np.asarray(jax.jit(fn)(batch, *tables.operands()))
    per_word = np.unpackbits(
        np.ascontiguousarray(out[:, :-1]).view(np.uint8), bitorder="little"
    ).reshape(len(out), -1, 32).sum(axis=(0, 2))
    assert counts.tolist() == per_word.tolist()
    hit_rows, hit_rules, _, _ = tables.pairs(out)
    ridx = compiled.rule_ids.index("github-pat")
    assert sorted(hit_rows[hit_rules == ridx].tolist()) == [0, 1, 2]
    assert counts.sum() % 3 == 0 and counts.sum() > 0


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
    """Kernel-level: a model=2 raw-bytes scorer (shard tables over the
    mesh 'model' axis, rows over 'data') reassembles to the single-device
    scorer's gate and scores on the same text rows."""
    from trivy_tpu.licensing.classify import LicenseClassifier
    from trivy_tpu.ops import ngram_score as ng

    clf = LicenseClassifier(backend="device")
    single = ng.DeviceBytesScorer(clf.corpus_table(1))
    sharded = ng.DeviceBytesScorer(
        clf.corpus_table(2), mesh=get_mesh(8, model=2)
    )
    assert sharded.table.n_shards == 2
    L = single.table.n_licenses
    dp = sharded.data_parallelism
    encoded = [t.encode("latin-1", "replace") for t in _license_texts()]
    groups, _wide = ng.pack_text_rows(encoded)
    any_hit = False
    for width, (rows, _tis) in sorted(groups.items()):
        rows = rows[:16]
        pad = (-len(rows)) % dp
        if pad:
            rows = np.concatenate(
                [rows, np.zeros((pad, rows.shape[1]), np.uint8)]
            )
        dev1, dev2 = single.put_rows(rows), sharded.put_rows(rows)
        for a, b in zip(single.gate_bytes(dev1, width),
                        sharded.gate_bytes(dev2, width)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        fw1, pp1, nu1 = (np.asarray(x) for x in
                         single.score_from_bytes(dev1, width))
        fw2, pp2, nu2 = (np.asarray(x) for x in
                         sharded.score_from_bytes(dev2, width))
        np.testing.assert_allclose(fw1[:, :L], fw2[:, :L], rtol=1e-6)
        np.testing.assert_array_equal(pp1[:, :L], pp2[:, :L])
        np.testing.assert_array_equal(nu1, nu2)
        any_hit |= bool((pp1[:, :L] > 0).any())
    assert any_hit  # license texts intersect their own corpus
