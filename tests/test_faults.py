"""Fault-tolerant scan execution: the retry/breaker/fallback ladder, proven
with the deterministic fault-injection harness (trivy_tpu/faults.py).

Rungs under test, from the bottom up:

1. per-batch retry in the secret device loop (transient dispatch/fetch
   errors; OOM-shaped errors split the batch instead of retrying it whole)
2. per-device circuit breaker under round-robin dispatch (a dead device is
   excluded after K consecutive failures; surviving devices absorb its
   batches; /metrics shows the open breaker)
3. graceful degradation: all devices dead -> the scan completes on the
   exact host confirm path (the parity oracle), flagged Degraded
4. cache/rpc/walker failure domains: redis drop degrades to memory,
   rpc backoff is jittered/deadlined/Retry-After-aware, vanished files are
   counted instead of silently disappearing, server drains on SIGTERM
"""

import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tests.secret_samples import SAMPLES
from trivy_tpu import faults, obs
from trivy_tpu.obs import metrics as obs_metrics
from trivy_tpu.secret.engine import ScannerConfig, SecretScanner
from trivy_tpu.secret.tpu_scanner import TpuSecretScanner

GHP = "ghp_" + "A1b2C3d4E5f6G7h8I9j0K1l2M3n4O5p6Q7r8"

RULE_IDS = ["github-pat", "slack-access-token", "jwt-token", "private-key"]


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.clear()


@pytest.fixture(scope="module")
def cfg():
    return ScannerConfig.from_dict({"enable-builtin-rules": RULE_IDS})


@pytest.fixture(scope="module")
def cpu(cfg):
    return SecretScanner(cfg)


@pytest.fixture(scope="module")
def corpus():
    """40 distinct files (unique noise so in-scan dedup can't absorb the
    dispatch traffic the fault sites need to see)."""
    rng = np.random.default_rng(11)
    files = []
    for i in range(40):
        pad = rng.integers(97, 123, size=4000, dtype=np.uint8).tobytes()
        files.append(
            (
                f"f{i}.txt",
                b"head\n" + SAMPLES[RULE_IDS[i % 4]].encode() + b"\n" + pad,
            )
        )
    return files


def assert_parity(cpu, scanner, files):
    got = list(scanner.scan_files(files))
    assert len(got) == len(files)
    for (path, data), secret in zip(files, got):
        want = cpu.scan_bytes(path, data)
        assert secret.to_dict() == want.to_dict(), f"mismatch for {path}"


# -- the injection registry itself -------------------------------------------


def test_spec_parsing_and_nth_hit():
    plan = faults.configure("site.a:at=3:times=2,site.b@k1:error=oom,seed=5")
    assert plan.seed == 5
    fired = []
    for i in range(1, 7):
        try:
            faults.check("site.a")
            fired.append(False)
        except faults.InjectedFault:
            fired.append(True)
    assert fired == [False, False, True, True, False, False]
    # keyed rule: only k1 faults, and the OOM shape carries the marker
    faults.check("site.b", key="k2")
    with pytest.raises(faults.InjectedOom, match="RESOURCE_EXHAUSTED"):
        faults.check("site.b", key="k1")
    assert plan.fired() == {"site.a": 2, "site.b@k1": 1}


def test_error_kinds_and_bad_specs():
    faults.configure("a.b:error=conn,c.d:error=io")
    with pytest.raises(ConnectionError):
        faults.check("a.b")
    with pytest.raises(OSError):
        faults.check("c.d")
    for bad in ("x:wat=1", "x:error=nope", "x:at=0", "x:nonsense"):
        with pytest.raises(ValueError):
            faults.parse(bad)


def test_times_forever_and_per_key_counters():
    faults.configure("s@ka:at=2:times=-1")
    # per-(site, key) counters: kb traffic must not advance ka's counter
    faults.check("s", key="kb")
    faults.check("s", key="kb")
    faults.check("s", key="ka")  # ka hit 1 < at
    for _ in range(5):
        with pytest.raises(faults.InjectedFault):
            faults.check("s", key="ka")


def test_rate_mode_is_seed_deterministic():
    def pattern(seed):
        faults.configure(f"s.r:rate=0.5,seed={seed}")
        out = []
        for _ in range(64):
            try:
                faults.check("s.r", key="k")
                out.append(0)
            except faults.InjectedFault:
                out.append(1)
        return out

    a, b = pattern(7), pattern(7)
    assert a == b  # deterministic for a fixed seed
    assert 10 < sum(a) < 54  # and actually probabilistic-looking
    assert pattern(8) != a  # seed changes the schedule


def test_keys_containing_colons_are_addressable():
    """Redis cache keys look like fanal::artifact::<digest> — the grammar
    must treat only trailing known options as options."""
    plan = faults.parse("cache.redis.get@fanal::artifact::abc:times=-1")
    (rule,) = plan.rules
    assert rule.site == "cache.redis.get"
    assert rule.key == "fanal::artifact::abc"
    assert rule.times == -1
    faults.configure(plan)
    faults.check("cache.redis.get", key="fanal::artifact::other")
    with pytest.raises(faults.InjectedFault):
        faults.check("cache.redis.get", key="fanal::artifact::abc")


def test_env_arming(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "x.y:at=2")
    plan = faults.configure_from_env()
    assert plan.rules[0].site == "x.y" and plan.rules[0].at == 2


def test_disarmed_is_free():
    faults.clear()
    faults.check("device.dispatch", key="d0")  # no plan: never raises


# -- rung 1: per-batch retry + OOM halving -----------------------------------


def test_injected_dispatch_failure_recovers_with_parity(cfg, cpu, corpus):
    scanner = TpuSecretScanner(cfg, chunk_len=1024, batch_size=8)
    s0 = scanner.stats.snapshot()
    faults.configure("device.dispatch:at=2")
    assert_parity(cpu, scanner, corpus)
    s1 = scanner.stats.snapshot()
    assert s1["batch_retries"] - s0["batch_retries"] >= 1
    assert s1["degraded"] == s0["degraded"]  # recovered, not degraded


def test_oom_shaped_error_halves_the_batch(cfg, cpu, corpus):
    scanner = TpuSecretScanner(cfg, chunk_len=1024, batch_size=8)
    s0 = scanner.stats.snapshot()
    faults.configure("device.dispatch:at=1:error=oom")
    assert_parity(cpu, scanner, corpus)
    s1 = scanner.stats.snapshot()
    assert s1["batch_splits"] - s0["batch_splits"] >= 1
    # splits are not plain retries, and the scan stayed on the device path
    assert s1["degraded"] == s0["degraded"]


def test_fetch_failure_redispatches(cfg, cpu, corpus):
    import jax

    faults.configure("device.fetch@d1:at=1:times=2")
    scanner = TpuSecretScanner(
        cfg, chunk_len=1024, batch_size=8,
        dispatch="round_robin", devices=jax.devices()[:4], dedup=False,
    )
    assert_parity(cpu, scanner, corpus)
    s = scanner.stats.snapshot()
    assert s["batch_retries"] >= 1 and s["degraded"] == 0


# -- rung 2: circuit breaker under round-robin dispatch ----------------------


def test_breaker_opens_with_one_dead_device_parity_holds(cfg, cpu, corpus):
    """Acceptance: one of 8 devices scripted permanently dead — the
    multichip parity scan completes byte-identical, the breaker opens, and
    GET /metrics on a scan server shows it open."""
    import jax

    faults.configure("device.dispatch@d3:times=-1")
    scanner = TpuSecretScanner(
        cfg, chunk_len=1024, batch_size=8,
        dispatch="round_robin", devices=jax.devices(), dedup=False,
    )
    assert scanner._match.n_streams == 8
    assert_parity(cpu, scanner, corpus)
    assert scanner._match.breaker.is_open(3)
    assert scanner._match.breaker.open_devices() == [3]
    assert scanner.stats.snapshot()["degraded"] == 0
    # the process-global registry carries the breaker state...
    assert (
        'trivy_tpu_device_breaker_open{device="d3"} 1'
        in obs_metrics.REGISTRY.render()
    )
    # ...and the scan server's /metrics surface exposes it
    from trivy_tpu.cache import new_cache
    from trivy_tpu.rpc.server import start_server

    httpd, port = start_server(cache=new_cache("memory"))
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics"
        ) as r:
            body = r.read().decode()
    finally:
        httpd.shutdown()
    assert 'trivy_tpu_device_breaker_open{device="d3"} 1' in body


def test_breaker_reprobe_closes_after_recovery():
    """Half-open probe: after the backoff, one dispatch probes the open
    device; success closes the breaker, failure doubles the backoff."""
    from trivy_tpu.parallel.mesh import CircuitBreaker

    t = {"now": 0.0}
    b = CircuitBreaker(4, threshold=2, probe_backoff=1.0, clock=lambda: t["now"])
    b.record_failure(1)
    b.record_failure(1)
    assert b.is_open(1)
    assert b.next_device(1) == 2  # open, probe not due
    t["now"] = 1.5
    assert b.next_device(1) == 1  # probe due: half-open
    assert b.next_device(1) == 2  # one probe at a time
    b.record_failure(1)  # probe failed -> backoff doubled
    t["now"] = 2.9
    assert b.next_device(1) == 2
    t["now"] = 3.6
    assert b.next_device(1) == 1
    b.record_success(1)
    assert not b.is_open(1)
    assert b.next_device(1) == 1


def test_breaker_stale_inflight_failures_do_not_punish_recovery():
    """Failures from batches dispatched BEFORE the breaker opened must not
    count as failed probes (which would double the backoff with no probe
    ever sent)."""
    from trivy_tpu.parallel.mesh import CircuitBreaker

    t = {"now": 0.0}
    b = CircuitBreaker(2, threshold=2, probe_backoff=1.0, clock=lambda: t["now"])
    b.record_failure(0)
    b.record_failure(0)  # opens; next probe at t=1.0
    b.record_failure(0)  # stale in-flight batch, not a probe
    b.record_failure(0)  # another one
    t["now"] = 1.5
    assert b.next_device(0) == 0  # probe still due on the ORIGINAL schedule


def test_breaker_unreported_probe_expires():
    """A probe whose outcome is never reported (scan generator closed with
    the probe batch in flight) must not exclude the device forever — the
    probe slot expires after probe_timeout."""
    from trivy_tpu.parallel.mesh import CircuitBreaker

    t = {"now": 0.0}
    b = CircuitBreaker(
        2, threshold=1, probe_backoff=1.0, probe_timeout=10.0,
        clock=lambda: t["now"],
    )
    b.record_failure(0)
    t["now"] = 2.0
    assert b.next_device(0) == 0  # probe handed out, never reported
    t["now"] = 5.0
    assert b.next_device(0) == 1  # probe still pending: skip
    t["now"] = 13.0
    assert b.next_device(0) == 0  # pending probe expired: probe again


def test_all_devices_open_raises_devices_unavailable():
    from trivy_tpu.parallel.mesh import CircuitBreaker

    b = CircuitBreaker(2, threshold=1, probe_backoff=100.0)
    b.record_failure(0)
    b.record_failure(1)
    assert b.next_device(0) is None


# -- rung 3: graceful degradation to the host path ---------------------------


def test_all_devices_dead_falls_back_to_host(cfg, cpu, corpus):
    scanner = TpuSecretScanner(cfg, chunk_len=1024, batch_size=8)
    h0 = obs.current().health_snapshot().get("scan.degraded", 0)
    faults.configure("device.dispatch:times=-1")
    assert_parity(cpu, scanner, corpus)
    assert scanner.stats.snapshot()["degraded"] == 1
    assert obs.current().health_snapshot()["scan.degraded"] == h0 + 1


def test_no_host_fallback_raises(cfg, corpus):
    scanner = TpuSecretScanner(
        cfg, chunk_len=1024, batch_size=8, host_fallback=False
    )
    faults.configure("device.dispatch:times=-1")
    with pytest.raises(faults.InjectedFault):
        list(scanner.scan_files(corpus))


def test_fallback_mid_stream_preserves_order_and_parity(cfg, cpu):
    """The device path dies while the input stream is only half consumed:
    already-resolved files, in-flight files, and not-yet-read files must
    all emit, in order, with oracle findings."""
    rng = np.random.default_rng(3)
    files = []
    for i in range(30):
        pad = rng.integers(97, 123, size=3000, dtype=np.uint8).tobytes()
        files.append(
            (f"s{i}.txt", SAMPLES[RULE_IDS[i % 4]].encode() + b"\n" + pad)
        )
    scanner = TpuSecretScanner(cfg, chunk_len=1024, batch_size=4)
    faults.configure("device.dispatch:at=4:times=-1")  # dies mid-stream
    got = list(scanner.scan_files(iter(files)))  # generator input
    assert len(got) == len(files)
    for (path, data), secret in zip(files, got):
        assert secret.to_dict() == cpu.scan_bytes(path, data).to_dict(), path
    assert scanner.stats.snapshot()["degraded"] >= 1


def test_device_backend_init_failure_degrades_to_host(monkeypatch, tmp_path):
    """--backend that fails at init (import/compile/device probe) must scan
    on the exact host engine and mark the scan degraded."""
    from trivy_tpu.artifact.local_fs import ArtifactOption, LocalFSArtifact
    from trivy_tpu.cache import new_cache
    from trivy_tpu.fanal.analyzers import secret as secret_analyzer
    from trivy_tpu.scanner import ScanOptions, Scanner
    from trivy_tpu.scanner.local_driver import LocalDriver

    (tmp_path / "gh.txt").write_text(f"token {GHP} end\n")

    def boom(*a, **kw):
        raise RuntimeError("no accelerator: backend init failed")

    monkeypatch.setattr(
        "trivy_tpu.secret.tpu_scanner.TpuSecretScanner.__init__", boom
    )
    monkeypatch.setattr(secret_analyzer, "_scanner_cache", {})
    cache = new_cache("fs", str(tmp_path / "cache"))
    artifact = LocalFSArtifact(
        str(tmp_path), cache, ArtifactOption(backend="auto")
    )
    report = Scanner(artifact, LocalDriver(cache)).scan_artifact(
        ScanOptions(scanners=["secret"])
    )
    assert report.degraded
    assert [r.target for r in report.results] == ["gh.txt"]
    assert report.results[0].secrets[0].rule_id == "github-pat"


def test_device_backend_init_failure_is_loud_under_no_host_fallback(
    monkeypatch, tmp_path
):
    """The CLI's --no-host-fallback reaches the secret analyzer's init
    rung: a backend that cannot initialize fails the scan."""
    from trivy_tpu import cli
    from trivy_tpu.fanal.analyzers import secret as secret_analyzer

    (tmp_path / "tree").mkdir()
    (tmp_path / "tree" / "gh.txt").write_text(f"token {GHP} end\n")

    def boom(*a, **kw):
        raise RuntimeError("no accelerator: backend init failed")

    monkeypatch.setattr(
        "trivy_tpu.secret.tpu_scanner.TpuSecretScanner.__init__", boom
    )
    monkeypatch.setattr(secret_analyzer, "_scanner_cache", {})
    with pytest.raises(RuntimeError, match="backend init failed"):
        cli.main([
            "fs", "--scanners", "secret", "--backend", "xla",
            "--no-host-fallback", "--format", "json", "-q",
            "--cache-dir", str(tmp_path / "cache"),
            "-o", str(tmp_path / "out.json"), str(tmp_path / "tree"),
        ])


def test_scan_timeout_fails_the_scan_not_one_analyzer(monkeypatch, tmp_path):
    """--timeout expiring inside an analyzer's finalize must fail the
    scan: caught by the analyzer containment it used to drop that
    analyzer's findings from a report that exited 0 (found on the chip,
    where a cold compile ran a scan past the 300 s default)."""
    import time

    from trivy_tpu import cli
    from trivy_tpu.fanal.analyzers import license as license_analyzer
    from trivy_tpu.licensing.corpus_texts import FULL_TEXTS

    (tmp_path / "tree").mkdir()
    (tmp_path / "tree" / "LICENSE").write_text(FULL_TEXTS["MIT"])
    monkeypatch.setattr(
        license_analyzer._LicenseBatchAnalyzer, "finalize",
        lambda self: time.sleep(10),
    )
    t0 = time.monotonic()
    rc = cli.main([
        "fs", "--scanners", "license", "--backend", "cpu", "--timeout", "1",
        "--format", "json", "-q", "--cache-dir", str(tmp_path / "cache"),
        "-o", str(tmp_path / "out.json"), str(tmp_path / "tree"),
    ])
    assert rc == 1
    assert time.monotonic() - t0 < 9
    assert not (tmp_path / "out.json").exists()


@pytest.mark.parametrize("host_fallback", [True, False])
def test_license_auto_device_probe_failure(monkeypatch, host_fallback):
    """backend='auto' asks jax which platform it has; a probe that fails
    degrades to the host scorer, or fails under --no-host-fallback."""
    import jax

    from trivy_tpu.licensing.classify import LicenseClassifier

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    clf = LicenseClassifier(backend="auto", host_fallback=host_fallback)
    with obs.scan_context(name="probe", enabled=True) as ctx:
        if host_fallback:
            assert clf._use_device(64) is False
            assert ctx.counters.get("license.degraded", 0) == 1
        else:
            with pytest.raises(RuntimeError, match="initialize backend"):
                clf._use_device(64)


def test_license_device_leg_falls_back_to_host():
    from trivy_tpu.licensing.classify import LicenseClassifier
    from trivy_tpu.licensing.corpus_texts import FULL_TEXTS

    texts = [FULL_TEXTS[k] for k in sorted(FULL_TEXTS)[:6]]
    texts += ["no license content here at all"] * 6
    host = LicenseClassifier(backend="cpu").classify_batch(texts)
    faults.configure("device.dispatch@license:times=-1")
    dev = LicenseClassifier(backend="device").classify_batch(texts)
    for a, b in zip(host, dev):
        assert [(f.name, f.confidence) for f in a] == [
            (f.name, f.confidence) for f in b
        ]
    with pytest.raises(faults.InjectedFault):
        LicenseClassifier(backend="device", host_fallback=False).classify_batch(
            texts
        )


def test_license_fault_mid_batch_degrades_license_only():
    """Chaos leg: ``device.dispatch@license`` faulting MID-batch (the
    first dispatch lands, a later one faults) degrades ONLY the license
    stage to the host oracle — findings parity holds — while the secret
    stage's device feed (keyed ``d<i>``) keeps running under the armed
    fault and still reports its findings."""
    from tests.secret_samples import SAMPLES
    from trivy_tpu.licensing.classify import LicenseClassifier
    from trivy_tpu.licensing.corpus_texts import FULL_TEXTS
    from trivy_tpu.licensing.fused import FusedLicenseGate
    from trivy_tpu.secret.tpu_scanner import TpuSecretScanner

    # two row-width groups -> at least two license dispatches, so at=2
    # faults strictly mid-batch
    texts = [FULL_TEXTS[k] for k in sorted(FULL_TEXTS)[:8]]
    texts += [FULL_TEXTS["MIT"] + " more filler words here " * 300] * 4
    host = LicenseClassifier(backend="cpu").classify_batch(texts)

    scanner = TpuSecretScanner(
        ScannerConfig.from_dict({"enable-builtin-rules": ["github-pat"]}),
        chunk_len=2048, batch_size=8,
    )
    gate = FusedLicenseGate(license_full=True)
    files = [(f"t{i}/LICENSE", t.encode()) for i, t in enumerate(texts)]
    files.append(
        ("src/cfg.py", f"token = '{SAMPLES['github-pat']}'\n".encode())
    )

    faults.configure("device.dispatch@license:at=2:times=-1")
    with obs.scan_context(name="chaos-lic", enabled=True) as ctx:
        secret_findings = list(
            scanner.scan_files(iter(files), license_gate=gate)
        )
        dev = LicenseClassifier(backend="device").classify_batch(texts)
        assert ctx.counters.get("license.degraded", 0) >= 1
    assert secret_findings  # the secret stage kept running
    for a, b in zip(host, dev):
        assert [(f.name, f.confidence) for f in a] == [
            (f.name, f.confidence) for f in b
        ]


# -- cache failure domain ----------------------------------------------------


def _sever(cache):
    cache._resp.sock.shutdown(socket.SHUT_RDWR)


def test_redis_reconnects_once_on_dropped_connection():
    from tests.test_redis_cache import FakeRedis
    from trivy_tpu.cache.redis import RedisCache

    s = FakeRedis().start()
    try:
        cache = RedisCache(f"redis://127.0.0.1:{s.port}")
        cache.put_blob("b1", {"x": 1})
        _sever(cache)  # dropped connection, server still up
        assert cache.get_blob("b1") == {"x": 1}  # reconnect + replay
        assert not cache.degraded
        cache.close()
    finally:
        s.stop()


def test_redis_drop_mid_scan_degrades_to_memory():
    from tests.test_redis_cache import FakeRedis
    from trivy_tpu.cache.redis import RedisCache

    s = FakeRedis().start()
    cache = RedisCache(f"redis://127.0.0.1:{s.port}")
    cache.put_blob("b1", {"x": 1})
    h0 = obs.current().health_snapshot().get("cache.degraded", 0)
    s.stop()
    _sever(cache)  # connection AND server gone
    # every op keeps working against the in-memory fallback, no raise
    assert cache.get_blob("b1") is None  # redis-era entries are gone
    assert cache.degraded
    cache.put_blob("b2", {"y": 2})
    assert cache.get_blob("b2") == {"y": 2}
    assert cache.missing_blobs("a", ["b2", "b3"]) == (True, ["b3"])
    cache.delete_blobs(["b2"])
    assert cache.get_blob("b2") is None
    assert "trivy_tpu_cache_degraded 1" in obs_metrics.REGISTRY.render()
    assert obs.current().health_snapshot()["cache.degraded"] == h0 + 1


def test_redis_server_err_reply_does_not_degrade():
    """A server-level -ERR reply (OOM/LOADING/READONLY) is a command
    failure, not a transport failure: it must surface, not silently flip
    the healthy connection to the in-memory fallback."""
    from tests.test_redis_cache import FakeRedis
    from trivy_tpu.cache.redis import RedisCache, RedisError

    s = FakeRedis().start()
    try:
        cache = RedisCache(f"redis://127.0.0.1:{s.port}")
        with pytest.raises(RedisError):
            cache._do(lambda: cache._cmd("BOGUS"), lambda m: "mem")
        assert not cache.degraded
        cache.put_blob("b", {"x": 1})  # connection still healthy
        assert cache.get_blob("b") == {"x": 1}
        cache.close()
    finally:
        s.stop()


def test_redis_injected_fault_degrades():
    from tests.test_redis_cache import FakeRedis
    from trivy_tpu.cache.redis import RedisCache

    s = FakeRedis().start()
    try:
        cache = RedisCache(f"redis://127.0.0.1:{s.port}")
        faults.configure("cache.redis.get:times=-1:error=conn")
        assert cache.get_blob("anything") is None
        assert cache.degraded
    finally:
        s.stop()


def test_scan_completes_through_degraded_redis(tmp_path):
    """A real fs scan whose redis cache dies mid-flight completes and the
    report summary carries CacheDegraded."""
    from tests.test_redis_cache import FakeRedis
    from trivy_tpu.artifact.local_fs import ArtifactOption, LocalFSArtifact
    from trivy_tpu.cache.redis import RedisCache
    from trivy_tpu.scanner import ScanOptions, Scanner
    from trivy_tpu.scanner.local_driver import LocalDriver

    (tmp_path / "gh.txt").write_text(f"token {GHP} end\n")
    s = FakeRedis().start()
    cache = RedisCache(f"redis://127.0.0.1:{s.port}")
    s.stop()
    _sever(cache)  # the scan starts with the connection already dead
    artifact = LocalFSArtifact(
        str(tmp_path), cache, ArtifactOption(backend="cpu")
    )
    report = Scanner(artifact, LocalDriver(cache)).scan_artifact(
        ScanOptions(scanners=["secret"])
    )
    assert cache.degraded
    assert report.metadata.get("CacheDegraded") is True
    assert [r.target for r in report.results] == ["gh.txt"]


# -- rpc client backoff hardening --------------------------------------------


class _FakeTime:
    def __init__(self):
        self.now = 0.0
        self.sleeps = []

    def monotonic(self):
        return self.now

    def sleep(self, s):
        self.sleeps.append(s)
        self.now += s


def _http_error(code, headers=None):
    import email.message

    msg = email.message.Message()
    for k, v in (headers or {}).items():
        msg[k] = v
    return urllib.error.HTTPError("http://x", code, "err", msg, None)


def test_rpc_retry_honors_retry_after_on_503(monkeypatch):
    from trivy_tpu.rpc import client as client_mod

    ft = _FakeTime()
    monkeypatch.setattr(client_mod, "time", ft)

    class FakeRandom:
        @staticmethod
        def uniform(lo, hi):
            return hi / 2

    monkeypatch.setattr(client_mod, "random", FakeRandom)
    calls = {"n": 0}

    def fake_request(url, method, body, headers, timeout):
        calls["n"] += 1
        if calls["n"] <= 2:
            return 503, {"Retry-After": "2.5"}, b'{"error": "draining"}'
        return 200, {}, b'{"ok": true}'

    monkeypatch.setattr(client_mod._POOL, "request", fake_request)
    out = client_mod._post("http://x", "/p", {}, "", "T", 1.0)
    assert out == {"ok": True}
    # server-directed minimum plus jitter (backoff/2 here): never shorter
    # than Retry-After, never the exact same instant across a fleet
    assert ft.sleeps == [2.5 + 0.05, 2.5 + 0.1]


def test_rpc_retry_uses_full_jitter(monkeypatch):
    from trivy_tpu.rpc import client as client_mod

    ft = _FakeTime()
    monkeypatch.setattr(client_mod, "time", ft)
    spans = []

    class FakeRandom:
        @staticmethod
        def uniform(lo, hi):
            spans.append((lo, hi))
            return hi / 2  # deterministic mid-jitter

    monkeypatch.setattr(client_mod, "random", FakeRandom)

    def always_refused(url, method, body, headers, timeout):
        raise ConnectionRefusedError("nope")

    monkeypatch.setattr(client_mod._POOL, "request", always_refused)
    with pytest.raises(client_mod.RPCError, match="retries exhausted|nope"):
        client_mod._post("http://x", "/p", {}, "", "T", 1.0, retries=4)
    # full jitter: every sleep drawn from U(0, backoff), backoff doubling
    # and capped at MAX_BACKOFF
    assert [lo for lo, _ in spans] == [0.0] * len(spans)
    his = [hi for _, hi in spans]
    assert his == [0.1, 0.2, 0.4, 0.8]
    assert all(s == hi / 2 for s, (_, hi) in zip(ft.sleeps, spans))


def test_rpc_retry_wall_clock_deadline(monkeypatch):
    from trivy_tpu.rpc import client as client_mod

    ft = _FakeTime()
    monkeypatch.setattr(client_mod, "time", ft)

    def always_refused(url, method, body, headers, timeout):
        ft.now += 2.0  # each attempt burns wall clock
        raise ConnectionRefusedError("nope")

    monkeypatch.setattr(client_mod._POOL, "request", always_refused)
    with pytest.raises(client_mod.RPCError, match="deadline"):
        client_mod._post(
            "http://x", "/p", {}, "", "T", 1.0, retries=100, deadline=5.0
        )
    assert ft.now < 10.0  # bounded, nowhere near 100 retries


def test_rpc_post_fault_site_retries_to_success(monkeypatch):
    """The rpc.post injection site exercises the real retry loop."""
    from trivy_tpu.rpc import client as client_mod

    ft = _FakeTime()
    monkeypatch.setattr(client_mod, "time", ft)

    def fake_request(url, method, body, headers, timeout):
        return 200, {}, b"{}"

    monkeypatch.setattr(client_mod._POOL, "request", fake_request)
    faults.configure("rpc.post:at=1:times=2:error=conn")
    assert client_mod._post("http://x", "/p", {}, "", "T", 1.0) == {}
    assert len(ft.sleeps) == 2
    # the default error kind must also ride the retry loop, not crash it
    faults.configure("rpc.post:at=1")
    assert client_mod._post("http://x", "/p", {}, "", "T", 1.0) == {}
    assert len(ft.sleeps) == 3


# -- server graceful shutdown ------------------------------------------------


def test_server_drains_on_shutdown():
    from trivy_tpu.cache import new_cache
    from trivy_tpu.rpc.server import drain_and_shutdown, start_server

    class SlowCache:
        def __init__(self):
            self.inner = new_cache("memory")

        def put_blob(self, blob_id, info):
            time.sleep(0.6)
            self.inner.put_blob(blob_id, info)

        def __getattr__(self, name):
            return getattr(self.inner, name)

    httpd, port = start_server(cache=SlowCache())
    base = f"http://127.0.0.1:{port}"

    def healthz():
        with urllib.request.urlopen(base + "/healthz") as r:
            return json.loads(r.read())

    assert healthz()["Status"] == "ok"
    put_path = "/twirp/trivy.cache.v1.Cache/PutBlob"

    def slow_put():
        req = urllib.request.Request(
            base + put_path,
            data=json.dumps({"DiffID": "d", "BlobInfo": {}}).encode(),
            headers={"Content-Type": "application/json"},
        )
        urllib.request.urlopen(req)

    t = threading.Thread(target=slow_put)
    t.start()
    time.sleep(0.15)  # let the slow request go in-flight
    result = {}
    drainer = threading.Thread(
        target=lambda: result.update(left=drain_and_shutdown(httpd, timeout=5))
    )
    drainer.start()
    time.sleep(0.1)
    # while draining: healthz flips so LBs stop routing...
    assert healthz()["Status"] == "draining"
    # ...and new RPCs bounce with 503 + Retry-After (the client honors it)
    req = urllib.request.Request(
        base + put_path, data=b"{}",
        headers={"Content-Type": "application/json"},
    )
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req)
    assert ei.value.code == 503
    assert ei.value.headers.get("Retry-After") == "1"
    drainer.join()
    t.join()
    assert result["left"] == 0  # the in-flight request finished cleanly


def test_server_drain_timeout_is_bounded():
    from trivy_tpu.cache import new_cache
    from trivy_tpu.rpc.server import drain_and_shutdown, start_server

    httpd, _port = start_server(cache=new_cache("memory"))
    httpd.service.metrics.in_flight.inc()  # a request that never finishes
    t0 = time.monotonic()
    left = drain_and_shutdown(httpd, timeout=0.3)
    assert left == 1
    assert time.monotonic() - t0 < 3.0


# -- walker skip accounting --------------------------------------------------


def test_toctou_file_deleted_between_walk_and_read(tmp_path):
    """TOCTOU: a file vanishes after the walker yields it but before the
    analyzer reads it — the scan completes, the skip is counted in the
    report summary, other findings are unaffected."""
    from trivy_tpu.artifact.local_fs import ArtifactOption, LocalFSArtifact
    from trivy_tpu.cache import new_cache
    from trivy_tpu.scanner import ScanOptions, Scanner
    from trivy_tpu.scanner.local_driver import LocalDriver

    (tmp_path / "gh.txt").write_text(f"token {GHP} end\n")
    (tmp_path / "victim.txt").write_text("about to vanish\n")
    cache = new_cache("fs", str(tmp_path / "cache"))
    artifact = LocalFSArtifact(
        str(tmp_path), cache, ArtifactOption(backend="cpu")
    )
    real_walk = artifact.walker.walk

    def walk_and_delete(root):
        for rel, info, opener in real_walk(root):
            if rel == "victim.txt":
                os.remove(os.path.join(root, rel))
            yield rel, info, opener

    artifact.walker.walk = walk_and_delete
    report = Scanner(artifact, LocalDriver(cache)).scan_artifact(
        ScanOptions(scanners=["secret"])
    )
    assert report.metadata.get("SkippedFiles") == 1
    assert not report.degraded
    assert [r.target for r in report.results] == ["gh.txt"]


def test_walker_read_fault_counts_skip(tmp_path):
    from trivy_tpu.artifact.local_fs import ArtifactOption, LocalFSArtifact
    from trivy_tpu.cache import new_cache
    from trivy_tpu.scanner import ScanOptions, Scanner
    from trivy_tpu.scanner.local_driver import LocalDriver

    (tmp_path / "a.txt").write_text("hello world, nothing secret\n")
    (tmp_path / "gh.txt").write_text(f"token {GHP} end\n")
    faults.configure("walker.read@a.txt:times=-1:error=io")
    cache = new_cache("fs", str(tmp_path / "cache"))
    artifact = LocalFSArtifact(
        str(tmp_path), cache, ArtifactOption(backend="cpu")
    )
    report = Scanner(artifact, LocalDriver(cache)).scan_artifact(
        ScanOptions(scanners=["secret"])
    )
    assert report.metadata.get("SkippedFiles") == 1
    assert [r.target for r in report.results] == ["gh.txt"]


def test_walker_counts_stat_and_walk_errors(tmp_path, monkeypatch):
    from trivy_tpu.fanal.walker import FSWalker

    (tmp_path / "ok.txt").write_text("x")
    (tmp_path / "gone.txt").write_text("y")
    real_lstat = os.lstat

    def flaky_lstat(path, *a, **kw):
        if path.endswith("gone.txt"):
            raise OSError(5, "stat failed")
        return real_lstat(path, *a, **kw)

    monkeypatch.setattr(os, "lstat", flaky_lstat)
    w = FSWalker()
    seen = [rel for rel, _, _ in w.walk(str(tmp_path))]
    assert seen == ["ok.txt"]
    assert w.skipped == 1


# -- misconf failure domain --------------------------------------------------


def test_misconf_one_crashing_file_does_not_kill_the_batch():
    from trivy_tpu.misconf.scanner import MisconfScanner

    dockerfile = b"FROM alpine:3.18\nUSER root\nADD . /app\n"
    files = [
        ("a/Dockerfile", dockerfile),
        ("b/Dockerfile", dockerfile),
    ]
    baseline = MisconfScanner().scan_files(files)
    assert {m.file_path for m in baseline} == {"a/Dockerfile", "b/Dockerfile"}
    faults.configure("misconf.eval@a/Dockerfile:times=-1")
    got = MisconfScanner().scan_files(files)
    assert {m.file_path for m in got} == {"b/Dockerfile"}


# -- e2e: the fs scan acceptance path ----------------------------------------


def run_cli(*args):
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "trivy_tpu.cli", *args],
        capture_output=True, text=True, env=env, cwd="/root/repo",
    )


def test_e2e_fs_all_devices_dead_host_fallback(tmp_path):
    """Acceptance: with every device scripted dead, the fs e2e scan
    completes via host fallback with findings identical to the CPU backend
    and ``Degraded: true`` in the summary."""
    from trivy_tpu.artifact.local_fs import ArtifactOption, LocalFSArtifact
    from trivy_tpu.cache import new_cache
    from trivy_tpu.scanner import ScanOptions, Scanner
    from trivy_tpu.scanner.local_driver import LocalDriver

    tree = tmp_path / "tree"
    (tree / "src").mkdir(parents=True)
    (tree / "src" / "gh.txt").write_text(f"token {GHP} end\n")
    (tree / "src" / "clean.py").write_text("print('hello')\n")
    # oracle findings from the in-process CPU backend (same Results schema
    # the CLI emits; one subprocess is enough for the degraded leg)
    cache = new_cache("fs", str(tmp_path / "c1"))
    artifact = LocalFSArtifact(str(tree), cache, ArtifactOption(backend="cpu"))
    base = Scanner(artifact, LocalDriver(cache)).scan_artifact(
        ScanOptions(scanners=["secret"])
    )
    assert not base.degraded
    dead = run_cli(
        "fs", "--scanners", "secret", "--backend", "auto", "--format", "json",
        "--fault-inject", "device.dispatch:times=-1",
        "--cache-dir", str(tmp_path / "c2"), str(tree),
    )
    assert dead.returncode == 0, dead.stderr
    doc_dead = json.loads(dead.stdout)
    assert doc_dead.get("Degraded") is True
    assert doc_dead["Results"] == [r.to_dict() for r in base.results]
    assert "host confirm path" in dead.stderr
