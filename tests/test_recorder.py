"""Flight recorder + device-lane forensics: ring bounds under flood,
disjoint per-scan rings, compile/HBM ledger consistency, recompile-storm
detection, diagnostic-bundle schema + gzip round-trip + retention,
auto-emit on an injected ``device.dispatch`` fault (verdict names the
site), the /healthz forensics fields, the token-gated ``GET
/debug/bundle`` route, the explicit ``POST /fleet/deregister`` inverse of
register, and the recorder's no-threads / zero-cost-when-off discipline.
"""

import gzip
import json
import os
import threading

import pytest

from trivy_tpu import faults, obs
from trivy_tpu.fleet.coordinator import FleetConfig, FleetCoordinator
from trivy_tpu.obs import recorder
from trivy_tpu.rpc.admission import resolve_admission
from trivy_tpu.rpc.client import (
    RPCError,
    fetch_debug_bundle,
    post_deregister,
)
from trivy_tpu.rpc.server import start_server
from trivy_tpu.scanner import ScanOptions

GHP = "ghp_" + "A1b2C3d4E5f6G7h8I9j0K1l2M3n4O5p6Q7r8"[:36]

SO = ScanOptions(scanners=["secret"])


@pytest.fixture(autouse=True)
def _fresh_recorder():
    """Every test starts from a clean recorder state (fresh rings and
    ledgers, env re-read) and leaves it clean, with faults disarmed."""
    recorder.configure()
    yield
    faults.clear()
    recorder.configure()


@pytest.fixture(autouse=True)
def _recorder_never_threads():
    """The recorder itself must never start a thread in any mode: the
    ring is passive memory written in-line by its callers."""
    before = {t.ident for t in threading.enumerate()}
    yield
    new = [
        t.name for t in threading.enumerate()
        if t.ident not in before and t.is_alive()
        and ("record" in t.name.lower() or "flight" in t.name.lower())
    ]
    assert not new, f"recorder-looking thread(s) leaked: {new}"


@pytest.fixture(scope="module")
def scanner():
    from trivy_tpu.secret.tpu_scanner import TpuSecretScanner

    return TpuSecretScanner(batch_size=16)


def _files(n=6):
    return [
        (f"pkg{i}/cred.txt", f"svc{i} token {GHP}\n".encode() * 24)
        for i in range(n)
    ]


# -- ring bounds --------------------------------------------------------------


class TestRingBounds:
    def test_flood_stays_within_event_and_byte_caps(self):
        ring = recorder.Ring()
        payload = "x" * recorder.DETAIL_MAX_CHARS
        for i in range(recorder.RING_MAX_EVENTS * 8):
            ring.append({
                "t": float(i), "kind": "flood", "what": f"ev-{i}",
                "trace": "0" * 8, "detail": {"payload": payload},
            })
        assert len(ring) <= recorder.RING_MAX_EVENTS
        assert ring.approx_bytes() <= recorder.ring_bytes()
        assert ring.dropped > 0
        # newest survive, oldest evict
        events = ring.snapshot()
        assert events[-1]["what"] == f"ev-{recorder.RING_MAX_EVENTS * 8 - 1}"
        assert events[0]["what"] != "ev-0"

    def test_byte_bound_bites_before_count_on_huge_events(self):
        """A flood of max-size events must be evicted by BYTES, not just
        count — the byte bound is the giant-detail backstop."""
        ring = recorder.Ring(max_events=10**6, max_bytes=64 * 1024)
        for i in range(4096):
            ring.append({
                "t": float(i), "kind": "flood", "what": "w" * 64,
                "trace": "0" * 8,
                "detail": {"d": "y" * recorder.DETAIL_MAX_CHARS},
            })
        assert ring.approx_bytes() <= 64 * 1024
        assert len(ring) < 4096

    def test_record_truncates_oversized_detail_values(self):
        with obs.scan_context(name="trunc", enabled=False) as ctx:
            recorder.record(
                "error", "boom", {"repr": "z" * 10_000}, ctx=ctx,
            )
            ev = recorder._ctx_ring(ctx).snapshot()[-1]
        assert len(ev["detail"]["repr"]) == recorder.DETAIL_MAX_CHARS

    def test_record_is_noop_when_disabled(self):
        recorder.configure(enabled_override=False)
        assert recorder._STATE is None
        recorder.record("fault", "should-vanish")
        assert recorder._STATE is None
        assert obs._flight_hook is None


# -- disjoint per-scan rings --------------------------------------------------


class TestDisjointRings:
    def test_concurrent_scans_keep_disjoint_rings(self):
        """Two scan contexts recording concurrently must not bleed events
        into each other's ring (the process ring sees both)."""
        errs = []
        barrier = threading.Barrier(2)

        def run(tag):
            try:
                with obs.scan_context(name=tag, enabled=False) as ctx:
                    barrier.wait(timeout=10)
                    for i in range(64):
                        recorder.record(
                            "retry", f"{tag}-ev-{i}", ctx=ctx,
                        )
                    whats = {
                        e["what"]
                        for e in recorder._ctx_ring(ctx).snapshot()
                    }
                    assert whats == {f"{tag}-ev-{i}" for i in range(64)}
            except Exception as e:  # surfaced below, not swallowed
                errs.append(e)

        threads = [
            threading.Thread(target=run, args=(tag,), name=f"scan-{tag}")
            for tag in ("alpha", "beta")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errs, errs
        process = {
            e["what"] for e in recorder._STATE.ring.snapshot()
        }
        assert "alpha-ev-0" in process and "beta-ev-0" in process


# -- compile ledger -----------------------------------------------------------


class TestCompileLedger:
    def test_instrument_jit_counts_once_per_shape_bucket(self):
        import jax.numpy as jnp

        fn = recorder.instrument_jit("probe", lambda x: x + 1)
        before = recorder.compile_count()
        for _ in range(3):  # re-calls on a seen bucket add nothing
            fn(jnp.ones((4,), jnp.float32))
            fn(jnp.ones((8,), jnp.float32))
        assert recorder.compile_count() - before == 2
        dev = recorder.device_doc()
        assert dev["compiles"]["probe"]["count"] == 2
        assert dev["compiles"]["probe"]["wall_s"] >= 0
        assert sum(
            n for k, n in dev["shape_buckets"].items()
            if k.startswith("probe|")
        ) == 2

    def test_compile_counter_parity_across_dispatch_paths(self):
        """Parity gate: the SAME kernel body driven through two
        instrumented entry points (the plain CPU path and a mesh-style
        stage wrapper) must land identical per-kernel counts and
        shape-bucket sets — the ledger attributes compiles to shapes, not
        to which wrapper dispatched them."""
        import jax.numpy as jnp

        body = lambda x: x * 2  # noqa: E731
        cpu_fn = recorder.instrument_jit("parity.cpu", body)
        mesh_fn = recorder.instrument_jit("parity.mesh", body)
        shapes = [(4,), (8,), (16,)]
        for s in shapes:
            cpu_fn(jnp.ones(s, jnp.float32))
            mesh_fn(jnp.ones(s, jnp.float32))
        dev = recorder.device_doc()
        assert (
            dev["compiles"]["parity.cpu"]["count"]
            == dev["compiles"]["parity.mesh"]["count"]
            == len(shapes)
        )
        cpu_buckets = {
            k.split("|", 1)[1]
            for k in dev["shape_buckets"] if k.startswith("parity.cpu|")
        }
        mesh_buckets = {
            k.split("|", 1)[1]
            for k in dev["shape_buckets"] if k.startswith("parity.mesh|")
        }
        assert cpu_buckets == mesh_buckets
        assert dev["compile_total"] == recorder.compile_count()

    def test_instrument_jit_is_bare_when_disabled(self):
        recorder.configure(enabled_override=False)
        import jax.numpy as jnp

        fn = recorder.instrument_jit("off-probe", lambda x: x + 1)
        fn(jnp.ones((4,), jnp.float32))
        assert recorder.compile_count() == 0
        assert recorder.device_doc() is None

    def test_recompile_storm_fires_exactly_once(self, monkeypatch):
        import jax.numpy as jnp

        monkeypatch.setenv(recorder.ENV_STORM, "2")
        recorder.configure()
        fn = recorder.instrument_jit("stormy", lambda x: x - 1)
        for n in range(1, 6):  # 5 distinct shapes, threshold 2
            fn(jnp.ones((n,), jnp.float32))
        assert recorder.storm_count() == 1
        storm_events = [
            e for e in recorder._STATE.ring.snapshot()
            if e["kind"] == "storm" and e["what"] == "stormy"
        ]
        assert len(storm_events) == 1
        assert recorder.device_doc()["recompile_storms"] == ["stormy"]

    def test_hbm_ledger_and_live_fragment(self):
        recorder.note_resident("corpus", 1 << 20)
        recorder.note_resident("cve", 2 << 20)
        recorder.release_resident("corpus", 1 << 20)
        dev = recorder.device_doc()
        assert dev["hbm"]["resident_bytes"] == {"corpus": 0, "cve": 2 << 20}
        assert dev["hbm"]["resident_total_bytes"] == 2 << 20
        assert 0.0 < recorder.hbm_ratio() <= 1.0
        frag = recorder.live_fragment()
        assert frag.startswith("compiles 0 hbm") or "hbm" in frag


    @pytest.mark.parametrize(
        "platform,stats,want",
        [
            ("cpu", None, 1024 << 20),  # TRIVY_TPU_HBM_BUDGET_MB stands in
            ("tpu", {"bytes_limit": 16 << 30}, 16 << 30),
            ("tpu", {}, RuntimeError),  # an accelerator must report it
        ],
    )
    def test_device_capacity(self, monkeypatch, platform, stats, want):
        import jax

        class Dev:
            id = 0
            device_kind = "TPU v5 lite"

            def __init__(self):
                self.platform = platform

            def memory_stats(self):
                return stats

        monkeypatch.setattr(jax, "local_devices", lambda: [Dev()])
        monkeypatch.delenv("TRIVY_TPU_HBM_BUDGET_MB", raising=False)
        st = recorder._State()
        if want is RuntimeError:
            with pytest.raises(RuntimeError, match="bytes_limit"):
                st.capacity_bytes()
        else:
            assert st.capacity_bytes() == want


# -- diagnostic bundles -------------------------------------------------------


class TestBundles:
    def test_schema_and_gzip_round_trip(self, tmp_path):
        with obs.scan_context(name="rt", enabled=False) as ctx:
            recorder.record("retry", "batch 3", {"n": 1}, ctx=ctx)
            doc = recorder.build_bundle(ctx=ctx, reason="on-demand")
        assert doc["schema"] == recorder.BUNDLE_SCHEMA
        assert doc["reason"] == "on-demand"
        assert doc["trace_id"] == ctx.trace_id
        assert any(e["what"] == "batch 3" for e in doc["events"])
        path = recorder.write_bundle(doc, str(tmp_path))
        assert path.endswith(".json.gz")
        with gzip.open(path, "rt") as f:  # genuinely gzipped on disk
            assert json.load(f) == doc
        assert recorder.read_bundle(path) == doc

    def test_retention_keeps_newest(self, tmp_path):
        with obs.scan_context(name="keep", enabled=False) as ctx:
            doc = recorder.build_bundle(ctx=ctx, reason="on-demand")
        paths = []
        for seq in range(7):
            paths.append(recorder.write_bundle(
                {**doc, "seq": seq}, str(tmp_path), keep=3
            ))
        left = sorted(os.listdir(tmp_path))
        assert len(left) == 3
        assert os.path.basename(paths[-1]) in left
        # the survivors are the NEWEST three bundles (file names may be
        # recycled after retention deletes, so compare contents)
        seqs = sorted(
            recorder.read_bundle(os.path.join(tmp_path, name))["seq"]
            for name in left
        )
        assert seqs == [4, 5, 6]

    def test_auto_emit_on_injected_fault_names_site(self, tmp_path,
                                                    scanner):
        """The chaos acceptance seam in-process: a scripted
        ``device.dispatch`` fault lands in the ring (faults.py records it
        before raising), and the auto-emitted bundle's machine verdict
        names that site as the first anomalous event."""
        recorder.set_debug_dir(str(tmp_path))
        faults.configure("device.dispatch:at=1:times=2")
        try:
            with obs.scan_context(name="chaos", enabled=False) as ctx:
                n = sum(
                    len(s.findings) for s in scanner.scan_files(_files())
                )
                path = recorder.auto_emit("degraded-completion", ctx=ctx)
        finally:
            faults.clear()
        assert n > 0  # the retry ladder absorbed the fault
        assert path is not None
        doc = recorder.read_bundle(path)
        assert doc["reason"] == "degraded-completion"
        assert "device.dispatch" in doc["verdict"]
        assert "fault" in doc["verdict"]
        assert any(e["kind"] == "fault" for e in doc["events"])

    def test_auto_emit_once_per_scan_and_reason(self, tmp_path):
        recorder.set_debug_dir(str(tmp_path))
        with obs.scan_context(name="dedupe", enabled=False) as ctx:
            first = recorder.auto_emit("breaker-trip", ctx=ctx)
            second = recorder.auto_emit("breaker-trip", ctx=ctx)
            other = recorder.auto_emit("terminal-failure", ctx=ctx)
        assert first is not None and os.path.exists(first)
        assert second is None
        assert other is not None and other != first

    def test_auto_emit_noop_without_debug_dir(self):
        assert recorder.debug_dir() == ""
        with obs.scan_context(name="nodir", enabled=False) as ctx:
            assert recorder.auto_emit("terminal-failure", ctx=ctx) is None

    def test_verdict_prefers_severe_kind_in_tie_window(self):
        """A fault and the degrade it causes land near-simultaneously;
        the verdict must name the fault (the cause), not the symptom."""
        with obs.scan_context(name="tie", enabled=False) as ctx:
            recorder.record("degrade", "host fallback", ctx=ctx)
            recorder.record("fault", "device.dispatch@d0", ctx=ctx)
            doc = recorder.build_bundle(ctx=ctx, reason="on-demand")
        assert "fault device.dispatch@d0" in doc["verdict"]


# -- /healthz forensics + GET /debug/bundle -----------------------------------


class TestServerSurfaces:
    def test_healthz_doc_fields(self):
        recorder.record("fault", "device.dispatch@d2")
        recorder.record("degrade", "scan fell back to host")
        recorder.record("breaker", "device d1 OPEN")
        recorder.record("breaker", "device d1 closed")
        doc = recorder.healthz_doc()
        assert doc["LastError"]["Event"] == "fault device.dispatch@d2"
        assert doc["LastDegraded"]["Event"] == (
            "degrade scan fell back to host"
        )
        # the trip field reports the last OPEN, not the close after it
        assert doc["LastBreakerTrip"]["Event"] == "breaker device d1 OPEN"
        assert "T" in doc["LastError"]["Time"]

    def test_healthz_route_carries_forensics(self):
        import urllib.request

        recorder.record("fault", "device.dispatch@d0")
        httpd, port = start_server(cache_dir=None)
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10
            ) as resp:
                doc = json.load(resp)
        finally:
            httpd.shutdown()
        assert doc["LastError"]["Event"] == "fault device.dispatch@d0"

    def test_debug_bundle_route_and_token_gate(self):
        recorder.record("oom", "arena slab 3")
        httpd, port = start_server(cache_dir=None, token="sekrit")
        host = f"127.0.0.1:{port}"
        try:
            with pytest.raises(RPCError, match="403"):
                fetch_debug_bundle(host, token="wrong")
            doc = fetch_debug_bundle(host, token="sekrit")
        finally:
            httpd.shutdown()
        assert doc["schema"] == recorder.BUNDLE_SCHEMA
        assert doc["reason"] == "on-demand"
        events = doc.get("events") or doc.get("process_events") or []
        assert any(e["what"] == "arena slab 3" for e in events)

    def test_debug_bundle_route_404_when_disabled(self):
        recorder.configure(enabled_override=False)
        httpd, port = start_server(cache_dir=None)
        try:
            with pytest.raises(RPCError, match="404"):
                fetch_debug_bundle(f"127.0.0.1:{port}")
        finally:
            httpd.shutdown()


# -- POST /fleet/deregister ---------------------------------------------------


def _coordinator(hosts):
    return FleetCoordinator(
        FleetConfig(hosts=list(hosts), telemetry_interval=0.0), SO
    )


def _server():
    httpd, port = start_server(
        cache_dir=None,
        admission=resolve_admission({"max_concurrent_scans": 2}),
    )
    return httpd, f"127.0.0.1:{port}"


class TestDeregisterSeam:
    def test_route_is_404_without_a_hook(self):
        httpd, host = _server()
        try:
            assert httpd.service.fleet_deregister_hook is None
            with pytest.raises(RPCError, match="404"):
                post_deregister(host, "127.0.0.1:1", retries=0)
        finally:
            httpd.shutdown()

    def test_http_roundtrip_token_and_idempotency(self):
        """The explicit inverse of register: wrong token → 403; good
        token → the replica drains (queued shards re-scatter); a
        duplicate re-POST (the leaver's retry ladder) answers Draining
        without error; an unknown host is a no-op answer, not a 502."""
        coord_httpd, coord_host = _server()
        replica_httpd, replica_host = _server()
        other_httpd, other_host = _server()
        try:
            coord = _coordinator([replica_host, other_host])
            coord_httpd.service.fleet_deregister_hook = (
                coord.deregister_replica
            )
            coord_httpd.service.fleet_register_token = "sekrit"
            with pytest.raises(RPCError, match="403"):
                post_deregister(
                    coord_host, replica_host, token="wrong", retries=0
                )
            assert coord._draining == [False, False]
            doc = post_deregister(coord_host, replica_host, token="sekrit")
            assert doc == {
                "Host": replica_host, "Known": True, "Draining": True,
                "Replicas": 2,
            }
            assert coord._draining == [True, False]
            dup = post_deregister(coord_host, replica_host, token="sekrit")
            assert dup["Draining"] is True
            assert coord._draining == [True, False]
            unknown = post_deregister(
                coord_host, "127.0.0.1:1", token="sekrit"
            )
            assert unknown == {
                "Host": "127.0.0.1:1", "Known": False, "Replicas": 2,
            }
        finally:
            for h in (coord_httpd, replica_httpd, other_httpd):
                h.shutdown()

    def test_deregister_allowed_while_coordinator_drains(self):
        """Deliberately NOT refused while the serving process drains: a
        winding-down coordinator must still let replicas leave cleanly
        (register, by contrast, refuses new joiners with a 503)."""
        coord_httpd, coord_host = _server()
        replica_httpd, replica_host = _server()
        try:
            coord = _coordinator([replica_host])
            coord_httpd.service.fleet_deregister_hook = (
                coord.deregister_replica
            )
            coord_httpd.service.draining = True
            doc = post_deregister(coord_host, replica_host)
            assert doc["Draining"] is True
        finally:
            for h in (coord_httpd, replica_httpd):
                h.shutdown()

    def test_bad_body_is_400(self):
        httpd, host = _server()
        try:
            httpd.service.fleet_deregister_hook = lambda h: {"Host": h}
            with pytest.raises(RPCError, match="400"):
                post_deregister(host, "", retries=0)
        finally:
            httpd.shutdown()

    def test_deregister_records_drain_event(self):
        replica_httpd, replica_host = _server()
        try:
            coord = _coordinator([replica_host])
            coord.deregister_replica(replica_host)
            drains = [
                e for e in recorder._STATE.ring.snapshot()
                if e["kind"] == "fleet" and "drain" in e["what"]
            ]
            assert drains, "deregister left no fleet drain event"
        finally:
            replica_httpd.shutdown()


# -- end-to-end counter parity across a real scan -----------------------------


class TestScanIntegration:
    def test_scan_feeds_ledger_and_counter_tracks(self, scanner):
        """A real (tiny) scan with a fresh recorder: the compile ledger,
        ``compile_total`` parity, and the Perfetto counter series must
        all agree; a warm second scan adds zero new compiles."""
        with obs.scan_context(name="ledger", enabled=False) as ctx:
            n = sum(len(s.findings) for s in scanner.scan_files(_files()))
        assert n > 0
        first = recorder.compile_count()
        dev = recorder.device_doc()
        if dev is not None:
            assert dev["compile_total"] == first
        series = recorder.counter_series(ctx)
        if first and series.get("device.compiles_total"):
            pts = series["device.compiles_total"]["points"]
            assert pts[-1][1] <= first
        scanner.clear_hit_cache()
        with obs.scan_context(name="ledger2", enabled=False):
            sum(len(s.findings) for s in scanner.scan_files(_files()))
        assert recorder.compile_count() == first, (
            "a warm re-scan recompiled kernels (shape-bucket leak)"
        )
