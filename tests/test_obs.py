"""Observability subsystem: span nesting/parenting, per-scan isolation,
Chrome-trace export schema, stall attribution, the Prometheus registry,
the module-level span surface, and JSON logging."""

import io
import json
import threading
import time

from trivy_tpu import log, obs
from trivy_tpu.obs import export, metrics, stall


class TestTraceContext:
    def test_span_nesting_records_parent_ids(self):
        ctx = obs.TraceContext(name="t", enabled=True)
        with ctx.span("a") as sa:
            with ctx.span("a.b") as sb:
                assert sb.parent_id == sa.span_id
                with ctx.span("a.b.c") as sc:
                    assert sc.parent_id == sb.span_id
            with ctx.span("a.d") as sd:
                assert sd.parent_id == sa.span_id
        assert sa.parent_id is None
        assert {s.name for s in ctx.events} == {"a", "a.b", "a.b.c", "a.d"}
        # durations nest: the parent covers its children
        by_name = {s.name: s for s in ctx.events}
        assert by_name["a"].duration >= by_name["a.b"].duration

    def test_disabled_context_records_nothing(self):
        ctx = obs.TraceContext(enabled=False)
        with ctx.span("x"):
            pass
        ctx.add("y", 1.0)
        ctx.count("c")
        ctx.sample("s", 3)
        assert not ctx.events and not ctx.counters and not ctx.samples
        # the no-op span is a shared singleton: no per-call allocation
        assert ctx.span("x") is ctx.span("y")

    def test_add_and_percentiles(self):
        ctx = obs.TraceContext(enabled=True)
        for ms in (1, 2, 3, 4, 100):
            ctx.add("stage", ms / 1000.0)
        s = ctx.stage_stats()["stage"]
        assert s["count"] == 5
        assert s["max"] == 0.1
        assert s["p50"] == 0.003
        assert abs(s["total"] - 0.11) < 1e-9

    def test_event_cap_is_not_silent(self, monkeypatch):
        monkeypatch.setattr(obs, "MAX_EVENTS", 4)
        ctx = obs.TraceContext(enabled=True)
        for _ in range(10):
            ctx.add("s", 0.001)
        assert len(ctx.events) == 4
        assert ctx.dropped_events == 6
        # aggregates stay complete and the report mentions the drop
        assert ctx.stage_stats()["s"]["count"] == 10
        buf = io.StringIO()
        ctx.report(buf)
        assert "dropped" in buf.getvalue()

    def test_duration_memory_is_bounded(self):
        """Past the reservoir size, per-stage storage stays bounded while
        count/total/max remain exact (a traced multi-million-file scan must
        not hold one float per file)."""
        ctx = obs.TraceContext(enabled=True)
        n = obs.RESERVOIR + 500
        for _ in range(n):
            ctx.add("s", 0.001)
        agg = ctx.durations["s"]
        assert len(agg.values) == obs.RESERVOIR
        s = ctx.stage_stats()["s"]
        assert s["count"] == n
        assert abs(s["total"] - n * 0.001) < 1e-6
        assert s["max"] == 0.001
        # samples are bounded the same way, with exact running stats
        for i in range(obs.MAX_SAMPLES + 100):
            ctx.sample("q", i % 7)
        count, total, vmax, raw = ctx.samples["q"]
        assert count == obs.MAX_SAMPLES + 100
        assert vmax == 6
        assert len(raw) == obs.MAX_SAMPLES

    def test_per_scan_isolation_under_two_threads(self):
        seen = {}
        barrier = threading.Barrier(2)

        def scan(tag):
            with obs.scan_context(name=tag, enabled=True) as ctx:
                with obs.span(f"{tag}.work"):
                    barrier.wait(timeout=5)  # both scans record concurrently
                obs.count(f"{tag}.count")
                seen[tag] = ctx

        threads = [
            threading.Thread(target=scan, args=(t,)) for t in ("s1", "s2")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert seen["s1"].trace_id != seen["s2"].trace_id
        assert [s.name for s in seen["s1"].events] == ["s1.work"]
        assert [s.name for s in seen["s2"].events] == ["s2.work"]
        assert seen["s1"].counters == {"s1.count": 1}
        assert seen["s2"].counters == {"s2.count": 1}

    def test_activate_carries_context_into_worker_thread(self):
        with obs.scan_context(name="outer", enabled=True) as ctx:
            def worker():
                with obs.activate(ctx):
                    obs.span("w.span").__class__  # touch module surface
                    with obs.span("w.span"):
                        pass

            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=5)
        assert "w.span" in ctx.durations


class TestStallAttribution:
    def test_percentages_sum_to_100(self):
        ctx = obs.TraceContext(enabled=True)
        ctx.add("secret.feed_wait", 0.72)
        ctx.add("secret.device_wait", 0.181)
        ctx.add("secret.confirm", 0.099)
        att = stall.attribution(ctx)
        assert set(att) == {"secret"}
        assert sum(att["secret"].values()) == 100
        assert att["secret"]["feed-starved"] == 72

    def test_verdict_line_format_and_multiple_pipelines(self):
        ctx = obs.TraceContext(enabled=True)
        ctx.add("secret.device_wait", 0.3)
        ctx.add("secret.confirm", 0.1)
        ctx.add("license.dispatch", 0.5)
        ctx.add("misconf.scan_files", 0.4)  # unbucketed stage: no verdict
        lines = stall.verdict_lines(ctx)
        assert any(l.startswith("secret: ") for l in lines)
        assert any(l == "license: upload-bound 100%" for l in lines)
        assert not any(l.startswith("misconf") for l in lines)

    def test_mesh_stream_stages_bucket_by_suffix(self):
        ctx = obs.TraceContext(enabled=True)
        ctx.add("mesh.d0.dispatch", 0.25)
        ctx.add("mesh.d1.dispatch", 0.75)
        att = stall.attribution(ctx)
        assert att["mesh"] == {"upload-bound": 100}

    def test_pooled_stage_time_normalized_by_thread_count(self):
        """Confirm-pool spans sum across N concurrent workers (up to N× wall
        time); attribution divides by the recording-thread count so an
        overlapped pool cannot dwarf the serial device-loop stages."""
        ctx = obs.TraceContext(enabled=True)
        # serial device thread: 1s of device wait
        ctx.add("secret.device_wait", 1.0)
        # 4 pool threads each spent 0.5s confirming (2.0s summed, 0.5s/worker)
        # — alive concurrently (a barrier): thread idents are reused once a
        # thread exits, which would undercount the distinct-worker set
        barrier = threading.Barrier(4)

        def confirm():
            ctx.add("secret.confirm", 0.5)
            barrier.wait(timeout=5)

        threads = [threading.Thread(target=confirm) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert ctx.durations["secret.confirm"].count == 4
        att = stall.attribution(ctx)["secret"]
        # 1.0 vs 2.0/4 = 0.5 -> 67/33, not the raw-sum 33/67 inversion
        assert att["device-bound"] > att["confirm-bound"]
        assert sum(att.values()) == 100


class TestChromeTraceExport:
    def test_schema(self, tmp_path):
        ctx = obs.TraceContext(name="unit", enabled=True)
        with ctx.span("secret.dispatch"):
            with ctx.span("secret.device_wait"):
                time.sleep(0.001)
        ctx.add("walk.next", 0.002)
        path = tmp_path / "trace.json"
        export.write_chrome_trace(ctx, str(path))
        doc = json.loads(path.read_text())
        assert isinstance(doc["traceEvents"], list)
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        ms = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert len(xs) == 3
        for e in xs:
            assert {"name", "cat", "ph", "pid", "tid", "ts", "dur", "args"} <= set(e)
            assert e["ts"] >= 0 and e["dur"] >= 0
        # one named track per stage (thread_name metadata), plus process_name
        names = {e["args"]["name"] for e in ms if e["name"] == "thread_name"}
        assert names == {"secret.dispatch", "secret.device_wait", "walk.next"}
        assert any(e["name"] == "process_name" for e in ms)
        # parenting survives export
        child = next(e for e in xs if e["name"] == "secret.device_wait")
        parent = next(e for e in xs if e["name"] == "secret.dispatch")
        assert child["args"]["parent_span_id"] == parent["args"]["span_id"]

    def test_metrics_json(self, tmp_path):
        ctx = obs.TraceContext(enabled=True)
        ctx.add("secret.device_wait", 0.05)
        ctx.count("secret.bytes_uploaded", 1024)
        ctx.sample("secret.queue_depth", 2)
        path = tmp_path / "metrics.json"
        export.write_metrics_json(ctx, str(path))
        doc = json.loads(path.read_text())
        assert doc["spans"]["secret.device_wait"]["count"] == 1
        assert doc["counters"]["secret.bytes_uploaded"] == 1024
        assert doc["samples"]["secret.queue_depth"]["max"] == 2
        assert doc["stall"]["secret"] == {"device-bound": 100}


class TestRegistry:
    def test_counter_gauge_histogram_render(self):
        r = metrics.Registry()
        c = r.counter("x_total", "things", labelnames=("kind",))
        c.inc(kind="a")
        c.inc(2, kind="a")
        g = r.gauge("x_inflight", "gauge")
        g.inc()
        h = r.histogram("x_seconds", "latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(5.0)
        text = r.render()
        assert '# TYPE x_total counter' in text
        assert 'x_total{kind="a"} 3' in text
        assert 'x_inflight 1' in text
        assert 'x_seconds_bucket{le="0.1"} 1' in text
        assert 'x_seconds_bucket{le="+Inf"} 2' in text
        assert 'x_seconds_count 2' in text

    def test_get_or_create_idempotent_and_kind_checked(self):
        import pytest

        r = metrics.Registry()
        assert r.counter("a_total") is r.counter("a_total")
        with pytest.raises(ValueError):
            r.gauge("a_total")


class TestCompatShim:
    """The module-level surface (``obs.span``/``add``/``count``/``report``/
    ``enable``) that the old flat ``trace`` module offered, routed to the
    current context."""

    def test_trace_module_routes_to_current_context(self):
        with obs.scan_context(name="shim", enabled=True) as ctx:
            assert obs.enabled()
            with obs.span("unit.shim.span"):
                pass
            obs.add("unit.shim.add", 0.5)
            obs.count("unit.shim.count", 3)
            buf = io.StringIO()
            obs.report(buf)
            out = buf.getvalue()
            assert "unit.shim.span" in out and "unit.shim.add" in out
            assert ctx.counters["unit.shim.count"] == 3
            obs.current().reset()
            assert not ctx.durations and not ctx.counters

    def test_global_enable_disable(self):
        obs.enable()
        try:
            assert obs.current().enabled
        finally:
            obs.disable()
            obs.current().reset()
        assert not obs.current().enabled


class TestJsonLogging:
    import pytest

    @pytest.fixture(autouse=True)
    def _pristine_logger(self):
        """log.init sets propagate=False on the trivy_tpu logger; restore
        the untouched state afterwards so later caplog-based tests (which
        need propagation to the root logger) still capture records."""
        import logging

        root = logging.getLogger("trivy_tpu")
        saved = (list(root.handlers), root.propagate, root.level)
        yield
        for h in list(root.handlers):
            root.removeHandler(h)
        for h in saved[0]:
            root.addHandler(h)
        root.propagate = saved[1]
        root.setLevel(saved[2])

    def test_one_json_object_per_line(self):
        buf = io.StringIO()
        log.init(stream=buf, fmt="json")
        log.logger("rpc:server").info("listening on %s:%d", "0.0.0.0", 80)
        line = buf.getvalue().strip()
        doc = json.loads(line)
        assert doc["level"] == "INFO"
        assert doc["subsystem"] == "rpc:server"
        assert doc["msg"] == "listening on 0.0.0.0:80"
        # UTC instant with explicit zone, e.g. 2026-08-03T09:00:00.123Z
        assert "T" in doc["ts"] and doc["ts"].endswith("Z")

    def test_plain_stays_default(self):
        buf = io.StringIO()
        log.init(stream=buf)
        log.logger("x").info("hello")
        assert "[trivy_tpu.x] hello" in buf.getvalue()

    def test_json_lines_carry_active_trace_id(self):
        """Log lines emitted inside a scan carry that scan's trace id —
        the same id a client's traceparent propagated — so server logs
        correlate with client traces."""
        buf = io.StringIO()
        log.init(stream=buf, fmt="json")
        with obs.scan_context(name="corr", enabled=True) as ctx:
            log.logger("rpc:server").info("mid-scan line")
        log.logger("rpc:server").info("post-scan line")
        lines = [json.loads(l) for l in buf.getvalue().strip().splitlines()]
        assert lines[0]["trace_id"] == ctx.trace_id
        # outside the scan the process-default context's id applies
        assert lines[1]["trace_id"] != ctx.trace_id


class TestHeartbeat:
    # a plain stdlib logger: the trivy_tpu root logger sets propagate=False
    # once log.init runs, which would hide records from caplog

    def test_logs_progress_lines(self, caplog):
        import logging

        lg = logging.getLogger("obs-heartbeat-test")
        with caplog.at_level(logging.INFO, logger="obs-heartbeat-test"):
            with obs.heartbeat(lg, "unit op", interval=0.05,
                               progress=lambda: "3 files"):
                time.sleep(0.2)
        msgs = [r.message for r in caplog.records if "unit op" in r.message]
        assert msgs and "3 files" in msgs[0]

    def test_short_block_logs_nothing(self, caplog):
        import logging

        lg = logging.getLogger("obs-heartbeat-test2")
        with caplog.at_level(logging.INFO, logger="obs-heartbeat-test2"):
            with obs.heartbeat(lg, "fast op", interval=30.0):
                pass
        assert not [r for r in caplog.records if "fast op" in r.message]

    def test_beats_include_trace_id(self, caplog):
        """Server operators correlate a progress line with the client
        trace that caused the work via the trace id on every beat."""
        import logging

        lg = logging.getLogger("obs-heartbeat-test3")
        with obs.scan_context(name="hb", enabled=True) as ctx:
            with caplog.at_level(logging.INFO, logger="obs-heartbeat-test3"):
                with obs.heartbeat(lg, "traced op", interval=0.05):
                    time.sleep(0.2)
        msgs = [r.message for r in caplog.records if "traced op" in r.message]
        assert msgs and f"[trace {ctx.trace_id}]" in msgs[0]
