"""The program's spans and kernel names on the profiler's clock
(benchmark/program_spans.py) and the six readers built on them, on a
hand-built trace whose idle split and kernel times were worked out by hand
(program_trace.json), and on a live CPU trace."""

import importlib.util
import json
import os

import pytest

from benchmark import program_spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(HERE, "program_trace.json")) as f:
        doc = json.load(f)
    tr = {"devices": {k: [tuple(e) for e in v] for k, v in doc["trace"]["devices"].items()},
          "annotations": [tuple(a) for a in doc["trace"]["annotations"]]}
    prog = {"window": tuple(doc["program"]["window"]),
            "spans": {k: [tuple(iv) for iv in v] for k, v in doc["program"]["spans"].items()},
            "modules": {k: [tuple(m) for m in v] for k, v in doc["program"]["modules"].items()}}
    return tr, prog, doc["expected"]


@pytest.fixture
def from_fixture(fixture, monkeypatch):
    """The readers' trace file is the fixture, filtered by span name as
    ``load`` filters it."""
    _tr, prog, _want = fixture

    def load(path, names):
        return dict(prog, spans={k: v for k, v in prog["spans"].items() if k in names})

    monkeypatch.setattr(program_spans, "newest_xplane", lambda: "fixture.xplane.pb")
    monkeypatch.setattr(program_spans, "load", load)


def _metric(name):
    path = os.path.join(ROOT, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_idle_split_by_overlapping_spans(fixture):
    tr, prog, want = fixture
    rd = program_spans.table(tr, prog, 1)
    assert rd["window_s"] == pytest.approx(want["window_s"])
    assert rd["idle_s"] == pytest.approx(want["idle_s"])
    assert rd["idle_by_span"] == pytest.approx(want["idle_by_span"])
    assert rd["idle_unattributed_s"] == pytest.approx(want["idle_unattributed_s"])
    assert rd["span_events"] == want["span_events"]


def test_device_seconds_per_kernel(fixture):
    tr, prog, want = fixture
    rd = program_spans.table(tr, prog, 1)
    assert rd["device_s_by_kernel"] == pytest.approx(want["device_s_by_kernel"])
    assert program_spans.device_s(rd, ("ops.gram_gate", "stage.license")) == pytest.approx(want["gate_device_s"])
    assert program_spans.device_s(rd, ("ops.no_such_kernel",)) == 0


def test_idle_overlapping_a_set_of_spans(fixture):
    tr, prog, want = fixture
    rd = program_spans.table(tr, prog, 1)
    idle = program_spans.idle_overlapping(rd, ("commands.setup", "report.write"))
    assert 100 * idle / rd["window_s"] == pytest.approx(want["fixed_host_pct"])
    assert program_spans.idle_overlapping(rd, ("no.such_span",)) is None


def test_table_refuses_another_runs_trace(fixture):
    tr, prog, _want = fixture
    assert program_spans.table(tr, dict(prog, window=(1, 2)), 1) is None
    assert program_spans.table(dict(tr, annotations=[]), prog, 1) is None


def test_kernel_of_a_module_event():
    assert program_spans.kernel_of("jit_stage.license(15030226096816970272)") == "stage.license"
    assert program_spans.kernel_of("jit_ops.gram_gate(1)") == "ops.gram_gate"
    assert program_spans.kernel_of("jit__lambda(12834683425627218508)") == "_lambda"
    assert program_spans.kernel_of("jit_fn") == "fn"


def test_fs_readers(fixture, from_fixture):
    tr, _prog, want = fixture
    r = dict(want["readings"], trace=tr)
    for name in ("commands.setup_s_per_scan", "report.s_per_scan", "license.gate_device_s_per_gb"):
        assert _metric(name).read(r) == pytest.approx(want[name]), name
    assert _metric("device_idle_pct.fs.fixed_host").read(r) == pytest.approx(want["fixed_host_pct"])


def test_fs_readers_read_nothing_from_a_program_without_the_spans(fixture, from_fixture,
                                                                   monkeypatch):
    """What the parent program gives: no set-up or report span, no kernel
    names (its modules are ``jit_fn``, ``jit_call``, ``jit_gate``...)."""
    tr, prog, want = fixture
    r = dict(want["readings"], trace=tr, spans={"fs.read_wait": 0.002}, counters={})
    for name in ("commands.setup_s_per_scan", "report.s_per_scan", "device_idle_pct.fs.fixed_host"):
        assert _metric(name).read(r) is None, name
    assert _metric("device_idle_pct.fs.fixed_host").read(dict(r, trace=None)) is None
    unnamed = {k: [(s, e, "fn") for s, e, _k in v] for k, v in prog["modules"].items()}
    monkeypatch.setattr(program_spans, "load", lambda path, names: dict(prog, modules=unnamed))
    assert _metric("license.gate_device_s_per_gb").read(dict(want["readings"], trace=tr)) is None


@pytest.mark.parametrize("spans, host, resident", [
    ({"cve.host_compare": 0.2, "cve.encode": 0.1, "cve.assemble": 0.05, "cve.join": 0.3}, 100.0, 75.0),
    ({"cve.host_compare": 0.2, "cve.join": 0.0}, 100.0, 0.0),  # never took the resident join
    ({"cve.encode": 0.1, "cve.assemble": 0.05}, 0.0, 75.0),  # never compared on the host
    ({"cve.join": 0.3, "server.scan": 3.0}, None, None),  # a program without these spans
])
def test_cve_readers(spans, host, resident):
    r = {"spans": spans, "counters": {}, "packages": 2000}
    got_host = _metric("cve.host_compare_ms_per_kpkg").read(r)
    got_res = _metric("cve.resident_host_ms_per_kpkg").read(r)
    assert got_host == (None if host is None else pytest.approx(host))
    assert got_res == (None if resident is None else pytest.approx(resident))
    assert _metric("cve.host_compare_ms_per_kpkg").read(dict(r, packages=0)) is None


def test_live_cpu_trace(tmp_path, capsys):
    """A live CPU trace: an enabled program span and the benchmark window
    load on the profiler's clock, once per path, and the command line prints
    the table for it."""
    import jax.numpy as jnp

    from benchmark import common, trace
    from trivy_tpu import obs
    from trivy_tpu.obs import recorder

    f = recorder.instrument_jit("ops.unit_live", lambda x: (x * 2).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    ctx = obs.TraceContext(enabled=True)
    with common.profiler_window(True, str(tmp_path)) as prof:
        with common.annotate(trace.WINDOW):
            with ctx.span("unit.live_span"):
                f(x).block_until_ready()
    path = prof["xplane"]
    prog = program_spans.load(path, frozenset({"unit.live_span"}))
    assert prog is program_spans.load(path, frozenset({"unit.live_span"}))
    assert list(prog["spans"]) == ["unit.live_span"]
    (s, e), = prog["spans"]["unit.live_span"]
    w0, w1 = prog["window"]
    assert w0 <= s < e <= w1
    rd = program_spans.table(trace.load(path, host_ops=True), prog, 1)
    assert rd["idle_unattributed_s"] <= rd["idle_s"]
    assert rd["span_events"] == 1
    assert program_spans.main([path, "--cell", "ci_tree.fresh", "--rehearsal"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert "unit.live_span" in doc["idle_by_span"]
