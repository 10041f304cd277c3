"""The benchmark's trace reduction, on a small recorded trace whose busy
union, idle share, gap attribution, kernel time and roofline were worked
out by hand (small_trace.json), and on a live CPU trace."""

import importlib.util
import json
import os

import pytest

from benchmark import kernels, peaks, trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "small_trace.json")) as f:
        doc = json.load(f)
    tr = {"devices": {k: [tuple(e) for e in v] for k, v in doc["devices"].items()},
          "annotations": [tuple(a) for a in doc["annotations"]]}
    return tr, doc["expected"]


def _metric(name):
    path = os.path.join(ROOT, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_union_merges_overlaps():
    assert trace.union([(5, 6), (1, 3), (2, 4), (4, 4)]) == [(1, 4), (5, 6)]


def test_reduce_one_device(recorded):
    tr, want = recorded
    red = trace.reduce(tr, 1)
    w = want["one_device"]
    assert red["busy_s"] == pytest.approx(w["busy_s"])
    assert red["window_s"] == pytest.approx(w["window_s"])
    assert red["idle_share"] == pytest.approx(w["idle_share"])
    assert [n for n, _ in red["idle_gaps"]] == [n for n, _ in w["idle_gaps"]]
    assert [s for _, s in red["idle_gaps"]] == pytest.approx([s for _, s in w["idle_gaps"]])
    assert [n for n, _ in red["device_ops"]] == [n for n, _ in w["device_ops"]]
    assert [s for _, s in red["device_ops"]] == pytest.approx([s for _, s in w["device_ops"]])


def test_reduce_averages_busy_over_devices(recorded):
    tr, want = recorded
    red = trace.reduce(tr, 2)
    assert red["busy_s"] == pytest.approx(want["two_devices"]["busy_s"])
    assert red["idle_share"] == pytest.approx(want["two_devices"]["idle_share"])
    top2 = red["idle_gaps"][:2]
    assert [n for n, _ in top2] == [n for n, _ in want["two_devices"]["idle_gaps_top2"]]
    assert [s for _, s in top2] == pytest.approx([s for _, s in want["two_devices"]["idle_gaps_top2"]])


def test_kernel_time_and_bytes(recorded):
    tr, want = recorded
    events = trace.kernel_events(tr, _metric("secret_match_roofline").KERNEL, 1)
    assert sum(s for s, _ in events) == pytest.approx(want["match_seconds_one_device"])
    assert kernels.pallas_call_bytes(events[0][1]) == want["match_bytes"]
    assert kernels.match_kernel_bytes(64, 2560, 113) == want["match_bytes"]


def test_roofline_reader(recorded, monkeypatch):
    tr, want = recorded
    mod = _metric("secret_match_roofline")
    got = mod.read({"trace": tr, "peaks": peaks.lookup("TPU v5 lite"), "chips": 1})
    assert got == pytest.approx(want["match_roofline_pct"])
    assert mod.read({"trace": tr, "peaks": None, "chips": 1}) is None
    monkeypatch.setattr(mod, "KERNEL", r"no_such_kernel")
    assert mod.read({"trace": tr, "peaks": peaks.lookup("TPU v5 lite"), "chips": 1}) is None


def test_idle_reader(recorded):
    tr, want = recorded
    red = trace.reduce(tr, 1)
    assert _metric("device_idle_pct.fs").read({"reduced": red}) == pytest.approx(100 * want["one_device"]["idle_share"])
    assert _metric("device_idle_pct.dep").read({"reduced": None}) is None


def test_peaks_table_refuses_unknown_kinds():
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert peaks.lookup("cpu") is None
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")


def test_span_readers_per_gb():
    r = {"spans": {"fs.read_wait": 2.0, "secret.confirm": 1.0, "license.dispatch": 0.5,
                   "license.device_wait": 0.25, "license.finalize": 0.25},
         "counters": {"secret.bytes_uploaded": 250_000_000}, "bytes_scanned": 500_000_000}
    assert _metric("walk.read_wait_s_per_gb").read(r) == pytest.approx(4.0)
    assert _metric("secret.confirm_s_per_gb").read(r) == pytest.approx(2.0)
    assert _metric("license.s_per_gb").read(r) == pytest.approx(2.0)
    assert _metric("secret.upload_share").read(r) == pytest.approx(50.0)
    assert _metric("secret.device_wait_s_per_gb").read(r) is None
    d = {"spans": {"server.scan": 3.0, "cve.join": 0.3}, "counters": {"cve.resident_rows": 500},
         "packages": 1000}
    assert _metric("server.scan_ms_per_kpkg").read(d) == pytest.approx(3000.0)
    assert _metric("cve.join_ms_per_kpkg").read(d) == pytest.approx(300.0)
    assert _metric("cve.device_rows_per_pkg").read(d) == pytest.approx(0.5)


@pytest.mark.parametrize("host_ops", [True, False])
def test_live_cpu_trace_loads(tmp_path, host_ops):
    """A rehearsal reads the CPU's XLA ops as its device ops; a chip run
    never does, so a CPU trace reads no device op and busy 0."""
    import jax
    import jax.numpy as jnp

    from benchmark import common

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    with common.profiler_window(True, str(tmp_path)) as prof:
        with common.annotate(trace.WINDOW):
            with common.annotate("bench.call"):
                f(x).block_until_ready()
    tr = trace.load(prof["xplane"], host_ops=host_ops)
    red = trace.reduce(tr, 1)
    assert red["window_s"] > 0
    assert 0.0 <= red["idle_share"] <= 1.0
    assert bool(tr["devices"]) is host_ops
    if not host_ops:
        assert red["busy_s"] == 0 and red["idle_share"] == 1.0
    assert any(n == "bench.call" for n, _s, _e in tr["annotations"])
