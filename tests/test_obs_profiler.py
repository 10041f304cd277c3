"""Spans on the profiler's clock and stable kernel names: an enabled span is
a TraceAnnotation of its own name on the profiler's host plane, a disabled
one costs the shared no-op and leaves nothing, externally timed spans stay
totals-only, and every instrumented kernel lowers to a module of its own
name."""

import glob
import os
import threading

import pytest

from trivy_tpu import obs


def _host_events(log_dir) -> list[tuple[str, str]]:
    """``(name, line name)`` of every event on the trace's host planes."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"), recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                out += [(ev.name, ln.name) for ev in ln.events]
    return out


@pytest.fixture
def profiled(tmp_path):
    """Runs a block under a CPU profiler session; yields the host events
    recorded once the block has run."""
    import jax

    got: list = []

    class Session:
        def __enter__(self):
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=options)
            return got

        def __exit__(self, *exc):
            jax.profiler.stop_trace()
            got.extend(_host_events(tmp_path))
            return False

    return Session()


def test_enabled_span_lands_on_the_host_plane(profiled):
    ctx = obs.TraceContext(enabled=True)
    with profiled as events:
        with ctx.span("unit.prof.outer"):
            with ctx.span("unit.prof.inner"):
                pass
    names = [n for n, _line in events]
    assert names.count("unit.prof.outer") == 1
    assert names.count("unit.prof.inner") == 1
    assert ctx.stage_totals()["unit.prof.outer"][1] == 1


def test_disabled_span_is_the_shared_noop_and_leaves_nothing(profiled):
    ctx = obs.TraceContext(enabled=False)
    assert ctx.span("unit.prof.off") is obs._NULL
    with profiled as events:
        with ctx.span("unit.prof.off"):
            pass
    assert "unit.prof.off" not in {n for n, _ in events}
    assert not ctx.durations


def test_add_records_totals_only(profiled):
    ctx = obs.TraceContext(enabled=True)
    with profiled as events:
        ctx.add("unit.prof.added", 0.25)
    assert ctx.stage_totals()["unit.prof.added"][0] == pytest.approx(0.25)
    assert "unit.prof.added" not in {n for n, _ in events}


def test_span_in_a_generator_records_totals_without_an_annotation(profiled):
    """A generator can be resumed on another thread, and an annotation
    records on the thread that ends it: spans opened in one get none."""
    ctx = obs.TraceContext(enabled=True)

    def gen():
        with ctx.span("unit.prof.gen"):
            yield 1

    with profiled as events:
        it = gen()
        next(it)
        t = threading.Thread(target=lambda: list(it))
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert ctx.stage_totals()["unit.prof.gen"][1] == 1
    assert "unit.prof.gen" not in {n for n, _ in events}


def test_span_handed_out_by_a_helper_lands_on_the_host_plane(profiled):
    """The frame that enters the span decides: a helper that returns the
    span (as ``commands._setup_span`` does) leaves it to a plain caller."""
    ctx = obs.TraceContext(enabled=True)

    def helper():
        return ctx.span("unit.prof.helper")

    with profiled as events:
        with helper():
            pass
    assert [n for n, _ in events].count("unit.prof.helper") == 1


def test_cli_layer_spans_land_on_the_host_plane(profiled, tmp_path):
    from trivy_tpu import cli

    tree = tmp_path / "tree"
    tree.mkdir()
    (tree / "LICENSE").write_text("Permission is hereby granted, free of charge, to any person\n")
    with profiled as events:
        rc = cli.main(["fs", "--scanners", "secret,license", "--backend", "cpu",
                       "--format", "json", "-o", str(tmp_path / "out.json"),
                       "--cache-dir", str(tmp_path / "cache"),
                       "--metrics-out", str(tmp_path / "metrics.json"), str(tree)])
    assert rc == 0
    names = [n for n, _ in events]
    for name in ("commands.setup", "scan.inspect", "scan.detect", "report.write"):
        assert names.count(name) == 1, name


@pytest.mark.parametrize("recorder_on", [True, False])
def test_instrument_jit_scopes_the_kernel(recorder_on):
    import jax.numpy as jnp

    from trivy_tpu.obs import recorder

    recorder.configure(enabled_override=recorder_on)
    try:
        fn = recorder.instrument_jit("ops.x", lambda a: (a * 2).sum(axis=1))
        x = jnp.ones((8, 128), jnp.float32)
        jfn = fn if hasattr(fn, "lower") else fn.__wrapped__
        text = jfn.lower(x).as_text(debug_info=True)
        assert "module @jit_ops.x " in text  # the module a device trace names
        assert float(fn(x)[0]) == 256.0
        assert (jfn._cache_size() if hasattr(jfn, "_cache_size") else 1) == 1
    finally:
        recorder.configure()
