"""What every cell shares: the benchmark's files, the platform check, the
profiler window, the per-layer metric readers and the result line."""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# generated inputs, scan outputs and traces (benchmark/.gitignore lists it),
# at fixed paths that every run empties and fills anew
CACHE = os.path.join(BENCH, ".cache")


class Refused(Exception):
    """The run cannot measure: no chip, too few chips, or no program."""


def say(key: str, value) -> None:
    print(f"{key}: {json.dumps(value, sort_keys=True)}", flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """``(bench, cell, config, traffic)`` for the workload ``name``, found
    through ``BENCHMARK.json``: the config's ``file``, and the traffic file
    ``benchmark/traffic/<traffic>.json``."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    return bench, cell, config, traffic


def open_platform(chips: int, rehearse: bool) -> dict:
    """Import the program and JAX, point the compile cache at its fixed
    home, and check the devices. Returns the device stamp."""
    if chips == 1:
        # one chip of however many the host has, before JAX initializes
        os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
        os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
        os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        import trivy_tpu  # noqa: F401
    except ImportError as e:
        raise Refused(f"the program is not in this checkout: {e}")
    from trivy_tpu import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if not rehearse and d0.platform != "tpu":
        raise Refused(f"JAX found {d0.platform!r} ({d0.device_kind}), not a TPU")
    if len(devices) < chips:
        raise Refused(f"{len(devices)} devices, the cell needs {chips}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": chips if not rehearse else len(devices)}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def inputs_dir(traffic: dict) -> str:
    """An emptied directory for this run's generated inputs, keyed by the
    traffic's name and parameters (a rehearsal's tiny pool never shares
    one with the cell's). Every run makes its inputs anew from its seed,
    as set-up: no run finds another's, so every run's set-up is alike."""
    import hashlib

    key = hashlib.sha256(json.dumps(traffic, sort_keys=True).encode()).hexdigest()[:12]
    return fresh_dir(os.path.join(CACHE, "inputs", f"{traffic['name']}-{key}"))


def fresh_dir(path: str) -> str:
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


@contextlib.contextmanager
def profiler_window(enabled: bool, log_dir: str):
    """With ``enabled``, a JAX profiler trace over the block; yields a dict
    that holds ``xplane`` (the trace file) afterwards."""
    out: dict = {}
    if not enabled:
        yield out
        return
    import glob

    import jax

    fresh_dir(log_dir)
    # no Python function tracing: it slows the host several-fold and the
    # reduction reads only device ops and the benchmark's annotations
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        out["xplane"] = files[-1] if files else None


def annotate(name: str):
    """A host span on the profiler's clock around one call into the
    program; idle gaps on the device are attributed to these."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def read_per_layer(bench: dict, cell: dict, readings: dict) -> dict:
    """Run each per-layer metric's reader (``benchmark/metrics/<name>.py``)
    that applies to this cell; a reader that finds nothing returns None and
    the metric is left out."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])}
    out = {}
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and cell["name"] not in cells:
            continue
        if cells is None and m["moves"] not in e2e:
            continue
        path = os.path.join(BENCH, "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location("metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(readings)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def traced_result(bench: dict, cell: dict, body: dict, device: dict,
                  readings: dict, xplane: str | None, rehearse: bool) -> None:
    """Fill a ``--trace 1`` result: reduce the profiler trace (busy and
    window seconds into ``device``, the ``breakdown``), then run the
    per-layer readers over ``readings`` (the program's spans and counters
    and the cell's own counts) with the trace added. Only a rehearsal
    takes the CPU's XLA ops for device ops; on the chip a window without
    a device op reads busy 0."""
    from benchmark import peaks, trace

    chips = int(cell["chips"])
    readings = dict(readings, chips=chips, peaks=peaks.lookup(device["kind"]),
                    trace=None, reduced=None)
    if xplane:
        tr = trace.load(xplane, host_ops=rehearse)
        red = trace.reduce(tr, chips)
        readings.update(trace=tr, reduced=red)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        body["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    body["metrics"] = read_per_layer(bench, cell, readings)


def correct(checks: list[tuple[str, float, float]]) -> bool:
    """The rule that decides ``correct``, for the program and for the
    control alike: every number compared within its limit."""
    return all(v <= lim for _name, v, lim in checks)


def emit(result: dict, checks: list[tuple[str, float, float]], prefix: str = "") -> None:
    """The result line, last on stdout, with the numbers compared last in
    it; the same numbers as the last lines of stderr. A rehearsal, a sweep
    or a control prefixes its line, so that no reader can take it for a
    chip result."""
    result = dict(result)
    result["checks"] = {name: {"value": v, "limit": lim} for name, v, lim in checks}
    for name, v, lim in checks:
        print(f"check {name} = {v} (limit {lim})", file=sys.stderr, flush=True)
    line = json.dumps(result)
    print(prefix + line, flush=True)


def wall() -> float:
    return time.perf_counter()
