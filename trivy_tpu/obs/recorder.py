"""Flight recorder + device-lane forensics (the always-on black box).

Three cooperating pieces, all near-zero-cost and strictly bounded:

- **Flight recorder ring** — every subsystem already emitting to ``obs``
  feeds one cheap :func:`record` hook with its *significant* events (span
  boundaries above a latency floor, batch retries / OOM splits, breaker
  transitions, degrade events, tuning and fleet-controller decisions,
  admission sheds, warm-store cold starts, injected faults). Events land
  in a byte-bounded ring on the active scan context (concurrent scans
  keep disjoint rings) and mirror into one process ring so a long-lived
  server can answer "what last went wrong" without a scan handle.
- **Device-lane accounting** — :func:`instrument_jit` wraps the jit/stage
  compilation sites in ``parallel/mesh.py`` and the kernel entry points
  to count compiles and compile wall per (kernel, shape-bucket), detect
  recompile storms (same kernel compiled more than
  ``TRIVY_TPU_RECOMPILE_STORM`` times → loud warning + counter), and a
  live HBM ledger (:func:`note_resident`) tracks resident corpus / CVE /
  arena bytes against device memory. Both export as
  ``trivy_tpu_compile_*`` / ``trivy_tpu_hbm_*`` gauges, Perfetto counter
  tracks, and the ``device`` block in ``--metrics-out``.
- **Diagnostic bundles** — on any terminal failure, degraded completion,
  breaker trip, or dead-replica declaration, :func:`auto_emit` writes a
  self-contained gzipped bundle (ring dump, last metrics/tuning/fleet
  snapshots, stall verdict, compile/HBM ledgers, and a one-paragraph
  machine-built verdict naming the first anomalous event) under
  ``--debug-dir`` / ``TRIVY_TPU_DEBUG_DIR`` with bounded retention.
  ``trivy-tpu debug <bundle>`` renders the timeline + verdict; the scan
  server serves its live state over ``GET /debug/bundle`` so a fleet
  coordinator can merge replica bundles into one incident document.

Zero-cost-when-off discipline (``TRIVY_TPU_FLIGHT_RECORDER=0``): no ring
objects, no span hook on the trace context, no recorder gauges in the
process registry, no threads — :func:`record` is one global ``None``
check and :func:`instrument_jit` hands back the bare jitted callable
(``bench --smoke`` asserts all of it). The recorder itself never starts
a thread in either mode: the ring is passive memory, written in-line by
its callers.
"""

from __future__ import annotations

import gzip
import json
import os
import threading
import time

from trivy_tpu import log

logger = log.logger("obs:recorder")

ENV_ENABLED = "TRIVY_TPU_FLIGHT_RECORDER"
ENV_RING_KB = "TRIVY_TPU_RECORDER_RING_KB"
ENV_SPAN_FLOOR_MS = "TRIVY_TPU_RECORDER_SPAN_FLOOR_MS"
ENV_STORM = "TRIVY_TPU_RECOMPILE_STORM"
ENV_DEBUG_DIR = "TRIVY_TPU_DEBUG_DIR"
ENV_DEBUG_KEEP = "TRIVY_TPU_DEBUG_KEEP"

# ring bounds: a ring holds at most RING_MAX_EVENTS events AND at most
# ring_kb() kilobytes (approximate accounting; oldest events evict first).
# 512 events x ~200 bytes sits well under the default 256 KB byte bound,
# so the count cap normally bites first and the byte bound is the
# flood-of-huge-details backstop
RING_MAX_EVENTS = 512
DEFAULT_RING_KB = 256
# span boundaries only enter the ring above this duration — the ring is
# for *significant* events, not a second span table
DEFAULT_SPAN_FLOOR_MS = 50.0
# same kernel compiled more than this many times in one process = a
# recompile storm (the default 3-rung bucket ladder compiles each kernel
# 3x by design; the headroom above that is deliberate)
DEFAULT_STORM_THRESHOLD = 6
DEFAULT_DEBUG_KEEP = 8
# per-event detail strings are truncated so one giant error repr cannot
# evict the whole ring
DETAIL_MAX_CHARS = 200

BUNDLE_SCHEMA = "trivy-tpu-debug-bundle/v1"

# event kinds that count as anomalous for the machine verdict, most
# severe first — the verdict names the FIRST (earliest) anomalous event,
# ties broken by this ranking
ANOMALOUS_KINDS = (
    "fault", "error", "oom", "dead", "breaker", "degrade", "storm",
    "retry", "shed",
)

_EVENT_BASE_BYTES = 96  # approximate fixed per-event overhead


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "")
    if not raw:
        return default
    try:
        v = float(raw)
    except ValueError:
        return default
    return v if v >= 0 else default


def _env_int(name: str, default: int) -> int:
    return int(_env_float(name, float(default)))


def ring_bytes() -> int:
    """The ring's byte bound (``TRIVY_TPU_RECORDER_RING_KB``)."""
    return max(1, _env_int(ENV_RING_KB, DEFAULT_RING_KB)) * 1024


class Ring:
    """Byte- and count-bounded event ring (oldest evicts first)."""

    __slots__ = ("max_events", "max_bytes", "_lock", "_events", "_bytes",
                 "dropped")

    def __init__(self, max_events: int = RING_MAX_EVENTS,
                 max_bytes: int | None = None):
        self.max_events = max_events
        self.max_bytes = max_bytes or ring_bytes()
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._bytes = 0
        self.dropped = 0

    @staticmethod
    def _size(ev: dict) -> int:
        n = _EVENT_BASE_BYTES + len(ev.get("what", ""))
        for k, v in (ev.get("detail") or {}).items():
            n += len(k) + len(str(v))
        return n

    def append(self, ev: dict) -> None:
        sz = self._size(ev)
        with self._lock:
            self._events.append(ev)
            self._bytes += sz
            while self._events and (
                len(self._events) > self.max_events
                or self._bytes > self.max_bytes
            ):
                old = self._events.pop(0)
                self._bytes -= self._size(old)
                self.dropped += 1

    def snapshot(self) -> list[dict]:
        with self._lock:
            return [dict(e) for e in self._events]

    def approx_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def last(self, *kinds: str) -> dict | None:
        """Most recent event whose kind is in ``kinds`` (any kind when
        empty), or None."""
        with self._lock:
            for ev in reversed(self._events):
                if not kinds or ev.get("kind") in kinds:
                    return dict(ev)
        return None


class _State:
    """Per-process recorder state: the process ring, the compile and HBM
    ledgers, and the bundle-emission bookkeeping. Exists ONLY while the
    recorder is enabled."""

    def __init__(self):
        self.ring = Ring()
        self.lock = threading.Lock()
        self.span_floor_s = _env_float(
            ENV_SPAN_FLOOR_MS, DEFAULT_SPAN_FLOOR_MS
        ) / 1e3
        self.storm_threshold = _env_int(ENV_STORM, DEFAULT_STORM_THRESHOLD)
        self.debug_dir: str = os.environ.get(ENV_DEBUG_DIR, "")
        self.debug_keep = max(1, _env_int(ENV_DEBUG_KEEP, DEFAULT_DEBUG_KEEP))
        # compile ledger: per-kernel [count, wall_s], per (kernel, bucket)
        # count, and the set of kernels already storm-warned (warn ONCE
        # per kernel, not once per extra compile)
        self.compiles: dict[str, list] = {}
        self.compile_buckets: dict[tuple[str, str], int] = {}
        self.storms: set[str] = set()
        # HBM ledger: category -> resident bytes
        self.resident: dict[str, int] = {}
        self._capacity: int | None = None
        # bundle bookkeeping: (trace8, reason) pairs already emitted, so a
        # breaker flapping mid-scan yields one bundle, not a flood
        self.emitted: set[tuple[str, str]] = set()

    # -- device memory capacity ---------------------------------------------

    def capacity_bytes(self) -> int:
        if self._capacity is not None:
            return self._capacity
        import jax

        # device 0 is where the resident tables (license corpus, CVE
        # bounds) are placed, so its capacity is the one they press on
        dev = jax.local_devices()[0]
        if dev.platform == "cpu":
            # the CPU backend reports no memory_stats: the admission
            # controller's HBM proxy budget (MB) stands in
            cap = _env_int("TRIVY_TPU_HBM_BUDGET_MB", 1024) * (1 << 20)
        else:
            cap = int((dev.memory_stats() or {}).get("bytes_limit", 0) or 0)
            if cap <= 0:
                raise RuntimeError(
                    f"{dev.platform} device {dev.id} ({dev.device_kind}) "
                    "reports no memory_stats bytes_limit; the HBM ledger "
                    "has no capacity to score residency against"
                )
        self._capacity = cap
        return cap


_STATE: _State | None = None
_STATE_LOCK = threading.Lock()
_ENABLED: bool | None = None


def enabled() -> bool:
    """One cached env read: ``TRIVY_TPU_FLIGHT_RECORDER`` (default on)."""
    global _ENABLED
    if _ENABLED is None:
        _ENABLED = os.environ.get(ENV_ENABLED, "1").strip().lower() not in (
            "0", "off", "false", "no",
        )
    return _ENABLED


def _state() -> _State | None:
    global _STATE
    st = _STATE
    if st is not None:
        return st
    if not enabled():
        return None
    with _STATE_LOCK:
        if _STATE is None:
            _STATE = _State()
            _install_hook()
        return _STATE


def _install_hook() -> None:
    from trivy_tpu import obs

    obs._flight_hook = _span_hook


def configure(enabled_override: bool | None = None) -> None:
    """Re-read the environment and reset recorder state (rings, ledgers,
    emitted-bundle memory, gauge *values* stay — the registry cannot
    unregister). Test/bench hook: production code never calls this."""
    global _STATE, _ENABLED
    from trivy_tpu import obs

    with _STATE_LOCK:
        _STATE = None
        _ENABLED = enabled_override
        obs._flight_hook = None
    if enabled_override is None:
        enabled()  # re-read env
    if enabled():
        _state()


def set_debug_dir(path: str | None) -> None:
    """Install the bundle destination (``--debug-dir``); no-op when the
    recorder is off."""
    st = _state()
    if st is not None and path:
        st.debug_dir = path


def debug_dir() -> str:
    st = _STATE
    return st.debug_dir if st is not None else ""


# -- the one cheap event hook -----------------------------------------------


def _ctx_ring(ctx) -> Ring:
    ring = getattr(ctx, "_flight_ring", None)
    if ring is None:
        with _STATE_LOCK:
            ring = getattr(ctx, "_flight_ring", None)
            if ring is None:
                ring = ctx._flight_ring = Ring()
    return ring


def record(kind: str, what: str, detail: dict | None = None,
           ctx=None) -> None:
    """Append one significant event to the active scan's ring and the
    process ring. The cheap hook every subsystem calls; a no-op (one
    global check) when the recorder is off."""
    st = _STATE if _ENABLED else _state()
    if st is None:
        st = _state()
        if st is None:
            return
    from trivy_tpu import obs

    if ctx is None:
        ctx = obs.current()
    ev: dict = {
        "t": time.time(),
        "kind": kind,
        "what": what,
        "trace": ctx.trace_id[:8],
    }
    if detail:
        ev["detail"] = {
            k: (v if isinstance(v, (int, float, bool)) or v is None
                else str(v)[:DETAIL_MAX_CHARS])
            for k, v in detail.items()
        }
    _ctx_ring(ctx).append(ev)
    st.ring.append(ev)


def _span_hook(ctx, sp) -> None:
    """Installed as ``obs._flight_hook``: span boundaries above the
    latency floor become ring events."""
    st = _STATE
    if st is None or sp.duration < st.span_floor_s:
        return
    record(
        "span", sp.name, {"seconds": round(sp.duration, 4)}, ctx=ctx,
    )


# -- device-lane accounting: compiles ---------------------------------------


def _metric_counter(name: str, help: str, labelnames=()):
    from trivy_tpu.obs import metrics as obs_metrics

    return obs_metrics.REGISTRY.counter(name, help, labelnames)


def _metric_gauge(name: str, help: str, labelnames=()):
    from trivy_tpu.obs import metrics as obs_metrics

    return obs_metrics.REGISTRY.gauge(name, help, labelnames)


def note_compile(kernel: str, bucket: str, seconds: float) -> None:
    """One XLA/Mosaic compile of ``kernel`` for shape-bucket ``bucket``
    took ``seconds`` of wall. Feeds the compile ledger, the
    ``trivy_tpu_compile_*`` instruments, the ring, and the recompile-storm
    detector."""
    st = _STATE
    if st is None:
        return
    with st.lock:
        tot = st.compiles.setdefault(kernel, [0, 0.0])
        tot[0] += 1
        tot[1] += seconds
        count = tot[0]
        key = (kernel, bucket)
        st.compile_buckets[key] = st.compile_buckets.get(key, 0) + 1
        storm = count > st.storm_threshold and kernel not in st.storms
        if storm:
            st.storms.add(kernel)
    _metric_counter(
        "trivy_tpu_compile_total",
        "Kernel compiles observed by the flight recorder",
        labelnames=("kernel",),
    ).inc(kernel=kernel)
    _metric_counter(
        "trivy_tpu_compile_seconds_total",
        "Kernel compile wall time",
        labelnames=("kernel",),
    ).inc(seconds, kernel=kernel)
    record("compile", kernel, {
        "bucket": bucket, "seconds": round(seconds, 4), "n": count,
    })
    if storm:
        _metric_counter(
            "trivy_tpu_compile_storms_total",
            "Kernels that recompiled past the storm threshold",
            labelnames=("kernel",),
        ).inc(kernel=kernel)
        record("storm", kernel, {
            "compiles": count, "threshold": st.storm_threshold,
        })
        logger.warning(
            "RECOMPILE STORM: kernel %s compiled %d times (threshold %d) — "
            "a shape bucket or rung ladder is churning the compile cache",
            kernel, count, st.storm_threshold,
        )


def _shape_bucket(args) -> str:
    parts = []
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is not None:
            parts.append(f"{getattr(a, 'dtype', '?')}{tuple(shape)}")
        elif isinstance(a, (tuple, list)):
            parts.append("(" + _shape_bucket(a) + ")")
        else:
            parts.append(type(a).__name__)
    return ",".join(parts)


def instrument_jit(kernel: str, fn, **jit_kwargs):
    """``jax.jit(fn)`` with compile accounting: the first call per
    argument shape-bucket is timed as a compile (trace + compile wall)
    and fed to :func:`note_compile`. With the recorder off this returns
    the bare jitted callable — zero wrapper, zero per-call cost.

    Either way ``fn`` is jitted under the name ``kernel``, so its XLA
    module is ``jit_<kernel>``: a TPU profiler trace names the device time
    of each module execution by it (its ops carry no ``op_name``)."""
    import functools

    import jax

    @functools.wraps(fn)
    def named(*args, **kwargs):
        return fn(*args, **kwargs)

    named.__name__ = named.__qualname__ = kernel
    jfn = jax.jit(named, **jit_kwargs)
    if _state() is None:
        return jfn
    seen: set[str] = set()
    lock = threading.Lock()

    def call(*args):
        bucket = _shape_bucket(args)
        with lock:
            first = bucket not in seen
            if first:
                seen.add(bucket)
        if not first:
            return jfn(*args)
        t0 = time.perf_counter()
        out = jfn(*args)
        note_compile(kernel, bucket, time.perf_counter() - t0)
        return out

    call.__wrapped__ = jfn
    return call


def compile_count() -> int:
    """Total compiles observed so far (bench's per-rep regression metric
    differences two reads of this)."""
    st = _STATE
    if st is None:
        return 0
    with st.lock:
        return sum(c for c, _ in st.compiles.values())


def storm_count() -> int:
    st = _STATE
    if st is None:
        return 0
    with st.lock:
        return len(st.storms)


# -- device-lane accounting: HBM ledger -------------------------------------


def note_resident(category: str, nbytes: int) -> None:
    """``nbytes`` more of ``category`` (corpus / cve / arena) became
    device-resident. Negative deltas release."""
    st = _STATE
    if st is None or not nbytes:
        return
    with st.lock:
        now = st.resident.get(category, 0) + int(nbytes)
        st.resident[category] = max(0, now)
        total = sum(st.resident.values())
    _metric_gauge(
        "trivy_tpu_hbm_resident_bytes",
        "Device-resident bytes tracked by the flight recorder's HBM "
        "ledger, by category",
        labelnames=("category",),
    ).set(st.resident[category], category=category)
    _metric_gauge(
        "trivy_tpu_hbm_device_capacity_bytes",
        "Device memory capacity the HBM ledger scores residency against",
    ).set(st.capacity_bytes())
    record("hbm", category, {
        "delta": int(nbytes), "resident": st.resident[category],
        "total": total,
    })


def release_resident(category: str, nbytes: int) -> None:
    note_resident(category, -abs(int(nbytes)))


def hbm_ratio() -> float:
    """Resident bytes / device capacity, 0.0 with the recorder off."""
    st = _STATE
    if st is None:
        return 0.0
    with st.lock:
        total = sum(st.resident.values())
    cap = st.capacity_bytes()
    return total / cap if cap > 0 else 0.0


# -- export surfaces --------------------------------------------------------


def device_doc() -> dict | None:
    """The ``device`` block for ``--metrics-out``: compile ledger, storm
    set, and HBM residency. None when the recorder is off or nothing was
    observed — off-mode exports stay byte-identical."""
    st = _STATE
    if st is None:
        return None
    with st.lock:
        if not st.compiles and not st.resident:
            return None
        compiles = {
            k: {"count": c, "wall_s": round(w, 4)}
            for k, (c, w) in sorted(st.compiles.items())
        }
        buckets = {
            f"{k}|{b}": n
            for (k, b), n in sorted(st.compile_buckets.items())
        }
        storms = sorted(st.storms)
        resident = dict(sorted(st.resident.items()))
    cap = st.capacity_bytes()
    total = sum(resident.values())
    return {
        "compiles": compiles,
        "compile_total": sum(v["count"] for v in compiles.values()),
        "compile_wall_s": round(
            sum(v["wall_s"] for v in compiles.values()), 4
        ),
        "shape_buckets": buckets,
        "recompile_storms": storms,
        "storm_threshold": st.storm_threshold,
        "hbm": {
            "resident_bytes": resident,
            "resident_total_bytes": total,
            "device_capacity_bytes": cap,
            "ratio": round(total / cap, 4) if cap else 0.0,
        },
    }


def counter_series(ctx) -> dict:
    """Perfetto counter tracks derived from the scan ring: cumulative
    compile count and HBM-resident bytes over the scan's timeline (the
    same ``{"points": [(t, v)]}`` shape the sampler's series use)."""
    st = _STATE
    ring = getattr(ctx, "_flight_ring", None) if st is not None else None
    if ring is None:
        return {}
    compiles: list = []
    hbm: list = []
    n = 0
    for ev in ring.snapshot():
        t = ev["t"] - ctx.created_wall
        if ev["kind"] == "compile":
            n += 1
            compiles.append((round(t, 6), n))
        elif ev["kind"] == "hbm":
            hbm.append((round(t, 6), (ev.get("detail") or {}).get("total", 0)))
    out = {}
    if compiles:
        out["device.compiles_total"] = {"points": compiles}
    if hbm:
        out["device.hbm_resident_bytes"] = {"points": hbm}
    return out


def _iso(t: float) -> str:
    import datetime

    return datetime.datetime.fromtimestamp(
        t, datetime.timezone.utc
    ).isoformat(timespec="milliseconds")


def healthz_doc() -> dict:
    """The ``/healthz`` forensics fields: last error / degrade / breaker
    trip from the process ring, each as event + ISO timestamp. Empty dict
    when the recorder is off or nothing bad ever happened."""
    st = _STATE
    if st is None:
        return {}
    out = {}
    for field, kinds in (
        ("LastError", ("error", "fault", "oom")),
        ("LastDegraded", ("degrade",)),
        ("LastBreakerTrip", ("breaker",)),
    ):
        ev = st.ring.last(*kinds)
        if field == "LastBreakerTrip" and ev is not None and (
            "OPEN" not in ev.get("what", "")
        ):
            # breaker events cover open AND close; the trip field reports
            # the last OPEN specifically
            for cand in reversed(st.ring.snapshot()):
                if cand.get("kind") == "breaker" and "OPEN" in cand.get(
                    "what", ""
                ):
                    ev = cand
                    break
            else:
                ev = None
        if ev is not None:
            out[field] = {
                "Event": f"{ev['kind']} {ev['what']}",
                "Time": _iso(ev["t"]),
            }
    return out


# -- live fragments (heartbeat / --live) ------------------------------------


def live_fragment() -> str:
    """Stateless compact device fragment for the ``--live`` line:
    ``compiles 12 hbm 61%`` (plus a storm marker). Empty when the
    recorder is off or nothing was observed."""
    st = _STATE
    if st is None:
        return ""
    n = compile_count()
    ratio = hbm_ratio()
    if not n and not ratio:
        return ""
    frag = f"compiles {n}"
    if ratio:
        frag += f" hbm {ratio * 100:.0f}%"
    if storm_count():
        frag += " STORM"
    return frag


def heartbeat_fragment(carrier) -> str:
    """Heartbeat device fragment with a per-beat delta:
    ``compiles 12 (+0) hbm 61%``; a recompile storm since the previous
    beat surfaces immediately (``RECOMPILE-STORM <kernel>``). ``carrier``
    is any object the per-beat state can hang off (the heartbeat
    instance)."""
    st = _STATE
    if st is None:
        return ""
    n = compile_count()
    ratio = hbm_ratio()
    storms = storm_count()
    last_n = getattr(carrier, "_rec_last_compiles", None)
    last_storms = getattr(carrier, "_rec_last_storms", 0)
    carrier._rec_last_compiles = n
    carrier._rec_last_storms = storms
    if not n and not ratio:
        return ""
    frag = f"compiles {n}"
    if last_n is not None:
        frag += f" (+{max(0, n - last_n)})"
    if ratio:
        frag += f" hbm {ratio * 100:.0f}%"
    if storms > last_storms:
        with st.lock:
            names = sorted(st.storms)
        frag += f" RECOMPILE-STORM {names[-1] if names else '?'}"
    return frag


# -- diagnostic bundles -----------------------------------------------------


def _verdict(reason: str, ctx, events: list[dict],
             error: str | None = None) -> str:
    """One machine-built paragraph naming the first anomalous event."""
    st = _STATE
    anomalous = [e for e in events if e.get("kind") in ANOMALOUS_KINDS]
    first = None
    if anomalous:
        rank = {k: i for i, k in enumerate(ANOMALOUS_KINDS)}
        t0 = min(e["t"] for e in anomalous)
        # earliest wins; among events in the same 10 ms window the most
        # severe kind names the verdict (a fault and the degrade it caused
        # land near-simultaneously — the fault is the cause)
        window = [e for e in anomalous if e["t"] - t0 <= 0.010]
        first = min(window, key=lambda e: rank.get(e["kind"], 99))
    parts = [f"Scan {ctx.trace_id[:8]}: {reason}."]
    if first is not None:
        rel = first["t"] - ctx.created_wall
        parts.append(
            f"First anomalous event: {first['kind']} {first['what']} at "
            f"{_iso(first['t'])} (+{rel:.2f}s into the scan)."
        )
        if len(anomalous) > 1:
            kinds: dict[str, int] = {}
            for e in anomalous:
                kinds[e["kind"]] = kinds.get(e["kind"], 0) + 1
            parts.append(
                f"{len(anomalous)} anomalous events total ("
                + ", ".join(f"{k} x{n}" for k, n in sorted(kinds.items()))
                + ")."
            )
    else:
        parts.append("No anomalous events in the ring.")
    if error:
        parts.append(f"Last error: {str(error)[:DETAIL_MAX_CHARS]}.")
    if st is not None:
        n = compile_count()
        if n:
            with st.lock:
                wall = sum(w for _, w in st.compiles.values())
                kernels = len(st.compiles)
            parts.append(
                f"Device lane: {n} compiles / {wall:.2f}s compile wall "
                f"across {kernels} kernels; HBM resident "
                f"{hbm_ratio() * 100:.0f}% of device memory."
            )
    return " ".join(parts)


def build_bundle(ctx=None, reason: str = "on-demand",
                 error=None) -> dict:
    """Assemble a self-contained diagnostic bundle as a dict. Works with
    the recorder off too (empty ring, no ledgers) so the server route can
    answer honestly either way."""
    from trivy_tpu import obs
    from trivy_tpu.obs import export as obs_export
    from trivy_tpu.obs import stall as obs_stall

    if ctx is None:
        ctx = obs.current()
    st = _STATE
    ring = getattr(ctx, "_flight_ring", None)
    events = ring.snapshot() if ring is not None else []
    process_events = st.ring.snapshot() if st is not None else []
    doc: dict = {
        "schema": BUNDLE_SCHEMA,
        "reason": reason,
        "created": _iso(time.time()),
        "trace_id": ctx.trace_id,
        "name": ctx.name,
        "verdict": _verdict(
            reason, ctx, events or process_events,
            error=str(error) if error is not None else None,
        ),
        "events": events,
        "health": ctx.health_snapshot(),
    }
    if error is not None:
        doc["error"] = str(error)[:1000]
    if process_events and process_events != events:
        doc["process_events"] = process_events
    try:
        doc["stall"] = obs_stall.attribution(ctx)
    except Exception:
        pass
    dev = device_doc()
    if dev is not None:
        doc["device"] = dev
    try:
        doc["metrics"] = obs_export.metrics_dict(ctx)
    except Exception as e:  # a dying context must not kill the bundle
        doc["metrics_error"] = str(e)
    tuning = ctx.tuning_doc()
    if tuning is not None:
        doc["tuning"] = tuning
    fleet = getattr(ctx, "fleet", None)
    if fleet:
        doc["fleet"] = fleet
    return doc


def write_bundle(doc: dict, dest_dir: str, keep: int | None = None) -> str:
    """Write one bundle as gzipped JSON under ``dest_dir`` and enforce
    retention (newest ``keep`` bundles survive). Returns the path."""
    st = _STATE
    keep = keep or (st.debug_keep if st is not None else DEFAULT_DEBUG_KEEP)
    os.makedirs(dest_dir, exist_ok=True)
    stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
    trace8 = str(doc.get("trace_id", ""))[:8] or "proc"
    reason = str(doc.get("reason", "bundle")).replace("/", "-")
    path = os.path.join(
        dest_dir, f"bundle-{stamp}-{trace8}-{reason}.json.gz"
    )
    # a same-second re-emit for the same scan must not clobber
    n = 1
    while os.path.exists(path):
        n += 1
        path = os.path.join(
            dest_dir, f"bundle-{stamp}-{trace8}-{reason}.{n}.json.gz"
        )
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)
    bundles = sorted(
        (
            os.path.join(dest_dir, name)
            for name in os.listdir(dest_dir)
            if name.startswith("bundle-") and name.endswith(".json.gz")
        ),
        key=os.path.getmtime,
    )
    for old in bundles[:-keep]:
        try:
            os.unlink(old)
        except OSError:
            pass
    return path


def read_bundle(path: str) -> dict:
    """Load a bundle written by :func:`write_bundle` (gz or plain JSON)."""
    if path.endswith(".gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    with open(path) as f:
        return json.load(f)


def auto_emit(reason: str, ctx=None, error=None, extra: dict | None = None,
              ) -> str | None:
    """Emit a diagnostic bundle for a failure-shaped moment (terminal
    failure, degraded completion, breaker trip, dead replica). At most
    ONE bundle per (scan, reason); a no-op unless a debug dir is
    configured (``--debug-dir`` / ``TRIVY_TPU_DEBUG_DIR``). Never raises:
    forensics must not take the scan down with it."""
    st = _STATE
    if st is None or not st.debug_dir:
        return None
    from trivy_tpu import obs

    if ctx is None:
        ctx = obs.current()
    key = (ctx.trace_id[:8], reason)
    with st.lock:
        if key in st.emitted:
            return None
        st.emitted.add(key)
    try:
        doc = build_bundle(ctx=ctx, reason=reason, error=error)
        if extra:
            doc.update(extra)
        path = write_bundle(doc, st.debug_dir)
    except Exception as e:
        logger.warning("debug bundle emit (%s) failed: %s", reason, e)
        return None
    logger.warning("diagnostic bundle written: %s (%s)", path, reason)
    return path


# install the span hook at import when enabled: importing this module is
# how a subsystem opts its process into the recorder (commands, the scan
# server, mesh, and bench all do)
if enabled():
    _state()
