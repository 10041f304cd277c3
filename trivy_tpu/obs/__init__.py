"""Observability subsystem: per-scan trace contexts, span histograms,
stall attribution, Chrome-trace/metrics export, and a Prometheus registry.

The design borrows the two instrumentation surfaces a training/
inference stack leans on:

- **Dapper-style span trees** (:class:`TraceContext`): every span carries a
  trace id, span id, and parent span id. Contexts are carried in a
  contextvar — ``commands.run`` and ``ScanServer.scan`` each enter a fresh
  one — so concurrent server-mode scans record into disjoint tables instead
  of interleaving into one process-global dict. Worker threads that outlive
  the contextvar (the secret scanner's device thread, the confirm pool)
  re-enter the parent scan's context with :func:`activate`.
- **JAX-profiler-style stage tracks**: spans are exportable as Chrome
  trace-event JSON (:mod:`trivy_tpu.obs.export`, loadable in Perfetto) with
  one track per pipeline stage and device stream, and aggregate to
  p50/p95/max histograms plus a per-pipeline stall-attribution verdict
  (:mod:`trivy_tpu.obs.stall`) — ``feed-starved 72% / device-bound 18% /
  confirm-bound 10%`` — so perf rounds can pick targets from attribution,
  not totals.
- **Spans on the profiler's clock**: while a JAX profiler session records,
  each span of an enabled context is also a ``jax.profiler.TraceAnnotation``
  of its own name, on the host plane of the same trace as the device ops,
  so device idle time can be attributed to the host work it overlaps.
  Externally timed spans (:meth:`TraceContext.add`) cannot be back-dated
  onto that clock and stay totals-only.

Disabled contexts cost one attribute check per span site (the acceptance
bar: < 1% overhead with tracing off).
"""

from __future__ import annotations

import contextvars
import inspect
import itertools
import os
import random
import sys
import threading
import time
from contextlib import contextmanager

__all__ = [
    "Span",
    "TraceContext",
    "activate",
    "add",
    "count",
    "current",
    "disable",
    "enable",
    "enabled",
    "health_count",
    "heartbeat",
    "note_scan_degraded",
    "parse_traceparent",
    "report",
    "sample",
    "scan_context",
    "span",
    "traceparent",
]

# span ids are seeded with 40 random bits per process so spans from a
# client and a remote server joined into one trace don't collide (a pid
# seed would: containerized client and server are both pid 1), keeping
# parent/child links in a merged export unambiguous; ids stay < 2**64 so
# the traceparent %016x rendering never truncates
_span_ids = itertools.count(
    (int.from_bytes(os.urandom(5), "big") << 24) + 1
)

# raw span-event cap per context: aggregates (histograms, counters, stall
# attribution) never drop, but the per-event list backing the Chrome trace
# export is bounded so a multi-million-file scan cannot hold every event.
# Exports report ``dropped_events`` — truncation is never silent.
MAX_EVENTS = 200_000
# per-stage percentile reservoir: running count/total/max are exact for any
# span volume; p50/p95 come from a uniform reservoir sample (Algorithm R)
# so a 10M-file traced scan holds a few thousand floats per stage, not
# tens of millions
RESERVOIR = 8192
# per-name cap on raw sample() observations (queue depths): running
# count/sum/max stay exact past it
MAX_SAMPLES = 8192


class _StageAgg:
    """Running per-stage duration aggregate: exact count/total/max plus a
    bounded uniform reservoir for percentile estimation, and the set of
    recording thread idents (stall attribution normalizes concurrent-pool
    stages by it)."""

    __slots__ = ("count", "total", "vmax", "values", "threads")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.vmax = 0.0
        self.values: list[float] = []
        self.threads: set[int] = set()

    def add(self, dur: float, thread: int) -> None:
        self.count += 1
        self.total += dur
        if dur > self.vmax:
            self.vmax = dur
        if len(self.values) < RESERVOIR:
            self.values.append(dur)
        else:
            i = random.randrange(self.count)
            if i < RESERVOIR:
                self.values[i] = dur
        self.threads.add(thread)


class Span:
    """One finished (or in-flight) timed region."""

    __slots__ = ("name", "span_id", "parent_id", "start", "duration", "thread")

    def __init__(self, name, span_id, parent_id, start, duration, thread):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = start  # perf_counter at entry
        self.duration = duration  # seconds
        self.thread = thread  # recording thread ident

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start": self.start,
            "duration": self.duration,
            "thread": self.thread,
        }


class _NullSpan:
    """Shared no-op context manager returned when tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()

# flight-recorder span hook (trivy_tpu/obs/recorder.py): installed once at
# recorder import when TRIVY_TPU_FLIGHT_RECORDER is on, so span boundaries
# above the recorder's latency floor land in the black-box ring. One global
# None-check per recorded span when off.
_flight_hook = None

# jax.profiler.TraceAnnotation, looked up once JAX is in the process: obs
# never imports JAX itself, so processes without it stay without it
_annotation = None
# a frame that can suspend (generator, coroutine) may resume a span's
# block on another thread, and a TraceAnnotation records on the thread
# that ends it: spans opened directly in such frames get none
_SUSPENDS = (
    inspect.CO_GENERATOR | inspect.CO_COROUTINE
    | inspect.CO_ITERABLE_COROUTINE | inspect.CO_ASYNC_GENERATOR
)


def _trace_annotation():
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class _SpanCM:
    __slots__ = ("ctx", "name", "sp", "ann")

    def __init__(self, ctx: "TraceContext", name: str):
        self.ctx = ctx
        self.name = name
        self.ann = None

    def __enter__(self) -> Span:
        ctx = self.ctx
        stack = ctx._stack()
        # a root span of a joined trace parents to the remote caller's span
        parent = stack[-1].span_id if stack else ctx.parent_span_id
        sp = Span(
            self.name,
            next(_span_ids),
            parent,
            time.perf_counter(),
            0.0,
            threading.get_ident(),
        )
        stack.append(sp)
        self.sp = sp
        # only while a profiler session records; TraceMe stamps its start
        # when constructed
        ann = _annotation or _trace_annotation()
        if (
            ann is not None and ann.is_enabled()
            and not sys._getframe(1).f_code.co_flags & _SUSPENDS
        ):
            self.ann = ann(self.name)
        return sp

    def __exit__(self, *exc) -> bool:
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
        sp = self.sp
        sp.duration = time.perf_counter() - sp.start
        stack = self.ctx._stack()
        if stack and stack[-1] is sp:
            stack.pop()
        self.ctx._record(sp)
        return False


class TraceContext:
    """Per-scan span table: raw events (bounded), per-name duration lists,
    integer counters, and numeric samples (queue depths), all thread-safe.

    Span parenting is tracked per recording thread: nested ``span()`` calls
    on one thread chain parent ids; spans from worker threads that entered
    via :func:`activate` parent to whatever is open on *their* stack.

    Cross-process: ``trace_id`` is a W3C-trace-context-shaped 32-hex id.
    A server joining a client's trace passes the incoming ids —
    ``trace_id`` plus ``parent_span_id`` (the client's RPC span), so its
    root spans parent under the caller — and ships its span table back in
    the scan response; the client folds it in with :meth:`ingest_remote`
    so one export carries both sides of the wire.
    """

    def __init__(self, name: str = "scan", enabled: bool = False,
                 trace_id: str | None = None,
                 parent_span_id: int | None = None):
        self.name = name
        self.trace_id = trace_id or os.urandom(16).hex()
        self.parent_span_id = parent_span_id
        self.enabled = enabled
        self.created = time.perf_counter()
        self.created_wall = time.time()
        self._lock = threading.Lock()
        self.events: list[Span] = []
        self.dropped_events = 0
        self.durations: dict[str, _StageAgg] = {}
        self.counters: dict[str, int] = {}
        # name -> last value of a level set by the scan (rules per lane)
        self.gauges: dict[str, float] = {}
        # name -> [count, sum, max, bounded raw values]
        self.samples: dict[str, list] = {}
        # scan-health events (degradations, skipped files): recorded even
        # with tracing off — they feed the report summary, not the trace
        self.health: dict[str, int] = {}
        # serialized remote context docs (export.context_doc) joined into
        # this trace — a server's half of a client-mode scan
        self.remote: list[dict] = []
        # per-rule / per-bucket cost profile, created lazily by profile()
        self._profile = None
        # live telemetry (obs/timeseries.py): the scan's bounded time
        # series, set by an attached Sampler; None on unsampled scans
        self.timeseries = None
        # tuning surface (trivy_tpu/tuning.py): the resolved knob config
        # (a plain dict, set by the command layer) and the attached online
        # controller (exposes .doc(), set by the pipeline when the
        # controller is on) — tuning_doc() merges both for export
        self.tuning: dict | None = None
        self.tuning_controller = None
        # fleet telemetry (trivy_tpu/fleet/telemetry.py): the per-replica
        # health doc attached at poller stop, plus the coordinator's
        # heartbeat-status and --live-fragment callables — all None on
        # non-fleet scans so exports show no empty block
        self.fleet: dict | None = None
        self.fleet_status = None
        self.fleet_live = None
        # always-on scan progress (bytes/files walked vs scanned), created
        # lazily by progress() — like health, NOT gated on `enabled`
        self._progress = None
        # telemetry probes: cheap callables returning {series: value},
        # registered by pipeline internals (feed arena, dispatch layer) and
        # polled only while a sampler thread is attached
        self._probes: list = []
        self._local = threading.local()

    # -- recording ----------------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, sp: Span) -> None:
        with self._lock:
            agg = self.durations.get(sp.name)
            if agg is None:
                agg = self.durations[sp.name] = _StageAgg()
            agg.add(sp.duration, sp.thread)
            if len(self.events) < MAX_EVENTS:
                self.events.append(sp)
            else:
                self.dropped_events += 1
        hook = _flight_hook
        if hook is not None:
            hook(self, sp)

    def span(self, name: str):
        """Context manager timing a block under ``name``; no-op when off."""
        if not self.enabled:
            return _NULL
        return _SpanCM(self, name)

    def add(self, name: str, seconds: float) -> None:
        """Record an externally-timed duration as a span ending now (in the
        totals only: it cannot be back-dated onto the profiler's clock)."""
        if not self.enabled:
            return
        self._record(
            Span(
                name,
                next(_span_ids),
                self.parent_span_id,
                time.perf_counter() - seconds,
                seconds,
                threading.get_ident(),
            )
        )

    def current_span_id(self) -> int | None:
        """The innermost open span on the calling thread (the parent a
        child process should link under), falling back to this context's
        own inherited parent."""
        stack = getattr(self._local, "stack", None)
        if stack:
            return stack[-1].span_id
        return self.parent_span_id

    def profile(self):
        """This scan's per-rule/per-bucket cost profile
        (:class:`trivy_tpu.obs.profile.ScanProfile`), created lazily.
        Pipelines guard recording on ``self.enabled`` themselves."""
        from trivy_tpu.obs.profile import ScanProfile

        with self._lock:
            if self._profile is None:
                self._profile = ScanProfile()
            return self._profile

    def progress(self):
        """This scan's :class:`trivy_tpu.obs.timeseries.ScanProgress`,
        created lazily. Always-on like the health channel: the progress
        API and heartbeat must work on untraced scans, and the cost is a
        lock + integer adds per file."""
        from trivy_tpu.obs.timeseries import ScanProgress

        with self._lock:
            if self._progress is None:
                self._progress = ScanProgress()
            return self._progress

    def progress_peek(self):
        """The progress tracker if any producer created one, else None —
        readers (heartbeat, sampler, --live) must not conjure an empty
        tracker that would then report a scan at 0% forever."""
        return self._progress

    def add_probe(self, fn) -> None:
        """Register a telemetry probe: a cheap callable returning a
        ``{series_name: float}`` dict. Names ending ``_total`` are
        cumulative counters (the sampler derives rates); everything else
        is an instantaneous gauge. Registration is O(1) and unconditional;
        the probe is only ever *called* by an attached sampler thread."""
        with self._lock:
            self._probes.append(fn)

    def remove_probe(self, fn) -> None:
        with self._lock:
            if fn in self._probes:
                self._probes.remove(fn)

    def probe_values(self) -> dict[str, float]:
        """Merged snapshot of every registered probe. A probe that raises
        (e.g. mid-teardown of a degrading pipeline) is skipped — telemetry
        must never take the scan down with it."""
        with self._lock:
            probes = list(self._probes)
        out: dict[str, float] = {}
        for fn in probes:
            try:
                out.update(fn())
            except Exception:
                pass
        return out

    def ingest_remote(self, doc: dict) -> None:
        """Join a remote scan's serialized context
        (:func:`trivy_tpu.obs.export.context_doc`) into this trace: its
        tracks ride the same Chrome-trace export, its stage totals feed the
        unified stall verdict, and its profile merges into this scan's."""
        if not isinstance(doc, dict):
            return
        with self._lock:
            self.remote.append(doc)

    def remote_stage_totals(self) -> dict[str, tuple[float, int]]:
        """Stage totals of every joined remote context, with the pipeline
        component prefixed ``server:`` so the stall verdict reports e.g.
        ``server:driver`` and ``server:secret`` lines distinct from the
        local pipelines."""
        with self._lock:
            docs = list(self.remote)
        out: dict[str, tuple[float, int]] = {}
        for doc in docs:
            for name, s in (doc.get("spans") or {}).items():
                key = f"server:{name}"
                total, threads = out.get(key, (0.0, 0))
                out[key] = (
                    total + float(s.get("total", 0.0)),
                    max(threads, int(s.get("threads", 1))),
                )
        return out

    def tuning_doc(self) -> dict | None:
        """The scan's tuning state for export: the resolved knob config
        (with per-knob provenance) plus — when an online controller is
        attached — its decision log snapshot. None when neither exists, so
        pre-tuning consumers see no empty block."""
        ctl = self.tuning_controller
        if self.tuning is None and ctl is None:
            return None
        doc = dict(self.tuning or {})
        if ctl is not None:
            try:
                doc["controller"] = ctl.doc()
            except Exception:  # a dying controller must not kill export
                pass
        return doc

    def merged_profile_dict(self) -> dict:
        """Local profile plus every joined remote profile as one dict —
        what ``--profile-out`` writes and the report table renders."""
        from trivy_tpu.obs.profile import ScanProfile

        with self._lock:
            local = self._profile
            docs = list(self.remote)
        merged = ScanProfile()
        if local is not None:
            merged.merge_dict(local.to_dict())
        for doc in docs:
            p = doc.get("profile")
            if p:
                merged.merge_dict(p)
        return merged.to_dict()

    def count(self, name: str, n: int = 1) -> None:
        """Accumulate an integer counter (byte/item tallies)."""
        if not self.enabled:
            return
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def gauge(self, name: str, value: float) -> None:
        """Set a level the scan ran at (last value wins)."""
        if not self.enabled:
            return
        with self._lock:
            self.gauges[name] = value

    def sample(self, name: str, value: float) -> None:
        """Record one observation of a fluctuating quantity (queue depth,
        in-flight count); count/sum/max stay exact, raw values are bounded."""
        if not self.enabled:
            return
        value = float(value)
        with self._lock:
            s = self.samples.get(name)
            if s is None:
                s = self.samples[name] = [0, 0.0, value, []]
            s[0] += 1
            s[1] += value
            if value > s[2]:
                s[2] = value
            if len(s[3]) < MAX_SAMPLES:
                s[3].append(value)

    def health_count(self, name: str, n: int = 1) -> None:
        """Accumulate a scan-health event (``scan.degraded``,
        ``walk.skipped``, ``cache.degraded``). Unlike :meth:`count` this is
        NOT gated on ``enabled`` — degradations must reach the report
        summary even on untraced scans. A few events per scan, so the
        always-on cost is a dict increment."""
        with self._lock:
            self.health[name] = self.health.get(name, 0) + n

    def health_snapshot(self) -> dict[str, int]:
        with self._lock:
            return dict(self.health)

    def reset(self) -> None:
        with self._lock:
            self.events.clear()
            self.dropped_events = 0
            self.durations.clear()
            self.counters.clear()
            self.gauges.clear()
            self.samples.clear()
            self.health.clear()
            self.remote.clear()
            self._profile = None
            self._progress = None
            self._probes.clear()
            self.timeseries = None
            self.tuning = None
            self.tuning_controller = None
            if getattr(self, "_flight_ring", None) is not None:
                self._flight_ring = None

    # -- aggregation --------------------------------------------------------

    def snapshot(self) -> dict[str, list[float]]:
        """Copy of the per-name duration values. Bounded: past RESERVOIR
        spans per stage this is a uniform sample, not the full list — use
        :meth:`stage_totals` / :meth:`stage_stats` for exact totals."""
        with self._lock:
            return {k: list(v.values) for k, v in self.durations.items()}

    def stage_totals(self) -> dict[str, tuple[float, int]]:
        """name -> (exact total seconds, distinct recording threads)."""
        with self._lock:
            return {
                k: (v.total, len(v.threads))
                for k, v in self.durations.items()
                if v.count
            }

    def stage_stats(self) -> dict[str, dict[str, float]]:
        """name -> {count, total, mean, p50, p95, max} in seconds.
        count/total/mean/max are exact; p50/p95 come from the reservoir."""
        with self._lock:
            aggs = {
                k: (v.count, v.total, v.vmax, list(v.values))
                for k, v in self.durations.items()
            }
        out = {}
        for name, (count, total, vmax, values) in sorted(aggs.items()):
            if not count:
                continue
            out[name] = {
                "count": count,
                "total": total,
                "mean": total / count,
                "p50": percentile(values, 50),
                "p95": percentile(values, 95),
                "max": vmax,
            }
        return out

    # -- reporting ----------------------------------------------------------

    def report(self, out=None) -> None:
        """Aggregate span table (count / total / mean / p50 / p95 / max),
        widest totals first, then counters and queue-depth samples, then the
        per-pipeline stall-attribution verdict."""
        if not self.enabled:
            return
        out = out or sys.stderr
        stats = self.stage_stats()
        with self._lock:
            counters = sorted(self.counters.items())
            samples = {
                k: (v[0], v[1], v[2]) for k, v in sorted(self.samples.items())
            }
            remote_docs = list(self.remote)
        # joined remote (server-side) stages render in the same table,
        # prefixed "server:", so one report covers both sides of the wire
        for doc in remote_docs:
            for name, s in sorted((doc.get("spans") or {}).items()):
                agg = wire_span_stats(s)
                if agg["count"]:
                    stats[f"server:{name}"] = agg
            for name, value in sorted((doc.get("counters") or {}).items()):
                counters.append((f"server:{name}", value))
        prof_doc = self.merged_profile_dict()
        has_profile = bool(prof_doc.get("rules") or prof_doc.get("buckets")
                           or prof_doc.get("fleet"))
        if not stats and not counters and not samples and not has_profile:
            return
        rows = sorted(stats.items(), key=lambda kv: -kv[1]["total"])
        out.write("\n-- trace " + "-" * 71 + "\n")
        if rows:
            out.write(
                f"{'span':<34}{'count':>7}{'total':>10}{'mean':>9}"
                f"{'p50':>9}{'p95':>9}{'max':>9}\n"
            )
            for name, s in rows:
                out.write(
                    f"{name:<34}{s['count']:>7}{s['total']:>9.3f}s"
                    f"{s['mean']:>8.4f}s{s['p50']:>8.4f}s"
                    f"{s['p95']:>8.4f}s{s['max']:>8.4f}s\n"
                )
        if counters:
            out.write(f"{'counter':<55}{'value':>15}\n")
            for name, value in counters:
                out.write(f"{name:<55}{value:>15}\n")
        if samples:
            out.write(f"{'sample':<40}{'count':>8}{'mean':>10}{'max':>10}\n")
            for name, (count, total, vmax) in samples.items():
                out.write(
                    f"{name:<40}{count:>8}"
                    f"{total / max(1, count):>10.1f}{vmax:>10.1f}\n"
                )
        from trivy_tpu.obs import stall

        lines = stall.verdict_lines(self)
        if lines:
            out.write("-- stall attribution " + "-" * 59 + "\n")
            for line in lines:
                out.write(line + "\n")
        from trivy_tpu.obs import profile as _profile

        prof_lines = _profile.table_lines(prof_doc)
        if prof_lines:
            out.write(
                f"-- hottest rules (top {_profile.TOP_K} by confirm cost) "
                + "-" * 33 + "\n"
            )
            for line in prof_lines:
                out.write(line + "\n")
        fleet_lines = _profile.fleet_table_lines(prof_doc)
        if fleet_lines:
            # fleet efficiency verdict: per-replica busy/idle/stalled/dead
            # buckets (sum 100%) — the distributed twin of the stall verdict
            out.write("-- fleet efficiency " + "-" * 60 + "\n")
            for line in fleet_lines:
                out.write(line + "\n")
        if self.dropped_events:
            out.write(
                f"(note: {self.dropped_events} raw span events dropped past "
                f"the {MAX_EVENTS}-event cap; aggregates above are complete)\n"
            )
        out.write("-" * 80 + "\n")


def percentile(values: list[float], p: float) -> float:
    """Nearest-rank percentile over an unsorted list."""
    if not values:
        return 0.0
    s = sorted(values)
    idx = int(round((p / 100.0) * (len(s) - 1)))
    return s[max(0, min(idx, len(s) - 1))]


def wire_span_stats(s: dict) -> dict:
    """Aggregate one serialized stage entry (a ``context_doc`` ``spans``
    value off the wire) into count/total/mean/p50/p95/max — the single
    place the remote span schema is parsed, shared by :meth:`report` and
    the metrics ``remote`` block."""
    count = int(s.get("count", 0))
    total = float(s.get("total", 0.0))
    values = list(s.get("values") or [])
    return {
        "count": count,
        "total": total,
        "mean": total / count if count else 0.0,
        "p50": percentile(values, 50),
        "p95": percentile(values, 95),
        "max": float(s.get("max", 0.0)),
    }


# -- module-level surface ---------------------------------------------------

# default context: library users who never enter scan_context() (or worker
# threads that never activate() one) record here, preserving the old
# process-global trace.* behavior behind the same API
_default_ctx = TraceContext(name="process")
_current: contextvars.ContextVar[TraceContext | None] = contextvars.ContextVar(
    "trivy_tpu_obs_ctx", default=None
)


def current() -> TraceContext:
    """The active trace context (contextvar, falling back to the process
    default)."""
    return _current.get() or _default_ctx


@contextmanager
def scan_context(name: str = "scan", enabled: bool | None = None,
                 trace_id: str | None = None,
                 parent_span_id: int | None = None):
    """Enter a fresh per-scan context. ``enabled=None`` inherits the process
    default's enabled bit (set by :func:`enable` / the ``--trace`` flag).
    ``trace_id``/``parent_span_id`` join an existing distributed trace (a
    server handling a client's ``traceparent``) instead of minting one."""
    ctx = TraceContext(
        name=name,
        enabled=_default_ctx.enabled if enabled is None else enabled,
        trace_id=trace_id,
        parent_span_id=parent_span_id,
    )
    token = _current.set(ctx)
    try:
        yield ctx
    finally:
        _current.reset(token)


@contextmanager
def activate(ctx: TraceContext):
    """Re-enter an existing context from a worker thread. Contextvars do not
    propagate into threads started before (or outside) a scan, so pipeline
    worker loops wrap themselves in ``activate(ctx)`` with the context their
    spawner captured."""
    token = _current.set(ctx)
    try:
        yield ctx
    finally:
        _current.reset(token)


def enable() -> None:
    """Enable tracing on the process-default context and on future
    ``scan_context(enabled=None)`` scopes (the ``--trace`` flag)."""
    _default_ctx.enabled = True


def disable() -> None:
    _default_ctx.enabled = False


def enabled() -> bool:
    return current().enabled


def span(name: str):
    return current().span(name)


def add(name: str, seconds: float) -> None:
    current().add(name, seconds)


def count(name: str, n: int = 1) -> None:
    current().count(name, n)


def health_count(name: str, n: int = 1) -> None:
    current().health_count(name, n)


def note_scan_degraded() -> None:
    """Record one scan-degradation event everywhere it must surface: the
    always-on health channel (folded into the report's ``Degraded`` flag)
    and the process-global Prometheus counter on ``GET /metrics``. Shared
    by every rung that degrades (device loop, license scorer, backend-init
    fallback) so the two surfaces cannot drift apart."""
    from trivy_tpu.obs import metrics as obs_metrics
    from trivy_tpu.obs import recorder as _recorder

    current().health_count("scan.degraded")
    obs_metrics.REGISTRY.counter(
        "trivy_tpu_scan_degraded_total",
        "Scans that completed on a degraded (host-fallback) path",
    ).inc()
    _recorder.record("degrade", "scan.degraded")


def sample(name: str, value: float) -> None:
    current().sample(name, value)


def report(out=None) -> None:
    current().report(out)


_HEX = set("0123456789abcdef")


def traceparent(span: Span | None = None) -> str:
    """W3C-style ``traceparent`` header for the active context:
    ``00-<32-hex trace id>-<16-hex parent span id>-01``. The parent id is
    ``span``'s (when the caller holds one open) or the calling thread's
    innermost open span; an all-zero parent means "join the trace id, no
    parent link" (tracing off on the client side)."""
    ctx = current()
    sid = span.span_id if span is not None else ctx.current_span_id()
    return f"00-{ctx.trace_id}-{(sid or 0):016x}-01"


def parse_traceparent(value: str | None) -> tuple[str, int | None] | None:
    """``(trace_id, parent_span_id)`` from a traceparent header, or None
    when absent/malformed. A zero parent id maps to None (no parent)."""
    if not value:
        return None
    parts = value.strip().lower().split("-")
    if len(parts) != 4:
        return None
    _ver, tid, pid, _flags = parts
    if len(tid) != 32 or len(pid) != 16:
        return None
    if set(tid) - _HEX or set(pid) - _HEX:
        return None
    if tid == "0" * 32:
        return None
    parent = int(pid, 16)
    return tid, (parent or None)


class heartbeat:
    """Progress logging for long-running operations: while the block runs,
    log one line every ``interval`` seconds (elapsed time, the scan's live
    telemetry summary — progress %, instantaneous MB/s, ETA — plus an
    optional ``progress()`` string) so server operators can tell a long
    scan from a hung one, and roughly *where* it is. Zero threads when the
    block finishes before the first beat fires is not attempted — the
    thread parks on an Event and exits quietly.
    """

    def __init__(self, logger, what: str, interval: float = 30.0, progress=None):
        self.logger = logger
        self.what = what
        self.interval = interval
        self.progress = progress
        self._stop = threading.Event()
        self._t0 = 0.0
        self._last_bytes: tuple[float, int] | None = None
        self._ctx: TraceContext | None = None

    def _telemetry(self) -> str:
        """The scan's live progress (bytes walked vs scanned, MB/s between
        beats, ETA) as one compact fragment, or '' when nothing has
        registered progress yet. The MB/s here is *instantaneous* — the
        delta since the previous beat — so a stalled pipeline shows 0.0
        even when the cumulative average still looks healthy."""
        ctx = self._ctx
        prog = ctx.progress_peek() if ctx is not None else None
        if prog is None:
            return ""
        snap = prog.snapshot()
        now = time.perf_counter()
        mbs = snap["mbs"]
        if self._last_bytes is not None:
            t0, b0 = self._last_bytes
            dt = now - t0
            if dt > 0:
                mbs = (snap["bytes_scanned"] - b0) / dt / (1 << 20)
        self._last_bytes = (now, snap["bytes_scanned"])
        parts = [f"{snap['ratio'] * 100:.1f}%", f"{mbs:.1f} MB/s"]
        if snap.get("eta_s") is not None:
            parts.append(f"ETA {snap['eta_s']:.0f}s")
        # effective-knob fragment: the live values when a controller is
        # adapting them, else the resolved config — so beats from two
        # differently-tuned scans stay comparable in the logs
        knobs = None
        ctl = ctx.tuning_controller if ctx is not None else None
        if ctl is not None:
            try:
                knobs = ctl.adapter.knobs()
            except Exception:
                knobs = None
        elif ctx is not None and isinstance(ctx.tuning, dict):
            cfg = ctx.tuning.get("config") or {}
            if cfg.get("feed_streams") or cfg.get("inflight"):
                knobs = cfg
        if knobs:
            frag = f"knobs s{knobs.get('feed_streams', 0)}" \
                   f"/i{knobs.get('inflight', 0)}"
            if knobs.get("arena_slabs"):
                frag += f"/a{knobs['arena_slabs']}"
            if ctl is not None:
                frag += f" ({len(ctl.decisions)} decisions)"
            parts.append(frag)
        # fleet fragment: the coordinator registers a status callable for
        # the duration of a fan-out (works with the telemetry poller off
        # too — replica health then degrades to breaker state, MB/s is
        # unknown), so a fleet scan's beats carry shard and replica counts
        status = getattr(ctx, "fleet_status", None) if ctx is not None \
            else None
        if status is not None:
            try:
                st = status()
                frag = (
                    f"fleet {st['shards_done']}/{st['shards_total']} "
                    f"shards, {st['healthy']}/{st['replicas']} healthy"
                )
                if st.get("breaker_open"):
                    frag += f", {st['breaker_open']} open"
                if st.get("fleet_mbs") is not None:
                    frag += f", {st['fleet_mbs']:.1f} MB/s"
                parts.append(frag)
            except Exception:
                pass
        # device fragment (flight recorder): compile count with per-beat
        # delta and HBM residency; a recompile storm since the previous
        # beat surfaces here immediately
        try:
            from trivy_tpu.obs import recorder as _recorder

            frag = _recorder.heartbeat_fragment(self)
            if frag:
                parts.append(frag)
        except Exception:
            pass
        return " [" + ", ".join(parts) + "]"

    def _loop(self) -> None:
        # the beat thread re-enters the spawning scan's context so the log
        # line (and the json formatter's trace_id field) correlates with
        # the client trace that caused this work
        with activate(self._ctx or _default_ctx):
            while not self._stop.wait(self.interval):
                extra = ""
                if self.progress is not None:
                    try:
                        extra = f" ({self.progress()})"
                    except Exception:
                        pass
                try:
                    extra = self._telemetry() + extra
                except Exception:
                    pass
                self.logger.info(
                    "%s in progress: %.0fs elapsed%s [trace %s]",
                    self.what,
                    time.perf_counter() - self._t0,
                    extra,
                    self._ctx.trace_id if self._ctx else "-",
                )

    def __enter__(self) -> "heartbeat":
        self._t0 = time.perf_counter()
        self._ctx = current()
        threading.Thread(target=self._loop, daemon=True).start()
        return self

    def __exit__(self, *exc) -> bool:
        self._stop.set()
        return False
