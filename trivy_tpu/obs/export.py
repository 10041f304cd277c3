"""Trace/metrics/profile file export.

- :func:`write_chrome_trace` — Chrome trace-event JSON (the format Perfetto
  and ``chrome://tracing`` load): one ``X`` complete event per span, one
  named track per pipeline stage (and per device stream — mesh dispatch
  spans are named per device), thread_name metadata events labeling tracks.
  Remote contexts joined via ``TraceContext.ingest_remote`` (a server's
  half of a client-mode scan) render as additional processes (pid 2, 3,
  ...) in the same timeline, timestamp-aligned via wall clocks, so one
  file shows client tracks + server tracks + device streams under one
  trace id.
- :func:`write_metrics_json` — the aggregate view: per-stage histograms
  (count/total/mean/p50/p95/max), counters, sample stats, the stall-
  attribution verdict, and the per-rule/per-bucket profile. ``bench.py``
  embeds this dict into BENCH reps.
- :func:`write_profile_json` — just the cost-attribution view: the merged
  (client+server) per-rule/per-bucket profile plus the stall verdict and
  stage totals it must stay consistent with.
- :func:`context_doc` — the wire form of a context (bounded events +
  aggregates + profile) a scan server returns in its response.
- :func:`write_timeseries_json` — the live-telemetry series (link MB/s,
  arena occupancy, queue depths, per-device busy, progress) recorded by
  an attached :class:`trivy_tpu.obs.timeseries.Sampler`; the same series
  render into ``--trace-out`` timelines as Perfetto **counter tracks**
  (``"ph": "C"`` events), local and remote alike.

Every path-based writer gzips transparently when the destination ends in
``.gz`` — merged cross-process traces get large.
"""

from __future__ import annotations

import json
import os

from trivy_tpu.obs import TraceContext, percentile, wire_span_stats
from trivy_tpu.obs import recorder as _recorder
from trivy_tpu.obs import stall as _stall

# bounds for the wire form of a context (a scan response rides HTTP):
# events beyond the cap are dropped from the remote timeline — aggregates
# and the profile never drop — and per-stage reservoirs are truncated
WIRE_MAX_EVENTS = 4096
WIRE_RESERVOIR = 256


def _dump(doc: dict, dest, indent: int | None = None) -> None:
    """Write JSON to a file object or path; paths ending in .gz gzip."""
    if hasattr(dest, "write"):
        json.dump(doc, dest, indent=indent)
        return
    if str(dest).endswith(".gz"):
        import gzip

        with gzip.open(dest, "wt") as f:
            json.dump(doc, f, indent=indent)
    else:
        with open(dest, "w") as f:
            json.dump(doc, f, indent=indent)


def _wire_values(values: list[float]) -> list[float]:
    """Bound a stage's duration reservoir for the wire by a uniform strided
    pick — a plain ``[:n]`` prefix would bias the receiver's percentiles
    toward the earliest (cold-cache, warm-up) spans of the scan."""
    n = len(values)
    if n <= WIRE_RESERVOIR:
        return values
    step = n / WIRE_RESERVOIR
    return [values[int(i * step)] for i in range(WIRE_RESERVOIR)]


def context_doc(ctx: TraceContext, max_events: int = WIRE_MAX_EVENTS) -> dict:
    """Serialize a context for the wire: bounded raw events (start times
    rebased to the context's creation so the receiver can align them via
    ``created_wall``), exact per-stage aggregates with a bounded percentile
    reservoir, counters, samples, and the scan profile."""
    with ctx._lock:
        events = [
            {
                "name": sp.name,
                "span_id": sp.span_id,
                "parent_id": sp.parent_id,
                "start": round(sp.start - ctx.created, 6),
                "duration": round(sp.duration, 6),
                "thread": sp.thread,
            }
            for sp in ctx.events[:max_events]
        ]
        dropped = ctx.dropped_events + max(0, len(ctx.events) - max_events)
        spans = {
            name: {
                "count": a.count,
                "total": round(a.total, 6),
                "max": round(a.vmax, 6),
                "threads": len(a.threads),
                "values": [round(v, 6) for v in _wire_values(a.values)],
            }
            for name, a in ctx.durations.items()
            if a.count
        }
        counters = dict(ctx.counters)
        samples = {k: [v[0], v[1], v[2]] for k, v in ctx.samples.items()}
        prof = ctx._profile
        prog = ctx._progress
        ts = ctx.timeseries
    doc = {
        "trace_id": ctx.trace_id,
        "name": ctx.name,
        "pid": os.getpid(),
        "created_wall": ctx.created_wall,
        "root_parent_id": ctx.parent_span_id,
        "events": events,
        "spans": spans,
        "counters": counters,
        "samples": samples,
        "dropped_events": dropped,
    }
    if prof is not None:
        doc["profile"] = prof.to_dict()
    if prog is not None:
        doc["progress"] = prog.snapshot()
    if ts is not None:
        # live-telemetry series ride the wire too (bounded), so a merged
        # client export carries the server's counter tracks
        doc["timeseries"] = ts.to_doc()
    tuning = ctx.tuning_doc()
    if tuning is not None:
        # resolved knobs + the controller decision log: a client-mode scan
        # can replay the SERVER's mid-scan adaptations from its own export
        doc["tuning"] = tuning
    return doc


def chrome_trace_events(ctx: TraceContext) -> list[dict]:
    """Flatten a context — plus any joined remote contexts — into
    trace-event dicts (sorted by start time per process)."""
    events: list[dict] = [
        {
            "name": "process_name",
            "ph": "M",
            "pid": 1,
            "tid": 0,
            "args": {"name": f"trivy-tpu {ctx.name} [{ctx.trace_id}]"},
        }
    ]
    # track per (pid, stage, thread): a stage whose spans run concurrently
    # in N threads (the confirm pool) gets N tracks ("stage", "stage #2",
    # ...) instead of one track with overlapping slices Perfetto would
    # mangle; tids are globally unique across processes
    tids: dict[tuple[int, str, int], int] = {}
    per_stage_threads: dict[tuple[int, str], int] = {}

    def tid_for(pid: int, name: str, thread: int) -> int:
        key = (pid, name, thread)
        t = tids.get(key)
        if t is None:
            t = tids[key] = len(tids) + 1
            skey = (pid, name)
            n = per_stage_threads[skey] = per_stage_threads.get(skey, 0) + 1
            label = name if n == 1 else f"{name} #{n}"
            events.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": t,
                    "args": {"name": label},
                }
            )
        return t

    def emit(pid: int, trace_id: str, name: str, thread: int, span_id,
             parent_id, ts_us: float, dur_s: float) -> None:
        args = {"trace_id": trace_id, "span_id": span_id}
        if parent_id is not None:
            args["parent_span_id"] = parent_id
        events.append(
            {
                "name": name,
                "cat": name.split(".", 1)[0],
                "ph": "X",
                "pid": pid,
                "tid": tid_for(pid, name, thread),
                # clamp: add()-style backdated spans can start a hair
                # before the context's own creation timestamp
                "ts": max(0.0, round(ts_us, 3)),
                "dur": round(dur_s * 1e6, 3),
                "args": args,
            }
        )

    def emit_counters(pid: int, series: dict, base_us: float = 0.0) -> None:
        """Perfetto counter tracks (``"ph": "C"``): one track per telemetry
        series, point timestamps aligned with the span clock."""
        for name, doc in sorted(series.items()):
            for t, v in doc.get("points", ()):
                events.append(
                    {
                        "name": name,
                        "cat": "telemetry",
                        "ph": "C",
                        "pid": pid,
                        "tid": 0,
                        "ts": max(0.0, round(base_us + t * 1e6, 3)),
                        "args": {"value": v},
                    }
                )

    def emit_tuning(pid: int, tuning: dict, base_us: float = 0.0) -> None:
        """Online-controller decisions as Perfetto INSTANT events
        (``"ph": "i"``, process-scoped): each carries the rule, the knob
        delta, and the gauge snapshot that fired it, landing on the same
        timeline as the knob-value counter tracks — so an operator can
        point at any mid-scan knob step and read why."""
        ctl = tuning.get("controller") or {}
        for d in ctl.get("decision_log", ()):
            events.append(
                {
                    "name": f"tuning:{d.get('rule', '?')}",
                    "cat": "tuning",
                    "ph": "i",
                    "s": "p",
                    "pid": pid,
                    "tid": 0,
                    "ts": max(0.0, round(base_us + d.get("t", 0.0) * 1e6, 3)),
                    "args": {
                        "knob": d.get("knob"),
                        "from": d.get("from"),
                        "to": d.get("to"),
                        "gauges": d.get("gauges", {}),
                    },
                }
            )

    with ctx._lock:
        spans = list(ctx.events)
        remote_docs = list(ctx.remote)
        ts = ctx.timeseries
    for sp in sorted(spans, key=lambda s: s.start):
        emit(
            1, ctx.trace_id, sp.name, sp.thread, sp.span_id, sp.parent_id,
            (sp.start - ctx.created) * 1e6, sp.duration,
        )
    if ts is not None:
        emit_counters(1, ts.to_doc())
    # device-lane counter tracks (flight recorder): cumulative compile
    # count and HBM-resident bytes over the scan timeline
    dev_series = _recorder.counter_series(ctx)
    if dev_series:
        emit_counters(1, dev_series)
    local_tuning = ctx.tuning_doc()
    if local_tuning is not None:
        emit_tuning(1, local_tuning)
    for i, doc in enumerate(remote_docs):
        pid = 2 + i
        events.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {
                    "name": f"trivy-tpu {doc.get('name', 'remote')} "
                            f"[{doc.get('trace_id', '')}] (remote)"
                },
            }
        )
        # perf_counter clocks don't compare across processes; align the
        # remote timeline by the wall-clock delta between context creations
        base_us = (
            doc.get("created_wall", ctx.created_wall) - ctx.created_wall
        ) * 1e6
        remote_spans = sorted(doc.get("events", []), key=lambda s: s["start"])
        if remote_spans:
            # wall clocks skew across hosts: a server clock running behind
            # would push the aligned track negative, and the per-event
            # clamp would collapse its early spans onto t=0 — shift the
            # whole track instead so relative timing survives
            first_us = base_us + remote_spans[0]["start"] * 1e6
            if first_us < 0:
                base_us -= first_us
        for sp in remote_spans:
            emit(
                pid, doc.get("trace_id", ""), sp["name"],
                sp.get("thread", 0), sp.get("span_id"), sp.get("parent_id"),
                base_us + sp["start"] * 1e6, sp.get("duration", 0.0),
            )
        if doc.get("timeseries"):
            emit_counters(pid, doc["timeseries"], base_us)
        if doc.get("tuning"):
            emit_tuning(pid, doc["tuning"], base_us)
    fleet = getattr(ctx, "fleet", None)
    if fleet:
        # fleet replicas join the one merged timeline as their own
        # processes, after the remote shard-trace pids: the poller's
        # per-replica health series (scraped on the coordinator's own
        # clock, so base shift 0) render as counter tracks
        for i, (host, rep) in enumerate(
            sorted((fleet.get("replicas") or {}).items())
        ):
            pid = 2 + len(remote_docs) + i
            events.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": 0,
                    "args": {"name": f"trivy-tpu fleet replica {host}"},
                }
            )
            if rep.get("series"):
                emit_counters(pid, rep["series"])
    return events


def write_chrome_trace(ctx: TraceContext, dest) -> None:
    """Write Perfetto-loadable trace-event JSON to a path or file object
    (transparent gzip when the path ends in .gz)."""
    with ctx._lock:
        remote_dropped = sum(d.get("dropped_events", 0) for d in ctx.remote)
    doc = {
        "traceEvents": chrome_trace_events(ctx),
        "displayTimeUnit": "ms",
        "otherData": {
            "trace_id": ctx.trace_id,
            "name": ctx.name,
            "dropped_events": ctx.dropped_events + remote_dropped,
        },
    }
    _dump(doc, dest)


def metrics_dict(ctx: TraceContext) -> dict:
    """Aggregate metrics as one JSON-serializable dict."""
    with ctx._lock:
        counters = dict(sorted(ctx.counters.items()))
        samples = {
            k: (v[0], v[1], v[2]) for k, v in sorted(ctx.samples.items())
        }
        remote_docs = list(ctx.remote)
    doc = {
        "trace_id": ctx.trace_id,
        "name": ctx.name,
        "spans": {
            name: {k: round(v, 6) for k, v in s.items()}
            for name, s in ctx.stage_stats().items()
        },
        "counters": counters,
        "samples": {
            name: {
                "count": count,
                "mean": round(total / max(1, count), 3),
                "max": vmax,
            }
            for name, (count, total, vmax) in samples.items()
            if count
        },
        "stall": _stall.attribution(ctx),
        "profile": ctx.merged_profile_dict(),
        "dropped_events": ctx.dropped_events,
    }
    with ctx._lock:
        gauges = dict(sorted(ctx.gauges.items()))
    if gauges:
        # levels the scan ran at (only present when set, so other exports
        # stay byte-identical)
        doc["gauges"] = gauges
    if ctx.timeseries is not None:
        # aggregate view of the live-telemetry series (count/mean/max/
        # p50/p95 per series); full points ride --timeseries-out
        doc["timeseries"] = ctx.timeseries.summary()
    if ctx._progress is not None:
        doc["progress"] = ctx._progress.snapshot()
    tuning = ctx.tuning_doc()
    if tuning is not None:
        # effective knobs + decision log: --metrics-out consumers (and the
        # bench reps embedding this dict) see WHAT the scan ran with and
        # every mid-scan change the controller made
        doc["tuning"] = tuning
    dev = _recorder.device_doc()
    if dev is not None:
        # device-lane accounting (flight recorder): compile ledger per
        # (kernel, shape-bucket), recompile storms, and the HBM residency
        # ledger — only present when the recorder observed device work, so
        # recorder-off exports stay byte-identical
        doc["device"] = dev
    fleet = getattr(ctx, "fleet", None)
    if fleet:
        # fleet telemetry plane: per-replica headroom/health summaries
        # (full series points ride --timeseries-out, same split as the
        # local sampler series) — only present on fleet scans with the
        # poller on, so single-host exports stay byte-identical
        doc["fleet"] = {
            "interval_s": fleet.get("interval_s"),
            "replicas": {
                host: {k: v for k, v in rep.items() if k != "series"}
                for host, rep in (fleet.get("replicas") or {}).items()
            },
        }
    if remote_docs:
        doc["remote"] = [
            {
                "trace_id": d.get("trace_id"),
                "name": d.get("name"),
                "spans": {
                    name: {
                        k: round(v, 6) if isinstance(v, float) else v
                        for k, v in wire_span_stats(s).items()
                    }
                    for name, s in sorted((d.get("spans") or {}).items())
                },
                "counters": dict(sorted((d.get("counters") or {}).items())),
            }
            for d in remote_docs
        ]
    return doc


def write_metrics_json(ctx: TraceContext, dest) -> None:
    _dump(metrics_dict(ctx), dest, indent=2)


def profile_dict(ctx: TraceContext) -> dict:
    """The cost-attribution view: merged client+server profile, the stall
    verdict it refines, and local stage totals (ms) so consumers can check
    the per-rule times sum consistently with the pipeline stages."""
    return {
        "trace_id": ctx.trace_id,
        "name": ctx.name,
        "profile": ctx.merged_profile_dict(),
        "stall": _stall.attribution(ctx),
        "stage_total_ms": {
            name: round(s["total"] * 1e3, 3)
            for name, s in ctx.stage_stats().items()
        },
    }


def write_profile_json(ctx: TraceContext, dest) -> None:
    _dump(profile_dict(ctx), dest, indent=2)


def timeseries_dict(ctx: TraceContext) -> dict:
    """The full live-telemetry view: every sampled series' points (local
    plus any joined remote contexts'), the per-series summary, and the
    final progress snapshot — what ``--timeseries-out`` writes."""
    with ctx._lock:
        remote_docs = list(ctx.remote)
        ts = ctx.timeseries
        prog = ctx._progress
    doc = {
        "trace_id": ctx.trace_id,
        "name": ctx.name,
        "series": ts.to_doc(max_points=ts._capacity) if ts is not None else {},
        "summary": ts.summary() if ts is not None else {},
    }
    if prog is not None:
        doc["progress"] = prog.snapshot()
    tuning = ctx.tuning_doc()
    if tuning is not None:
        doc["tuning"] = tuning
    remote = [
        {
            "trace_id": d.get("trace_id"),
            "name": d.get("name"),
            "series": d["timeseries"],
            **({"progress": d["progress"]} if d.get("progress") else {}),
        }
        for d in remote_docs
        if d.get("timeseries")
    ]
    if remote:
        doc["remote"] = remote
    fleet = getattr(ctx, "fleet", None)
    if fleet:
        # the full-points twin of metrics_dict's fleet summary block
        doc["fleet"] = fleet
    return doc


def write_timeseries_json(ctx: TraceContext, dest) -> None:
    _dump(timeseries_dict(ctx), dest, indent=2)
