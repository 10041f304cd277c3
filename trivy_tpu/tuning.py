"""Telemetry-driven tuning: one typed config for every perf knob, plus the
offline/online machinery that closes the observability loop (ROADMAP item 4).

Three pieces:

- :class:`TuningConfig` — the knob sprawl (transfer streams, per-stream
  in-flight window, arena slab count, dispatch bucket ladder, host read
  ``--parallel``) consolidated into one typed config, resolved with strict
  precedence **CLI > env > autotune record > topology default** and carrying
  per-knob provenance (``source``) so every surface can say *why* a knob has
  its value. The secret feed, the mesh dispatch, the artifact read-ahead,
  the offline tuner, and the online controller all read the same object.

- **Offline autotune records** (:func:`load_autotune` /
  :func:`save_autotune`) — ``bench --autotune`` sweeps the knob space and
  records the optimum plus the measured surface into a versioned
  ``AUTOTUNE.json`` keyed by *topology fingerprint* (device kind, device
  count, link class). A later run on the same topology resolves unset knobs
  from the record; a mismatched fingerprint falls back to topology defaults
  LOUDLY (a record tuned for an 8-chip host must not silently steer a
  single-chip box).

- :class:`TuningController` — the online half: a per-scan control loop
  riding the live-telemetry cadence that adapts stream count, in-flight
  windows, and arena sizing mid-scan from gauge feedback (grow streams
  while work is queued and the device is unsaturated, shrink when
  device-bound, back off the in-flight window on OOM-split signals), with
  hysteresis and bounded ±1 steps so it cannot oscillate. The controller is
  itself first-class telemetry: every decision appends to a bounded
  decision log (input gauge snapshot, rule fired, knob delta) exported as
  Perfetto instant events + counter tracks in ``--trace-out``, a ``tuning``
  block in ``--metrics-out``/``--timeseries-out``, ``trivy_tpu_tuning_*``
  gauges on ``GET /metrics``, and a decisions column in the ``--live``
  line — an operator can replay every decision it made.

Zero-cost-when-off: with the controller off nothing here allocates — no
thread, no decision buffers, no gauges (the same bar as the telemetry
sampler; ``bench --smoke`` asserts it).
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field

from trivy_tpu import log

logger = log.logger("tuning")

AUTOTUNE_VERSION = 1
AUTOTUNE_DEFAULT_PATH = "AUTOTUNE.json"
ENV_TUNING_FILE = "TRIVY_TPU_TUNING_FILE"

# online-controller cadence: one decision window per tick. Defaults to 2x
# the telemetry sampler's 250 ms so each tick sees at least one fresh
# sample of every gauge (--tuning-interval / TRIVY_TPU_TUNING_INTERVAL)
DEFAULT_TUNING_INTERVAL = 0.5

# fleet replica-poller cadence (--fleet-telemetry-interval /
# TRIVY_TPU_FLEET_TELEMETRY_INTERVAL, 0 = off): one /metrics scrape per
# replica per tick. Coarser than the in-process sampler's 250 ms — each
# tick is N HTTP round trips, and replica gauges only refresh at the
# replica's own sampler cadence anyway
DEFAULT_FLEET_TELEMETRY_INTERVAL = 1.0

# mid-scan shard re-planning (--fleet-split-threshold /
# TRIVY_TPU_FLEET_SPLIT_THRESHOLD, 0 = off): an in-flight fs shard whose
# wall exceeds this x the median shard estimate while its replica shows
# no headroom is split at a directory boundary and the remainder
# re-scattered to survivors. Above the speculate multiplier (2.0) by
# design: a full-copy twin is cheaper than a re-plan, so it gets first go
DEFAULT_FLEET_SPLIT_THRESHOLD = 3.0

# knobs TuningConfig owns; order is the canonical display/serialize order
KNOBS = (
    "feed_streams", "inflight", "arena_slabs", "bucket_rungs", "parallel",
    "fleet_inflight", "dedup_store_mb", "license_gate_block_min",
    "license_row_width",
)

# env spellings per knob (the feed-path pair predates this module and is
# documented in BASELINE.md; the rest follow the TRIVY_TPU_ prefix rule)
_ENV_NAMES = {
    "feed_streams": "TRIVY_TPU_FEED_STREAMS",
    "inflight": "TRIVY_TPU_FEED_INFLIGHT",
    "arena_slabs": "TRIVY_TPU_ARENA_SLABS",
    "bucket_rungs": "TRIVY_TPU_BUCKET_RUNGS",
    "parallel": "TRIVY_TPU_PARALLEL",
    "fleet_inflight": "TRIVY_TPU_FLEET_INFLIGHT",
    "dedup_store_mb": "TRIVY_TPU_DEDUP_STORE_MB",
    "license_gate_block_min": "TRIVY_TPU_LICENSE_GATE_BLOCK_MIN",
    "license_row_width": "TRIVY_TPU_LICENSE_ROW_WIDTH",
}


def validate_interval(value, name: str) -> float:
    """A sampling/tuning interval from flag/env input: a finite float
    >= 0 (0 = disabled). Negative, NaN, infinite, or garbage values are
    rejected LOUDLY at resolution time — a degenerate cadence would
    otherwise spawn a busy-spinning (or never-firing) background thread
    the user only notices from the symptoms."""
    try:
        v = float(value)
    except (TypeError, ValueError):
        raise ValueError(f"{name}: not a number: {value!r}") from None
    if math.isnan(v) or math.isinf(v):
        raise ValueError(f"{name}: must be a finite number, got {value!r}")
    if v < 0:
        raise ValueError(f"{name}: must be >= 0 (0 disables), got {value!r}")
    return v


def validate_ratio(value, name: str) -> float:
    """A (0, 1] fraction from flag/env input, rejected loudly otherwise —
    a wire budget of 0 would force every batch raw silently, and > 1
    would 'compress' batches into more bytes than raw."""
    try:
        v = float(value)
    except (TypeError, ValueError):
        raise ValueError(f"{name}: not a number: {value!r}") from None
    if math.isnan(v) or not 0.0 < v <= 1.0:
        raise ValueError(f"{name}: must be in (0, 1], got {value!r}")
    return v


def topology_fingerprint(devices=None, link: str | None = None) -> str:
    """``<device kind>:<device count>:<link class>`` — the key autotune
    records live under. Device kind/count come from the jax device set;
    the link class from :func:`trivy_tpu.parallel.mesh.link_class` (env
    override ``TRIVY_TPU_LINK_CLASS``)."""
    if devices is None:
        import jax

        devices = jax.local_devices()
    platform = devices[0].platform if devices else "cpu"
    if link is None:
        from trivy_tpu.parallel.mesh import link_class

        link = link_class(platform)
    return f"{platform}:{len(devices)}:{link}"


@dataclass
class TuningConfig:
    """Every feed/dispatch perf knob, post-resolution. 0 means "derive the
    topology default at the point of use" (the secret scanner's stream
    heuristic, the artifact layer's DEFAULT_PARALLEL) — resolved values are
    always explicit in ``source`` so surfaces can tell tuned from auto."""

    feed_streams: int = 0   # transfer-stream worker threads (0 = auto)
    inflight: int = 0       # in-flight batches per stream (0 = auto: 2)
    arena_slabs: int = 0    # chunk-arena slab count (0 = derived bound)
    bucket_rungs: int = 0   # dispatch bucket-ladder depth (0 = default: 3)
    parallel: int = 0       # host read/analyze workers (0 = DEFAULT_PARALLEL)
    fleet_inflight: int = 0  # shard jobs in flight per fleet replica (0 = 2)
    dedup_store_mb: int = 0  # dedup hit-store LRU byte budget (0 = 32 MB)
    license_gate_block_min: int = 0  # shingle-gate density floor (0 = 16)
    license_row_width: int = 0  # license row-width ladder cap (0 = full)
    # compressed slab wire format (secret/compress.py). Modes, not int
    # optima — like controller/tuning_interval they resolve CLI > env >
    # default with provenance, but never from an autotune record
    compress: str = ""          # 'auto' | 'on' | 'off' ('' = auto at use)
    compress_min_ratio: float = 0.0  # per-batch wire budget fraction
    # (0 = codec default 0.875, the 7-bit-packing line)
    controller: bool = False          # online mid-scan adaptation
    tuning_interval: float = DEFAULT_TUNING_INTERVAL
    # fleet replica-poller cadence (0 = off: no poller thread, no parser
    # import, no fleet gauges); only consulted in --fleet mode
    fleet_telemetry_interval: float = DEFAULT_FLEET_TELEMETRY_INTERVAL
    # straggler split multiplier over the median shard estimate (0 = no
    # mid-scan re-planning); only consulted in --fleet mode
    fleet_split_threshold: float = DEFAULT_FLEET_SPLIT_THRESHOLD
    topology: str = ""                # fingerprint this config resolved for
    autotune_path: str | None = None  # record file consulted (if any)
    # per-knob provenance: cli | env | autotune | default
    source: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {
            "feed_streams": self.feed_streams,
            "inflight": self.inflight,
            "arena_slabs": self.arena_slabs,
            "bucket_rungs": self.bucket_rungs,
            "parallel": self.parallel,
            "fleet_inflight": self.fleet_inflight,
            "dedup_store_mb": self.dedup_store_mb,
            "license_gate_block_min": self.license_gate_block_min,
            "license_row_width": self.license_row_width,
            "compress": self.compress,
            "compress_min_ratio": self.compress_min_ratio,
            "controller": self.controller,
            "tuning_interval": self.tuning_interval,
            "fleet_telemetry_interval": self.fleet_telemetry_interval,
            "fleet_split_threshold": self.fleet_split_threshold,
            "topology": self.topology,
            "source": dict(self.source),
        }


def _env_int(env: dict, knob: str) -> int | None:
    raw = env.get(_ENV_NAMES[knob], "")
    if not raw:
        return None
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"{_ENV_NAMES[knob]}: not an integer: {raw!r}"
        ) from None
    return v if v > 0 else None


def load_autotune(path: str, topology: str) -> dict | None:
    """The autotune record for ``topology`` from a versioned AUTOTUNE.json,
    or None. Every fallback is loud: a missing/corrupt file, an alien
    version, and — most importantly — a topology-fingerprint miss each log
    a warning naming what was expected, so "silently running hand-me-down
    knobs from different hardware" cannot happen."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except FileNotFoundError:
        return None
    except (OSError, ValueError) as e:
        logger.warning(
            "autotune record %s unreadable (%s); using topology defaults",
            path, e,
        )
        return None
    if not isinstance(doc, dict) or doc.get("version") != AUTOTUNE_VERSION:
        logger.warning(
            "autotune record %s has version %r (want %d); using topology "
            "defaults", path, doc.get("version") if isinstance(doc, dict)
            else None, AUTOTUNE_VERSION,
        )
        return None
    records = doc.get("records") or {}
    rec = records.get(topology)
    if rec is None:
        logger.warning(
            "autotune record %s has no entry for topology %r (recorded: %s)"
            "; using topology defaults — run `bench.py --autotune` on this "
            "hardware to close the gap",
            path, topology, sorted(records) or "none",
        )
        return None
    best = rec.get("best")
    if not isinstance(best, dict):
        logger.warning(
            "autotune record %s[%s] carries no 'best' knobs; using "
            "topology defaults", path, topology,
        )
        return None
    return rec


def save_autotune(path: str, topology: str, best: dict, surface: list,
                  meta: dict | None = None) -> dict:
    """Merge one topology's sweep result into AUTOTUNE.json (other
    topologies' records are preserved) and return the full document."""
    doc: dict = {"version": AUTOTUNE_VERSION, "records": {}}
    try:
        with open(path) as f:
            prev = json.load(f)
    except FileNotFoundError:
        prev = None
    except (OSError, ValueError) as e:
        # rewriting over an unreadable file drops every OTHER topology's
        # swept optimum — that must be as loud as load_autotune's fallback
        logger.warning(
            "existing autotune record %s unreadable (%s); rewriting it "
            "fresh — prior topologies' records are lost", path, e,
        )
        prev = None
    if isinstance(prev, dict) and prev.get("version") == AUTOTUNE_VERSION:
        doc = prev
    elif prev is not None:
        logger.warning(
            "existing autotune record %s has version %r (want %d); "
            "rewriting it fresh — records for %s are lost",
            path, prev.get("version") if isinstance(prev, dict) else None,
            AUTOTUNE_VERSION,
            sorted((prev.get("records") or {}))
            if isinstance(prev, dict) else "unknown topologies",
        )
    doc.setdefault("records", {})[topology] = {
        "created_wall": time.time(),
        "best": {k: int(v) for k, v in best.items() if k in KNOBS},
        "surface": list(surface),
        **(meta or {}),
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    return doc


def resolve_tuning(opts: dict | None = None, env: dict | None = None,
                   autotune_path: str | None = None,
                   topology: str | None = None) -> TuningConfig:
    """Resolve the knob set with strict precedence per knob:
    **CLI (``opts``) > env > autotune record > topology default (0)**.

    ``opts`` carries the flag layer's already-resolved values (which fold
    config files in); 0/None there means "unset". ``autotune_path`` — an
    explicit path, else ``TRIVY_TPU_TUNING_FILE``, else ``AUTOTUNE.json``
    in the working directory when present — supplies swept optima for the
    current topology fingerprint; everything still unset stays 0 and the
    point of use derives its topology default (exactly today's heuristics,
    so an untuned run behaves identically to one before this module)."""
    opts = opts or {}
    env = os.environ if env is None else env
    # CLI option spellings per knob (the flag layer's dest names)
    cli_names = {
        "feed_streams": "secret_streams",
        "inflight": "secret_inflight",
        "arena_slabs": "secret_arena_slabs",
        "bucket_rungs": "secret_bucket_rungs",
        "parallel": "parallel",
        "fleet_inflight": "fleet_inflight",
        "dedup_store_mb": "secret_dedup_mb",
        "license_gate_block_min": "license_gate_block_min",
        "license_row_width": "license_row_width",
    }
    if autotune_path is None:
        autotune_path = opts.get("tuning_file") or env.get(ENV_TUNING_FILE)
    if autotune_path is None and os.path.exists(AUTOTUNE_DEFAULT_PATH):
        autotune_path = AUTOTUNE_DEFAULT_PATH
    # the topology fingerprint probes jax.local_devices(), which can
    # INITIALIZE an accelerator backend (libtpu acquires the chips).
    # Device-free scan paths (misconfig/vuln-only, cpu backend) resolve
    # tuning too — so fingerprint only when something will actually key
    # off it: an autotune record to look up, or a caller-supplied value
    if topology is None and autotune_path:
        topology = topology_fingerprint()
    record = (
        load_autotune(autotune_path, topology)
        if autotune_path and topology else None
    )
    rec_best = (record or {}).get("best") or {}
    topology = topology or ""

    cfg = TuningConfig(topology=topology, autotune_path=autotune_path)
    for knob in KNOBS:
        cli_v = opts.get(cli_names[knob])
        env_v = _env_int(env, knob)
        rec_v = rec_best.get(knob)
        if isinstance(cli_v, (int, float)) and int(cli_v) > 0:
            value, source = int(cli_v), "cli"
        elif env_v is not None:
            value, source = env_v, "env"
        elif isinstance(rec_v, (int, float)) and int(rec_v) > 0:
            value, source = int(rec_v), "autotune"
        else:
            value, source = 0, "default"
        setattr(cfg, knob, value)
        cfg.source[knob] = source
    # compressed-feed mode + wire budget (CLI > env > default, with
    # provenance; no autotune layer — the codec is a mode, not an optimum)
    raw_cmp = opts.get("secret_compress")
    if raw_cmp is None or raw_cmp == "":
        env_cmp = str(env.get("TRIVY_TPU_SECRET_COMPRESS", "")).lower()
        if env_cmp:
            if env_cmp in ("1", "true", "yes", "on"):
                env_cmp = "on"
            elif env_cmp in ("0", "false", "no", "off"):
                env_cmp = "off"
            if env_cmp not in ("auto", "on", "off"):
                raise ValueError(
                    f"TRIVY_TPU_SECRET_COMPRESS: use auto/on/off, got "
                    f"{env_cmp!r}"
                )
            cfg.compress, cfg.source["compress"] = env_cmp, "env"
        else:
            cfg.source["compress"] = "default"
    else:
        v = str(raw_cmp).lower()
        if v not in ("auto", "on", "off"):
            raise ValueError(
                f"--secret-compress: use auto/on/off, got {raw_cmp!r}"
            )
        cfg.compress, cfg.source["compress"] = v, "cli"
    raw_mr = opts.get("secret_compress_min_ratio")
    if raw_mr is None or raw_mr == 0:
        env_mr = env.get("TRIVY_TPU_SECRET_COMPRESS_MIN_RATIO") or None
        if env_mr is not None:
            cfg.compress_min_ratio = validate_ratio(
                env_mr, "TRIVY_TPU_SECRET_COMPRESS_MIN_RATIO"
            )
            cfg.source["compress_min_ratio"] = "env"
        else:
            cfg.source["compress_min_ratio"] = "default"
    else:
        cfg.compress_min_ratio = validate_ratio(
            raw_mr, "--secret-compress-min-ratio"
        )
        cfg.source["compress_min_ratio"] = "cli"
    # controller + cadence (no autotune layer: they are modes, not optima)
    raw_ctl = opts.get("tuning_controller")
    if raw_ctl is None:
        raw_ctl = env.get("TRIVY_TPU_TUNING_CONTROLLER", "")
        raw_ctl = str(raw_ctl).lower() in ("1", "true", "yes", "on")
    cfg.controller = bool(raw_ctl)
    raw_iv = opts.get("tuning_interval")
    if raw_iv is None:
        raw_iv = env.get("TRIVY_TPU_TUNING_INTERVAL") or None
    if raw_iv is not None:
        cfg.tuning_interval = validate_interval(
            raw_iv, "--tuning-interval/TRIVY_TPU_TUNING_INTERVAL"
        )
    # fleet telemetry cadence: same CLI > env > default ladder, explicit 0
    # (a mode, not an unset value) disables the poller entirely
    raw_fiv = opts.get("fleet_telemetry_interval")
    if raw_fiv is None:
        raw_fiv = env.get("TRIVY_TPU_FLEET_TELEMETRY_INTERVAL") or None
    if raw_fiv is not None:
        cfg.fleet_telemetry_interval = validate_interval(
            raw_fiv,
            "--fleet-telemetry-interval/TRIVY_TPU_FLEET_TELEMETRY_INTERVAL",
        )
    # straggler split multiplier: same ladder, explicit 0 turns mid-scan
    # re-planning off (validate_interval's >= 0 contract fits exactly)
    raw_fst = opts.get("fleet_split_threshold")
    if raw_fst is None:
        raw_fst = env.get("TRIVY_TPU_FLEET_SPLIT_THRESHOLD") or None
    if raw_fst is not None:
        cfg.fleet_split_threshold = validate_interval(
            raw_fst,
            "--fleet-split-threshold/TRIVY_TPU_FLEET_SPLIT_THRESHOLD",
        )
    if record is not None and any(
        s == "autotune" for s in cfg.source.values()
    ):
        logger.info(
            "tuning knobs loaded from %s for topology %s: %s",
            autotune_path, topology,
            {k: getattr(cfg, k) for k, s in cfg.source.items()
             if s == "autotune"},
        )
    return cfg


# -- admission budgets (ROADMAP item 1: multi-tenant serving) ----------------

# HBM proxy for one scan's device-side footprint: the chunk arena is the
# feed's residency ceiling (slabs x slab bytes — PR 6's RSS bound), so
# "how many scans fit" is budget / arena footprint. Slab bytes use the
# pallas-backend batch geometry (1024 rows x 8 KiB chunks); the CPU/XLA
# fallback slabs are smaller, which only makes this proxy conservative.
SLAB_PROXY_BYTES = 8 << 20
# feed.py arena derivation constants, mirrored here so budget resolution
# never imports the scanner (which initializes jax — a vuln-only server
# must not touch the accelerator to size its queue)
_FEED_QUEUE_DEPTH = 2
_ARENA_MARGIN = 2
_DEFAULT_STREAMS = 4
_DEFAULT_INFLIGHT = 2

HBM_BUDGET_ENV = "TRIVY_TPU_HBM_BUDGET_MB"
DEFAULT_HBM_BUDGET_MB = 1024
MAX_DERIVED_CONCURRENT = 32


def admission_budgets(cfg: TuningConfig | None = None,
                      env: dict | None = None) -> dict:
    """Concurrent-scan and queued-bytes budgets for the admission
    controller, resolved through :class:`TuningConfig` from the topology.

    ``per_scan_bytes`` is the arena footprint one scan pins host+device
    side (arena slabs x slab bytes — the HBM proxy); the concurrent-scan
    budget is how many such footprints fit ``TRIVY_TPU_HBM_BUDGET_MB``
    (default 1024 MB), and the queued-bytes budget caps the host-side
    queue at one full budget's worth of pending work — queueing more
    than the device can absorb in one wave only converts overload into
    memory growth.

    The budget multiplies by device count only when the caller supplies a
    ``cfg`` with a resolved topology fingerprint: the env-only resolution
    path (a detection-only scan server) deliberately never probes jax —
    acquiring accelerators to size a queue would be backwards — so it
    budgets for one device and the operator raises
    ``TRIVY_TPU_HBM_BUDGET_MB`` on bigger hosts.
    """
    env = os.environ if env is None else env
    if cfg is None:
        # autotune_path="" skips record discovery AND the jax topology
        # probe (resolve_tuning only fingerprints when a record is
        # consulted) — budget resolution stays accelerator-free
        cfg = resolve_tuning(autotune_path="", env=env)
    streams = cfg.feed_streams or _DEFAULT_STREAMS
    inflight = cfg.inflight or _DEFAULT_INFLIGHT
    slabs = cfg.arena_slabs or (
        _FEED_QUEUE_DEPTH + streams * inflight + _ARENA_MARGIN
    )
    slabs = max(2, slabs)
    per_scan_bytes = slabs * SLAB_PROXY_BYTES
    raw = env.get(HBM_BUDGET_ENV, "")
    if raw:
        try:
            budget_mb = int(raw)
        except ValueError:
            raise ValueError(
                f"{HBM_BUDGET_ENV}: not an integer: {raw!r}") from None
        if budget_mb <= 0:
            raise ValueError(f"{HBM_BUDGET_ENV}: must be > 0, got {raw!r}")
    else:
        budget_mb = DEFAULT_HBM_BUDGET_MB
    devices = 1
    if cfg.topology:
        try:  # "<kind>:<count>:<link>"
            devices = max(1, int(cfg.topology.split(":")[1]))
        except (IndexError, ValueError):
            devices = 1
    budget_bytes = budget_mb * (1 << 20) * devices
    max_concurrent = max(
        1, min(MAX_DERIVED_CONCURRENT, budget_bytes // per_scan_bytes)
    )
    return {
        "max_concurrent": int(max_concurrent),
        "queued_bytes": int(budget_bytes),
        "per_scan_bytes": int(per_scan_bytes),
        "hbm_budget_mb": budget_mb,
        "devices": devices,
    }


def stream_limit(initial: int) -> int:
    """Online-controller headroom above the configured stream count: the
    controller may grow streams up to 2x the starting point (capped at
    16). The
    extra worker threads are allocated parked, controller-on only."""
    return max(initial, min(16, initial * 2))


def inflight_limit(initial: int) -> int:
    """Controller headroom for the per-stream in-flight window (2x,
    capped at 8: deeper windows only add host-memory residency once the
    link is saturated)."""
    return max(initial, min(8, initial * 2))


# -- online controller -------------------------------------------------------

# decision-rate bound: the log is replay evidence, not a firehose — at the
# default cadence 256 entries cover >2 minutes of *continuous* decisions,
# far beyond what hysteresis+cooldown allow; older entries drop counted
MAX_DECISIONS = 256
# hysteresis: a candidate rule must hold for this many CONSECUTIVE ticks
# before it fires (one noisy gauge sample cannot move a knob) ...
HYSTERESIS_TICKS = 2
# ... and after a knob moves, this many ticks pass before the next decision
# (the outcome window: the new setting must show up in the gauges first)
COOLDOWN_TICKS = 3
# OOM backoff holds longer: re-growing into a fresh OOM would thrash
OOM_COOLDOWN_TICKS = 8
# dead band: grow only while device busy <= GROW, shrink only past SHRINK —
# the gap between them is the no-decision zone that kills oscillation
GROW_BUSY_MAX = 0.80
SHRINK_BUSY_MIN = 0.95

# the gauge snapshot every decision must carry (the decision-log schema
# bench --smoke asserts): enough to replay why the rule fired
DECISION_GAUGES = (
    "queue_depth", "busy_ratio", "link_mbs", "arena_free", "oom_splits",
)
DECISION_FIELDS = ("t", "rule", "knob", "from", "to", "gauges")


class TuningController:
    """Per-scan online knob controller.

    ``adapter`` is the running pipeline's control surface (the secret
    scanner's ``_ScanRun`` in production; a stub in tests):

    - ``knobs() -> {"feed_streams", "inflight", "arena_slabs"}`` (current)
    - ``limits() -> {"max_streams", "max_inflight", "max_arena_slabs"}``
    - ``raw_gauges() -> dict`` — instantaneous gauges plus cumulative
      ``*_total`` counters the controller differentiates per tick
    - ``set_streams(n)`` / ``set_inflight(n)`` / ``grow_arena(k) -> int``

    Control law (one bounded ±1 step per decision, hysteresis + cooldown
    between them, dead band ``GROW_BUSY_MAX``..``SHRINK_BUSY_MIN``):

    - ``oom-backoff``: OOM-shaped batch splits observed → shrink the
      in-flight window (immediate — an OOM is a discrete loud event, not
      gauge noise — then the long cooldown holds the backoff)
    - ``shrink-streams``: device busy past the dead band → one less stream
    - ``grow-streams``: work queued AND device under the dead band (the
      link, not the device, is the binding constraint) → one more stream,
      arena grown to match so backpressure doesn't choke the new stream
    - ``grow-inflight``: same signal with streams maxed → deepen windows

    :meth:`step` is pure decision logic over an already-derived gauge dict
    — the hysteresis/convergence tests drive it with synthetic feeds, no
    threads or scans involved.
    """

    def __init__(self, adapter, ctx=None, interval: float | None = None,
                 clock=time.perf_counter):
        self.adapter = adapter
        self.ctx = ctx
        self.interval = (
            DEFAULT_TUNING_INTERVAL if interval is None else interval
        )
        self.clock = clock
        self.ticks = 0
        self.cooldown = 0
        self._pending: str | None = None
        self._streak = 0
        self._last_raw: dict | None = None
        self._last_t = 0.0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._gauges_set = False
        self._lock = threading.Lock()
        self.decisions: deque = deque(maxlen=MAX_DECISIONS)
        self.dropped = 0
        initial = dict(adapter.knobs())
        # the live document surfaces read (ctx.tuning["controller"]):
        # mutated in place under _lock, snapshotted by doc()
        self._doc = {
            "enabled": True,
            "interval": self.interval,
            "initial": initial,
            "current": dict(initial),
            "ticks": 0,
            "decisions": 0,
        }
        if ctx is not None:
            # surfaces (export, --live, heartbeat) snapshot the decision
            # log through ctx.tuning_doc() -> doc()
            ctx.tuning_controller = self

    # -- decision core ------------------------------------------------------

    def _candidate(self, g: dict) -> str | None:
        k = self.adapter.knobs()
        lim = self.adapter.limits()
        if g.get("oom_splits", 0) > 0 and k["inflight"] > 1:
            return "oom-backoff"
        busy = g.get("busy_ratio", 0.0)
        if busy >= SHRINK_BUSY_MIN and k["feed_streams"] > 1:
            return "shrink-streams"
        if g.get("queue_depth", 0.0) >= 1 and busy <= GROW_BUSY_MAX:
            if k["feed_streams"] < lim["max_streams"]:
                return "grow-streams"
            if k["inflight"] < lim["max_inflight"]:
                return "grow-inflight"
        return None

    def _record(self, t: float, rule: str, knob: str, old: int, new: int,
                g: dict) -> dict:
        d = {
            "t": round(t, 3),
            "rule": rule,
            "knob": knob,
            "from": int(old),
            "to": int(new),
            "gauges": {
                name: round(float(g.get(name, 0.0)), 4)
                for name in DECISION_GAUGES
            },
        }
        with self._lock:
            if len(self.decisions) == self.decisions.maxlen:
                self.dropped += 1
            self.decisions.append(d)
            self._doc["current"][knob] = int(new)
            self._doc["decisions"] = len(self.decisions) + self.dropped
            if self.dropped:
                self._doc["dropped"] = self.dropped
        from trivy_tpu.obs import recorder as flight

        flight.record(
            "tuning", f"{rule}: {knob} {int(old)}->{int(new)}", ctx=self.ctx,
        )
        return d

    def _apply(self, rule: str, g: dict, t: float) -> list[dict]:
        a = self.adapter
        k = a.knobs()
        out = []
        if rule == "oom-backoff":
            new = max(1, k["inflight"] - 1)
            if new != k["inflight"]:
                a.set_inflight(new)
                out.append(self._record(
                    t, rule, "inflight", k["inflight"], new, g))
        elif rule == "shrink-streams":
            new = max(1, k["feed_streams"] - 1)
            if new != k["feed_streams"]:
                a.set_streams(new)
                out.append(self._record(
                    t, rule, "feed_streams", k["feed_streams"], new, g))
        elif rule == "grow-streams":
            new = min(a.limits()["max_streams"], k["feed_streams"] + 1)
            if new != k["feed_streams"]:
                a.set_streams(new)
                out.append(self._record(
                    t, rule, "feed_streams", k["feed_streams"], new, g))
                # match the arena to the new stream's window so slab
                # backpressure doesn't immediately starve it
                grown = a.grow_arena(max(1, k["inflight"]))
                if grown != k["arena_slabs"]:
                    out.append(self._record(
                        t, rule, "arena_slabs", k["arena_slabs"], grown, g))
        elif rule == "grow-inflight":
            new = min(a.limits()["max_inflight"], k["inflight"] + 1)
            if new != k["inflight"]:
                a.set_inflight(new)
                out.append(self._record(
                    t, rule, "inflight", k["inflight"], new, g))
                grown = a.grow_arena(k["feed_streams"])
                if grown != k["arena_slabs"]:
                    out.append(self._record(
                        t, rule, "arena_slabs", k["arena_slabs"], grown, g))
        return out

    def step(self, g: dict, t: float | None = None) -> list[dict]:
        """One control tick over a derived gauge dict (keys:
        :data:`DECISION_GAUGES`); returns the decisions fired (usually
        none). OOM backoff fires immediately; every other rule must
        survive :data:`HYSTERESIS_TICKS` consecutive ticks, and any firing
        opens a cooldown window."""
        self.ticks += 1
        with self._lock:
            self._doc["ticks"] = self.ticks
        if t is None:
            t = self.ticks * self.interval
        if self.cooldown > 0:
            self.cooldown -= 1
            self._pending, self._streak = None, 0
            return []
        cand = self._candidate(g)
        if cand is None:
            self._pending, self._streak = None, 0
            return []
        if cand == "oom-backoff":
            self._pending, self._streak = None, 0
            self.cooldown = OOM_COOLDOWN_TICKS
            return self._apply(cand, g, t)
        if cand != self._pending:
            self._pending, self._streak = cand, 1
            return []
        self._streak += 1
        if self._streak < HYSTERESIS_TICKS:
            return []
        self._pending, self._streak = None, 0
        self.cooldown = COOLDOWN_TICKS
        return self._apply(cand, g, t)

    # -- gauge derivation ---------------------------------------------------

    def derive(self, raw: dict, now: float) -> dict:
        """Instantaneous decision gauges from a raw probe snapshot:
        cumulative ``*_total`` counters differentiate against the previous
        tick; everything else passes through."""
        g = {
            "queue_depth": float(raw.get("queue_depth", 0.0)),
            "arena_free": float(raw.get("arena_free", 0.0)),
            "busy_ratio": 0.0,
            "link_mbs": 0.0,
            "oom_splits": 0.0,
        }
        prev, prev_t = self._last_raw, self._last_t
        self._last_raw, self._last_t = dict(raw), now
        if prev is None:
            return g
        dt = now - prev_t
        if dt <= 0:
            return g
        g["busy_ratio"] = min(1.0, max(0.0, (
            raw.get("busy_seconds_total", 0.0)
            - prev.get("busy_seconds_total", 0.0)
        ) / dt))
        g["link_mbs"] = max(0.0, (
            raw.get("bytes_uploaded_total", 0.0)
            - prev.get("bytes_uploaded_total", 0.0)
        ) / dt / (1 << 20))
        g["oom_splits"] = max(0.0, (
            raw.get("batch_splits_total", 0.0)
            - prev.get("batch_splits_total", 0.0)
        ))
        return g

    def tick(self) -> list[dict]:
        """One live tick: read the adapter's raw gauges, derive, decide,
        and mirror knob values to the scan timeseries (counter tracks in
        --trace-out) and the process ``trivy_tpu_tuning_*`` gauges."""
        now = self.clock()
        try:
            raw = self.adapter.raw_gauges()
        except Exception as e:  # a dying pipeline must not kill the loop
            logger.debug("tuning gauge probe failed: %s", e)
            return []
        g = self.derive(raw, now)
        t = now - (self.ctx.created if self.ctx is not None else 0.0)
        fired = self.step(g, t)
        self._export_state(t)
        return fired

    def _export_state(self, t: float) -> None:
        k = self.adapter.knobs()
        ctx = self.ctx
        if ctx is not None:
            ts = getattr(ctx, "timeseries", None)
            if ts is None:
                # controller-on without a telemetry sampler: the knob
                # tracks still deserve a home in --trace-out
                from trivy_tpu.obs.timeseries import Timeseries

                ts = ctx.timeseries = Timeseries()
            for name, v in k.items():
                ts.record(f"tuning.{name}", t, float(v))
        from trivy_tpu.obs import metrics as obs_metrics

        # per-scan trace label: concurrent controller-on scans must not
        # clobber each other's knob gauges, and one scan's stop() must not
        # retire another's state — same cardinality discipline as
        # trivy_tpu_scan_progress_ratio{trace=} (label retired at stop)
        trace = self.ctx.trace_id if self.ctx is not None else "anon"
        reg = obs_metrics.REGISTRY
        for name, v in k.items():
            reg.gauge(
                f"trivy_tpu_tuning_{name}",
                f"Current value of the {name} tuning knob (online "
                f"controller attached)",
                labelnames=("trace",),
            ).set(float(v), trace=trace)
        reg.counter(
            "trivy_tpu_tuning_decisions_total",
            "Online tuning-controller decisions fired",
        )  # registered so a scrape sees 0 before the first decision
        self._gauges_set = True

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "TuningController":
        if self.interval <= 0:
            return self
        trace8 = (self.ctx.trace_id[:8] if self.ctx is not None else "anon")
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"tuning-controller-{trace8}",
        )
        self._thread.start()
        return self

    def _loop(self) -> None:
        from trivy_tpu import obs

        ctx = self.ctx
        cm = obs.activate(ctx) if ctx is not None else None
        if cm is not None:
            cm.__enter__()
        try:
            while not self._stop.wait(self.interval):
                try:
                    fired = self.tick()
                except Exception as e:
                    logger.debug("tuning tick failed: %s", e)
                    continue
                for d in fired:
                    from trivy_tpu.obs import metrics as obs_metrics

                    obs_metrics.REGISTRY.counter(
                        "trivy_tpu_tuning_decisions_total",
                        "Online tuning-controller decisions fired",
                    ).inc()
                    logger.info(
                        "tuning: %s %s %d -> %d (busy %.2f, queue %.1f, "
                        "link %.1f MB/s)",
                        d["rule"], d["knob"], d["from"], d["to"],
                        d["gauges"]["busy_ratio"], d["gauges"]["queue_depth"],
                        d["gauges"]["link_mbs"],
                    )
        finally:
            if cm is not None:
                cm.__exit__(None, None, None)

    def stop(self, timeout: float = 5.0) -> None:
        """Stop the loop (idempotent), freeze the final knob set into the
        document, and retire the process gauges so an idle fleet scrapes
        0-cardinality tuning state, not the last scan's knobs forever."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None
        with self._lock:
            self._doc["final"] = dict(self.adapter.knobs())
            self._doc["ticks"] = self.ticks
        if self._gauges_set:
            from trivy_tpu.obs import metrics as obs_metrics

            trace = self.ctx.trace_id if self.ctx is not None else "anon"
            reg = obs_metrics.REGISTRY
            for name in self._doc["final"]:
                reg.gauge(
                    f"trivy_tpu_tuning_{name}",
                    f"Current value of the {name} tuning knob (online "
                    f"controller attached)",
                    labelnames=("trace",),
                ).remove(trace=trace)
            self._gauges_set = False

    def doc(self) -> dict:
        """Snapshot of the decision log + knob state (the ``tuning``
        block's ``controller`` entry): initial/current/final knob dicts,
        tick count, and the bounded decision list — deltas sum exactly to
        ``final - initial`` per knob, the replay invariant tests assert."""
        with self._lock:
            out = dict(self._doc)
            out["current"] = dict(self._doc["current"])
            out["decision_log"] = [dict(d) for d in self.decisions]
            if "final" in out:
                out["final"] = dict(out["final"])
        return out
