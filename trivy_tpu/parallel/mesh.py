"""Device mesh construction and sharded kernel wrappers.

Design (scaling-book recipe): pick a mesh, annotate shardings, let XLA insert
the collectives. Scan workloads here are data-parallel over the chunk-batch
axis — chunks shard across 'data', rule tables are tiny and replicated;
reductions (per-rule hit counts for telemetry) psum over 'data'. The 'model'
axis exists for kernels with a large table dimension (license n-gram corpus
shards, advisory-DB shards) that shard their lookup tables.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from trivy_tpu import faults, log, obs
from trivy_tpu.obs import metrics as obs_metrics
from trivy_tpu.obs import recorder as flight

logger = log.logger("parallel:mesh")

# per-device circuit breaker defaults: a device is excluded from dispatch
# after this many CONSECUTIVE failures, then re-probed on an exponential
# backoff schedule (one probe dispatch at a time; success closes, failure
# doubles the backoff)
BREAKER_THRESHOLD = 3
BREAKER_PROBE_BACKOFF = 1.0  # seconds until the first re-probe
BREAKER_MAX_BACKOFF = 60.0
# a half-open probe whose outcome is never reported (scan generator closed
# with the probe batch still in flight) expires after this long, so the
# device is not excluded forever on a process-cached breaker
BREAKER_PROBE_TIMEOUT = 60.0

# breaker state surfaces on the process-global registry so the scan
# server's GET /metrics (which appends this registry) shows open breakers
_BREAKER_OPEN = obs_metrics.REGISTRY.gauge(
    "trivy_tpu_device_breaker_open",
    "1 while the per-device dispatch circuit breaker is open",
    labelnames=("device",),
)
_DEVICE_FAILURES = obs_metrics.REGISTRY.counter(
    "trivy_tpu_device_failures_total",
    "Device dispatch/fetch failures observed by the breaker",
    labelnames=("device",),
)


def link_class(platform: str | None = None) -> str:
    """Coarse host→device link classification for the tuning topology
    fingerprint (``TuningConfig`` autotune records are keyed by device
    kind/count + this): ``host`` for CPU-backend virtual devices (one
    memory bus, no real link), ``pcie`` otherwise. Override with
    ``TRIVY_TPU_LINK_CLASS`` when the heuristic misreads a deployment."""
    import os

    override = os.environ.get("TRIVY_TPU_LINK_CLASS", "")
    if override:
        return override
    if platform is None:
        platform = jax.devices()[0].platform
    if platform in ("cpu", "METAL"):
        return "host"
    return "pcie"


class DevicesUnavailable(RuntimeError):
    """Every dispatch device is circuit-broken (or the device set is empty):
    the caller's last rung is the host fallback, not a retry."""


class CircuitBreaker:
    """Per-device dispatch circuit breaker.

    closed -> open after ``threshold`` consecutive failures; while open the
    device is excluded from :meth:`next_device`. After ``probe_backoff``
    seconds one probe dispatch is allowed (half-open): success closes the
    breaker, failure re-opens it with the backoff doubled (capped at
    ``max_backoff``). All transitions are logged and mirrored to the
    process-global metrics registry.
    """

    def __init__(
        self,
        n_devices: int,
        threshold: int = BREAKER_THRESHOLD,
        probe_backoff: float = BREAKER_PROBE_BACKOFF,
        max_backoff: float = BREAKER_MAX_BACKOFF,
        probe_timeout: float = BREAKER_PROBE_TIMEOUT,
        clock=time.monotonic,
        labels: list[str] | None = None,
    ):
        self.n = n_devices
        self.threshold = threshold
        self.probe_backoff = probe_backoff
        self.max_backoff = max_backoff
        self.probe_timeout = probe_timeout
        self.clock = clock
        self.labels = labels or [f"d{i}" for i in range(n_devices)]
        self._lock = threading.Lock()
        self._fails = [0] * n_devices  # consecutive failures
        self._open = [False] * n_devices
        self._open_until = [0.0] * n_devices  # next probe time while open
        self._backoff = [probe_backoff] * n_devices
        self._probing = [False] * n_devices  # one half-open probe at a time
        self._probe_at = [0.0] * n_devices  # when that probe was handed out
        # register a healthy (0) row per device up front: readers of the
        # breaker gauge — the admission controller's all-devices-open
        # early-shed, operators scraping /metrics — must see every device
        # the breaker covers, not only the ones that have ever failed.
        # setdefault, not set: breakers share the process-global gauge and
        # generic d<N> labels, so a second breaker's construction (e.g. a
        # new value-keyed shared scanner) must not wipe an open row and
        # un-shed an already-degraded fleet
        for lbl in self.labels:
            _BREAKER_OPEN.setdefault(0, device=lbl)

    def record_failure(self, i: int) -> None:
        _DEVICE_FAILURES.inc(device=self.labels[i])
        opened = 0
        with self._lock:
            self._fails[i] += 1
            if self._open[i]:
                if self._probing[i]:
                    # failed probe: re-open with doubled backoff
                    self._probing[i] = False
                    self._backoff[i] = min(
                        self._backoff[i] * 2, self.max_backoff
                    )
                    self._open_until[i] = self.clock() + self._backoff[i]
                    logger.warning(
                        "device %s probe failed; breaker re-opened for %.1fs",
                        self.labels[i], self._backoff[i],
                    )
                # else: a stale in-flight batch failing after the breaker
                # already opened — not a probe outcome, don't punish the
                # recovery schedule for it
            elif self._fails[i] >= self.threshold:
                self._open[i] = True
                self._open_until[i] = self.clock() + self._backoff[i]
                opened = self._fails[i]
                _BREAKER_OPEN.set(1, device=self.labels[i])
                logger.warning(
                    "device %s breaker OPEN after %d consecutive failures; "
                    "re-probing in %.1fs",
                    self.labels[i], self._fails[i], self._backoff[i],
                )
        if opened:
            flight.record(
                "breaker", f"device {self.labels[i]} OPEN",
                {"fails": opened},
            )
            flight.auto_emit("breaker-trip")

    def record_success(self, i: int) -> None:
        with self._lock:
            was_open = self._open[i]
            self._fails[i] = 0
            self._open[i] = False
            self._probing[i] = False
            self._backoff[i] = self.probe_backoff
        if was_open:
            _BREAKER_OPEN.set(0, device=self.labels[i])
            logger.info("device %s recovered; breaker closed", self.labels[i])
            flight.record("breaker", f"device {self.labels[i]} closed")

    def next_device(self, start: int) -> int | None:
        """First dispatchable device scanning round-robin from ``start``:
        closed devices always qualify; an open device qualifies only when
        its probe window has arrived and no probe is already in flight.
        Returns None when nothing is dispatchable."""
        now = self.clock()
        with self._lock:
            for off in range(self.n):
                i = (start + off) % self.n
                if not self._open[i]:
                    return i
                probe_free = (
                    not self._probing[i]
                    or now - self._probe_at[i] >= self.probe_timeout
                )
                if probe_free and now >= self._open_until[i]:
                    # probe-due open device: take it now — waiting for "no
                    # healthy device left" would mean a recovered device is
                    # never probed back in while any peer stays up
                    self._probing[i] = True
                    self._probe_at[i] = now
                    return i
            return None

    def is_open(self, i: int) -> bool:
        with self._lock:
            return self._open[i]

    def try_probe(self, i: int) -> bool:
        """Dispatch gate for callers bound to a FIXED device (the fleet
        coordinator's per-replica workers): True when ``i`` is closed, or
        open with its half-open probe due and unclaimed — in which case
        THIS call claims ``i``'s probe slot (and only ``i``'s; unlike
        :meth:`next_device`, no peer's slot is touched)."""
        now = self.clock()
        with self._lock:
            if not self._open[i]:
                return True
            probe_free = (
                not self._probing[i]
                or now - self._probe_at[i] >= self.probe_timeout
            )
            if probe_free and now >= self._open_until[i]:
                self._probing[i] = True
                self._probe_at[i] = now
                return True
            return False

    def open_devices(self) -> list[int]:
        with self._lock:
            return [i for i in range(self.n) if self._open[i]]

    def grow(self, label: str | None = None) -> int:
        """Append one closed slot (live replica join on an elastic fleet):
        the new device starts healthy with a fresh backoff ladder. Returns
        the new slot's index."""
        with self._lock:
            i = self.n
            self.n += 1
            self.labels.append(label or f"d{i}")
            self._fails.append(0)
            self._open.append(False)
            self._open_until.append(0.0)
            self._backoff.append(self.probe_backoff)
            self._probing.append(False)
            self._probe_at.append(0.0)
        _BREAKER_OPEN.setdefault(0, device=self.labels[i])
        return i

    def trip(self, i: int, reason: str = "") -> None:
        """Force slot ``i`` open NOW (out-of-band death verdict, e.g. the
        fleet telemetry poller observing consecutive dead scrapes) without
        burning the consecutive-failure count: the half-open probe ladder
        still governs recovery, so a replica that comes back is probed in
        on the normal schedule."""
        with self._lock:
            if self._open[i]:
                return
            self._fails[i] = max(self._fails[i], self.threshold)
            self._open[i] = True
            self._open_until[i] = self.clock() + self._backoff[i]
        _BREAKER_OPEN.set(1, device=self.labels[i])
        logger.warning(
            "device %s breaker TRIPPED%s; re-probing in %.1fs",
            self.labels[i], f" ({reason})" if reason else "",
            self._backoff[i],
        )
        flight.record(
            "breaker", f"device {self.labels[i]} OPEN",
            {"forced": True, "reason": reason},
        )
        flight.auto_emit("breaker-trip")

class DeviceBusyTracker:
    """Per-device busy-interval accounting for live utilization telemetry.

    A device is *busy* while at least one dispatched batch has not yet
    fetched: :meth:`begin` at placement, :meth:`end` when the caller
    reports the fetch outcome. Overlapping in-flight windows on one device
    merge into a single busy interval (dispatch is async and pipelined),
    so ``busy_seconds`` is wall time with work in flight — exactly the
    numerator of the sampler's per-interval busy fraction
    (Δbusy_seconds/Δt, the ``trivy_tpu_device_busy_ratio`` gauge).

    Leak shape: a batch dropped without an ``end`` (scan generator closed
    mid-flight) would pin the device busy; :meth:`end` tolerates the
    matching underflow and the sampler stops with the scan, so the error
    is bounded to that scan's final samples.
    """

    def __init__(self, n: int, clock=time.monotonic):
        self.n = max(1, n)
        self.clock = clock
        self._lock = threading.Lock()
        self._inflight = [0] * self.n
        self._busy = [0.0] * self.n
        self._since = [0.0] * self.n
        self._batches = [0] * self.n

    def begin(self, i: int | None) -> None:
        i = (i or 0) % self.n
        with self._lock:
            if self._inflight[i] == 0:
                self._since[i] = self.clock()
            self._inflight[i] += 1
            self._batches[i] += 1

    def batches(self) -> list[int]:
        """Batches placed on each device so far."""
        with self._lock:
            return list(self._batches)

    def end(self, i: int | None) -> None:
        i = (i or 0) % self.n
        with self._lock:
            if self._inflight[i] <= 0:
                return  # unmatched end (retry bookkeeping); never negative
            self._inflight[i] -= 1
            if self._inflight[i] == 0:
                self._busy[i] += self.clock() - self._since[i]

    def busy_seconds(self) -> list[float]:
        """Cumulative busy wall-time per device, including the currently
        open interval — monotonic, so samplers can safely differentiate."""
        now = self.clock()
        with self._lock:
            return [
                b + (now - s if f > 0 else 0.0)
                for b, s, f in zip(self._busy, self._since, self._inflight)
            ]

    def probe(self) -> dict[str, float]:
        """Telemetry-probe fragment: ``device.dN.busy_seconds_total``
        series (cumulative counters the sampler turns into busy ratios)."""
        return {
            f"device.d{i}.busy_seconds_total": s
            for i, s in enumerate(self.busy_seconds())
        }


_shard_map = jax.shard_map


def get_mesh(n_devices: int | None = None, model: int = 1, devices=None) -> Mesh:
    """A ('data', 'model') mesh over the given (or available, or first n)
    devices. Pass `devices` explicitly when mixing platforms (e.g. virtual
    CPU devices provisioned for a dry run on a TPU host)."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    n = len(devs)
    if n % model != 0:
        raise ValueError(f"{n} devices not divisible by model={model}")
    arr = np.array(devs).reshape(n // model, model)
    return Mesh(arr, ("data", "model"))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Chunk batches: leading batch axis over 'data', bytes replicated."""
    return NamedSharding(mesh, P("data", None))


def pad_batch(chunks: np.ndarray, multiple: int) -> np.ndarray:
    """Zero-pad the batch axis up to a multiple (padding chunks are all-zero
    bytes: no literal hashes to zero, so they produce no hits)."""
    b = chunks.shape[0]
    rem = (-b) % multiple
    if rem == 0:
        return chunks
    return np.concatenate([chunks, np.zeros((rem,) + chunks.shape[1:], chunks.dtype)])


class StagedDispatch:
    """Place a batch on a device ONCE, then run several kernels against the
    resident rows — the fused-pass dispatch surface (prefilter + anchored
    match + license gram gate all read the same upload).

    Three placement flavors behind one API:

    - ``mesh``: rows shard over 'data' via one sharded ``device_put``;
      stages are shard_map'd row-wise kernels.
    - ``devices`` (round-robin): whole batches to the next healthy device,
      per-device :class:`CircuitBreaker`, per-stage jit cached per device.
      The transfer for batch N+1 (device k) overlaps the kernel for batch
      N (device j), so the host→device link bandwidth multiplies by the
      device count; when every device is open, :meth:`put` raises
      :class:`DevicesUnavailable` so the caller takes its host fallback.
    - neither: default placement, device index fixed at 0.

    ``put`` owns batch-axis padding and the ``device.dispatch`` fault gate
    (one check per batch); ``run`` launches a named stage asynchronously on
    the resident array. Fetch-time outcomes feed back through
    ``record_result``, which closes the busy interval and feeds the
    breaker.
    """

    def __init__(self, mesh=None, devices=None, rows_multiple: int = 1,
                 breaker: CircuitBreaker | None = None):
        self.mesh = mesh
        self.devices = list(devices) if devices is not None else None
        self.rows_multiple = max(1, rows_multiple)
        self._stages: dict = {}
        # per stage: extra operands (tables) resident on each dispatch
        # target, keyed by device index (None on the mesh/default flavors)
        self._operands: dict = {}
        if mesh is not None:
            self.pad_to = int(mesh.shape["data"]) * self.rows_multiple
            self.n_streams = 1
            self.breaker = None
        elif self.devices:
            self.pad_to = self.rows_multiple
            self.n_streams = len(self.devices)
            self.breaker = breaker or CircuitBreaker(len(self.devices))
            self._lock = threading.Lock()
            self._next = 0
        else:
            self.pad_to = self.rows_multiple
            self.n_streams = 1
            self.breaker = None
        # live utilization telemetry: busy-interval accounting per dispatch
        # target (one slot on the mesh/default flavors, one per round-robin
        # device); the feed path's probe exposes it as busy_seconds counters
        self.busy = DeviceBusyTracker(self.n_streams)

    def add_stage(self, name: str, fn, out_axes: int = 2,
                  operands: tuple = ()) -> None:
        """Register a row-wise kernel ``[B, C] -> [B, ...]``. ``out_axes``
        is the output rank (2 for per-rule masks, 1 for per-row flags) —
        the mesh flavor needs it for the shard_map out_specs. ``operands``
        (host arrays: a kernel's tables) are placed once on every dispatch
        target, replicated under a mesh, and passed after the rows, so the
        compiled program depends on their shapes, not their contents."""
        if self.mesh is not None:
            spec_out = P("data", None) if out_axes == 2 else P("data")
            # a kernel with loops over row-local state (the rule pass) is
            # row-local by construction: its carries need no vma check
            fn = _shard_map(
                fn, mesh=self.mesh,
                in_specs=(P("data", None),) + (P(),) * len(operands),
                out_specs=spec_out, check_vma=not operands,
            )
        self._stages[name] = flight.instrument_jit(f"stage.{name}", fn)
        if operands:
            if self.mesh is not None:
                rep = NamedSharding(self.mesh, P())
                self._operands[name] = {
                    None: tuple(jax.device_put(o, rep) for o in operands)
                }
            elif self.devices:
                self._operands[name] = {
                    i: tuple(jax.device_put(o, d) for o in operands)
                    for i, d in enumerate(self.devices)
                }
            else:
                self._operands[name] = {
                    None: tuple(jax.device_put(o) for o in operands)
                }

    def has_stage(self, name: str) -> bool:
        return name in self._stages

    def placements(self) -> list:
        """One ``host array -> resident array`` placement per dispatch
        target, matching :meth:`put` (each placement is its own jit
        executable). Warm-up places through these so it compiles exactly
        what the scan will run without touching the breaker, the busy
        tracker or the per-device batch counters."""
        if self.mesh is not None:
            sh = batch_sharding(self.mesh)
            return [lambda a: jax.device_put(a, sh)]
        if self.devices:
            return [
                (lambda a, d=d: jax.device_put(a, d)) for d in self.devices
            ]
        return [jax.device_put]

    def stage_fn(self, name: str):
        """The raw jitted stage (pure, traceable) — bench/warm-up hook."""
        return self._stages[name]

    def put(self, chunks: np.ndarray):
        """Pad + place one batch; returns ``(resident_array, device_idx)``.
        Raises :class:`DevicesUnavailable` when every round-robin device is
        circuit-broken."""
        if self.pad_to > 1:
            chunks = pad_batch(chunks, self.pad_to)
        if self.mesh is not None:
            faults.check("device.dispatch", key="d0")
            dev = jax.device_put(chunks, batch_sharding(self.mesh))
            self.busy.begin(None)
            return dev, None
        if self.devices:
            with self._lock:
                i = self.breaker.next_device(self._next)
                if i is None:
                    raise DevicesUnavailable(
                        f"all {len(self.devices)} dispatch devices are "
                        f"circuit-broken"
                    )
                self._next = (i + 1) % len(self.devices)
            try:
                faults.check("device.dispatch", key=f"d{i}")
                with obs.current().span(f"mesh.d{i}.dispatch"):
                    dev = jax.device_put(chunks, self.devices[i])
            except Exception:
                self.breaker.record_failure(i)
                raise
            obs.current().count(f"mesh.d{i}.batches")
            self.busy.begin(i)
            return dev, i
        faults.check("device.dispatch", key="d0")
        dev = jax.device_put(chunks)
        self.busy.begin(None)
        return dev, None

    def run(self, name: str, dev, device_idx=None):
        """Launch stage ``name`` on an already-resident batch (async)."""
        ops = self._operands.get(name)
        if ops:
            key = (device_idx or 0) if self.devices else None
            return self._stages[name](dev, *ops[key])
        return self._stages[name](dev)

    def record_result(self, i, ok: bool) -> None:
        # the fetch outcome closes the batch's busy interval on every
        # flavor (i is None on mesh/default placement: slot 0)
        self.busy.end(i)
        if self.breaker is None or i is None:
            return
        if ok:
            self.breaker.record_success(i)
        else:
            self.breaker.record_failure(i)


def corpus_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """Corpus fingerprint tables: leading shard axis over 'model', payload
    replicated across 'data'. Used to commit the license n-gram corpus
    (ops/ngram_score) to device memory once, HBM-resident across scans."""
    return NamedSharding(mesh, P(*(("model",) + (None,) * (ndim - 1))))


def sharded_bytes_gate_fn(gate_fn, mesh: Mesh):
    """Shard the raw-bytes shingle gate (ops/ngram_score
    build_bytes_gate_fn): uint8 text rows over 'data', the two shingle
    blooms replicated (they are corpus-global, not per-shard); the
    per-row outputs come back partitioned over 'data' only."""
    fn = flight.instrument_jit(
        "mesh.sharded_bytes_gate",
        _shard_map(
            gate_fn,
            mesh=mesh,
            in_specs=(P("data", None), P(), P()),
            out_specs=(P("data", None), P("data"), P("data")),
        ),
    )

    def run(rows, bloom8, bloom4):
        return fn(jnp.asarray(rows), bloom8, bloom4)

    run.data_parallelism = int(mesh.shape["data"])
    return run


def sharded_bytes_score_fn(score_fn, mesh: Mesh):
    """Shard the raw-bytes scoring kernel (ops/ngram_score
    build_bytes_score_fn): uint8 rows over 'data', corpus shards over
    'model'. Score pairs reassemble as [B, m*Ls] (each device scores its
    local row block against its local license slab); the third output
    (per-row unique-gram count, corpus-independent and identical on every
    model shard) stays partitioned over 'data' only.
    """
    def body(rows, keys, credit):
        full_w, phrase, n_uniq = score_fn(rows, keys, credit)
        # n_uniq is replicated across 'model'; collapse it explicitly so
        # the out_spec P("data") is sound under shard_map's checker.
        n_uniq = jax.lax.pmax(n_uniq, axis_name="model")
        return full_w, phrase, n_uniq

    fn = flight.instrument_jit(
        "mesh.sharded_bytes_score",
        _shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P("data", None),  # uint8 text rows [B/d, W]
                P("model", None),  # corpus keys [m, Ku] -> local [1, Ku]
                P("model", None, None),  # credit [m, Ku, 2*Ls]
            ),
            out_specs=(P("data", "model"), P("data", "model"), P("data")),
            # the credit gather's scan carries row-local state (its zero
            # init is not 'data'-varying): no vma check, as for the rule
            # pass stage
            check_vma=False,
        ),
    )

    def run(rows, keys, credit):
        return fn(jnp.asarray(rows), keys, credit)

    run.data_parallelism = int(mesh.shape["data"])
    return run


def hit_counts_psum(rules_fn, mesh: Mesh, n_tables: int):
    """Global set-bit counts per result word of the rule pass
    (``ops.rules``) over a sharded batch, reduced with psum over ICI — the
    telemetry/all-gather path exercised by dryrun_multichip. Called as
    ``fn(rows, *tables)``; the per-row overflow column is left out."""
    def step(chunks, *tables):  # local shard [B/d, C]
        words = rules_fn(chunks, *tables)[:, :-1]  # [B/d, out_words - 1]
        local = jnp.sum(jax.lax.population_count(words).astype(jnp.int32), axis=0)
        return jax.lax.psum(local, axis_name="data")

    return flight.instrument_jit(
        "mesh.hit_counts_psum",
        _shard_map(
            step,
            mesh=mesh,
            in_specs=(P("data", None),) + (P(),) * n_tables,
            out_specs=P(),
            check_vma=False,
        ),
    )
