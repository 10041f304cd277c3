"""TPU-backed secret scanner: device rule pass + exact host confirmation.

Pipeline (replaces the reference's walk→goroutine→regexp chain, ref:
pkg/fanal/secret/scanner.go:377 and SURVEY.md §3.2):

  files → overlapping fixed-size chunks → [B, C] batches → device rule
  pass → per-(file, rule) candidates → exact `SecretScanner` restricted to
  candidate rules → findings (byte-identical to the CPU backend).

Chunk overlap equals the compiled ruleset's maximum device window, so every
device-checkable window lies fully inside at least one chunk. The host
confirm is window-restricted only where the flagged chunk provably bounds
the match start (anchored lane; keyword lane with the keyword inside every
match — see ``_windowed_ids``); other keyword-lane rules rescan the whole
file on flag, with unbounded-width regexes accelerated by their bounded
start-detector prefix (``Rule.start_detector``).

The feed path is a fully asynchronous pipeline — the TPU analog of the
reference's walker-goroutine fan-out into a bounded channel
(`parallel.Pipeline`, ref: pkg/parallel/pipeline.go:14-115,
scan_flags.go:79-84):

  input thread (feeder): chunk / hash / dedup / pack into a fixed
  **chunk arena** of preallocated reusable row slabs
  (:class:`trivy_tpu.secret.feed.ChunkArena`) — large files gather all
  their full rows into a slab with ONE vectorized strided copy, counters
  accumulate per file, not per row
    → bounded dispatch queue
  **transfer streams** (N worker threads, one per round-robin device, ≥2
  on a single device): each keeps a bounded in-flight window of
  double-buffered dispatches (`jax.device_put` + kernel enqueue are
  async), so batch N+1's host→device transfer overlaps batch N's kernel
  AND the per-stream transfers overlap each other — on a link that
  serializes per transfer this multiplies effective feed bandwidth by the
  stream count; slabs release back to the arena only after the blocking
  fetch, then results resolve inline
    → confirm pool (bounded by a semaphore)
    → **reorder buffer**: the generator emits per-file results in input
  order from a completion map, so a slow head-of-line confirmation never
  stalls the feeder — readers keep filling the arena while emission
  waits.

Dispatch shapes are drawn from a fixed bucket ladder (B, B/2, B/4, ...) so
every shape compiles exactly once. Arena slots bound host memory (slabs in
the dispatch queue + per-stream windows + assembly margin); the confirm
semaphore bounds retained file bytes; together they are the streaming-RSS
guarantee the bench gate enforces.

The device side is a FUSED pass (README "Fused device pass"): each batch
is placed once (`parallel.mesh.StagedDispatch`) and every detector reads
the resident rows — the table-driven rule pass (``ops/rules.py``: one
compiled program per bucket rung whatever the rule count, the ruleset's
tables its operands) returns one bitset per row of anchored hits and
keyword candidates; candidates accumulate per file and gate host confirms
at whole-file MatchKeywords semantics; then (with ``--scanners
secret,license``) the license gram gate (`licensing/fused.py`) so license
candidacy costs zero extra link bytes.

The feed path sends link bytes ≪ corpus bytes:

- **chunk-dedup hit cache**: every row is content-hashed (keyed blake2b,
  key = ruleset fingerprint so a rule add/remove/change flips every key)
  and duplicate rows — vendored deps, repeated OCI layer content, zero
  pages — reuse the cached per-rule hit vector with no upload and no
  kernel. Sound because the hit vector is a pure function of (row bytes,
  compiled tables); path-dependent filtering happens later, host-side.
  Bounded in-process LRU, optionally persisted through the trivy_tpu.cache
  layer (fs/redis) for cross-scan reuse — the same insight as the
  reference's layer cache: never re-scan content already seen.
- **small-file row packing**: files smaller than a row share one row,
  separated by ≥-span zero guard gaps. A real match's device program reads
  only match bytes (+1 boundary byte), so packing can never suppress a hit;
  cross-file windows only add false candidates that the exact host confirm
  discards.
- **round-robin multi-stream dispatch** (parallel.mesh.StagedDispatch
  over several devices) sends whole batches to each local device in turn
  so transfers overlap kernels across devices, multiplying effective link
  bandwidth.

Failure domains (README "Robustness"): a failed batch re-dispatches up to
``batch_retries`` times (OOM-shaped errors split the batch in half
instead), round-robin dispatch carries a per-device circuit breaker that
excludes a dying device and re-probes it on a backoff, and when nothing
device-side survives the scan completes on the exact host confirm path —
the parity oracle — with findings byte-identical and the scan flagged
degraded.
"""

from __future__ import annotations

import hashlib
import os
import queue
import threading
import time
from collections import deque
from collections.abc import Iterable, Iterator
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

from trivy_tpu import faults, log, obs
from trivy_tpu.obs import recorder as flight
from trivy_tpu.secret.device_compile import CompiledRules, build_tables, compile_rules
from trivy_tpu.secret.feed import ChunkArena, row_windows
from trivy_tpu.secret.engine import ScannerConfig, SecretScanner
from trivy_tpu.types import Secret

logger = log.logger("secret:tpu")

DEFAULT_CHUNK_LEN = 65536
DEFAULT_BATCH = 64
# accelerator path: small self-contained rows.
# 1024 x 8 KiB = 8 MiB batches: small enough that pack -> transfer ->
# kernel -> confirm overlap through the pipeline (a 32 MiB batch serializes
# the whole corpus behind one blocking device wait), big enough to amortize
# kernel launch
PALLAS_CHUNK_LEN = 8192
PALLAS_BATCH = 1024
# rows per batch multiple on the accelerator path (the fused license
# gate's row blocks)
PALLAS_ROWS_MULTIPLE = 32
# per-transfer-stream in-flight window: 2 = double buffering (batch N+1's
# transfer overlaps batch N's kernel on the same stream)
FEED_INFLIGHT = 2
# assembled slabs queued between the feeder and the transfer streams
FEED_QUEUE_DEPTH = 2
# arena slack beyond queue + windows: the slab being assembled + one spare
ARENA_MARGIN = 2
# transfer streams on a single non-CPU device when nothing else decides it:
# concurrent device_put calls from separate threads overlap transfers with
# each other as well as with kernels (TRIVY_TPU_FEED_STREAMS=1 serializes)
SINGLE_DEVICE_STREAMS = 4
# workers for exact host confirmation (overlaps device-result waits)
CONFIRM_WORKERS = 4
# bounded in-process LRU for the chunk-dedup hit cache; most entries are an
# empty tuple (clean chunk), so 64k entries cost a few MB. The REAL bound
# is now the byte budget (hitstore.DEFAULT_STORE_MB / --secret-dedup-mb);
# this entry count stays as a backstop
HIT_CACHE_ENTRIES = 1 << 16
# bump when device-compile semantics change in a way that alters hit
# vectors for identical (rules, chunk) inputs — invalidates persisted caches
# (v2: values grew prefilter candidate masks + nfa/license flags;
# v3: the fingerprint folds the --secret-config file content and persisted
# lookups/writes are batched through secret/hitstore.py;
# v4: invalidated stores written by builds whose device input was not
# guaranteed byte-identical to the raw rows (keys hash raw row content);
# v5: the table-driven rule pass checks every rule on every row, so a
# verdict's nfa_ran is always true)
HIT_CACHE_VERSION = 5
# re-dispatches allowed per failed batch before the failure escalates to
# the scan-level fallback ladder (OOM-shaped splits don't consume this
# budget: halving strictly shrinks the batch, so it terminates on its own)
BATCH_RETRIES = 2

# error shapes that mean "the batch was too big", answered by halving the
# batch instead of retrying it whole (XLA/PJRT spellings + the injected one)
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "resource_exhausted", "out of memory",
                "Out of memory", "OOM")


def _is_oom(err: BaseException) -> bool:
    s = f"{type(err).__name__}: {err}"
    return any(m in s for m in _OOM_MARKERS)


class _DeviceFailed(Exception):
    """Internal marker the device loop posts when its retry ladder is
    exhausted; ``cause`` is the original device/link error."""

    def __init__(self, cause: BaseException):
        super().__init__(str(cause))
        self.cause = cause


def chunk_spans(n: int, chunk_len: int, overlap: int) -> list[int]:
    """Chunk start offsets covering ``n`` bytes with the given overlap."""
    if n <= chunk_len:
        return [0]
    step = chunk_len - overlap
    starts = list(range(0, n - overlap, step))
    return starts


def _keyword_spans(keywords, lower: str, windows) -> list[tuple[int, int]]:
    """Spans of the (lowered) keyword occurrences that start inside the
    flagged ``windows``: the device flags a row only where a keyword lies
    whole inside it."""
    out = []
    for ws, we in windows:
        for kw in keywords:
            i = lower.find(kw, ws, we + len(kw))
            while i != -1:
                out.append((i, i + len(kw)))
                i = lower.find(kw, i + 1, we + len(kw))
    return out


@dataclass
class _FileState:
    path: str
    data: bytes
    pending: int  # chunks not yet matched
    # candidate rule index -> chunk windows (byte spans) where it hit
    rules: dict[int, list[tuple[int, int]]] = field(default_factory=dict)
    # keyword candidate rules accumulated over EVERY chunk of the file:
    # guarded rules are confirmed only when present here — the
    # reference's whole-file MatchKeywords gate, applied from device data
    # instead of a host lowercase scan
    cand: set[int] = field(default_factory=set)


class ScanStats:
    """Cumulative link-traffic counters (thread-safe): bench snapshots
    before/after a scan to compute link_bytes_per_corpus_byte and the
    dedup hit rate. ``bytes_uploaded`` counts padded row bytes actually
    dispatched (real link traffic incl. bucket padding); ``bytes_dedup_hit``
    counts corpus bytes whose rows were served from the hit cache or
    coalesced onto an identical in-flight row."""

    FIELDS = (
        "bytes_in",          # corpus bytes fed to the device path
        "bytes_uploaded",    # padded row bytes dispatched over the link
        "bytes_dedup_hit",   # corpus bytes resolved without an upload
        "bytes_packed",      # corpus bytes sharing a row with another file
        "chunks",            # rows the corpus decomposed into
        "chunks_uploaded",   # rows actually dispatched
        "chunks_dedup_hit",  # rows served from the hit cache / coalesced
        "rows_packed",       # dispatched rows carrying >1 file segment
        "files_packed",      # files that rode a shared row
        "batch_retries",     # failed batches re-dispatched whole
        "batch_splits",      # OOM-shaped failures answered by halving
        "degraded",          # scans that fell back to the exact host path
        "chunks_warm_hit",   # rows served from the PERSISTENT store
        "bytes_warm_hit",    # corpus bytes those rows covered
        "rows_prefiltered",  # rows the rule pass inspected for keywords
        "rows_prefilter_hit",  # rows with >=1 candidate rule
        "license_rows_gated",    # arena rows the license gram gate read
        "license_rows_flagged",  # rows that flagged a license candidate
    )

    def __init__(self):
        self._lock = threading.Lock()
        self._v = dict.fromkeys(self.FIELDS, 0)

    def add(self, **kw: int) -> None:
        with self._lock:
            for k, n in kw.items():
                self._v[k] += n

    def snapshot(self) -> dict[str, int]:
        with self._lock:
            return dict(self._v)


class TpuSecretScanner:
    """Drop-in equivalent of :class:`SecretScanner` batched over TPU.

    ``scan_files`` consumes an iterable of (path, bytes) and yields one
    :class:`Secret` per input file, in input order, with findings identical
    to ``SecretScanner.scan_bytes``.
    """

    def __init__(
        self,
        config: ScannerConfig | None = None,
        chunk_len: int | None = None,
        batch_size: int | None = None,
        mesh=None,
        backend: str = "auto",
        confirm_workers: int = 0,  # 0 = CONFIRM_WORKERS default
        dedup: bool = True,
        pack_small: bool = True,
        hit_cache_entries: int = HIT_CACHE_ENTRIES,
        hit_cache=None,  # trivy_tpu.cache backend for cross-scan persistence
        dispatch: str = "auto",  # 'auto' | 'single' | 'round_robin'
        devices=None,  # explicit device list for round-robin dispatch
        host_fallback: bool = True,  # degrade to the exact host path on
        # unrecoverable device failure instead of failing the scan
        batch_retries: int = BATCH_RETRIES,
        feed_streams: int = 0,  # transfer-stream worker threads; 0 = auto
        # (one per round-robin device; SINGLE_DEVICE_STREAMS on one
        # accelerator; 2 on the CPU backend)
        inflight: int = 0,  # in-flight batches per stream; 0 = FEED_INFLIGHT
        tuning=None,  # trivy_tpu.tuning.TuningConfig; None = env-resolved
        # defaults (no implicit AUTOTUNE.json discovery — the CLI layer
        # resolves the full CLI > env > autotune > topology chain and
        # passes the result here; library callers stay hermetic)
        arena_slabs: int = 0,  # chunk-arena slab override; 0 = derived
        bucket_rungs: int = 0,  # dispatch bucket-ladder depth; 0 = default
        hit_cache_bytes: int = 0,  # dedup LRU byte budget; 0 = tuning's
        # dedup_store_mb (default hitstore.DEFAULT_STORE_MB)
    ):
        import jax

        from trivy_tpu.tuning import resolve_tuning, topology_fingerprint

        # the consolidated knob config (ROADMAP item 4): explicit ctor args
        # are the strongest layer (tests/bench pass them directly), then the
        # TuningConfig's own CLI > env > autotune > topology-default chain.
        # Fingerprinting here is free — this ctor initializes jax anyway
        if tuning is None:
            tuning = resolve_tuning(
                autotune_path="", topology=topology_fingerprint()
            )
        elif not tuning.topology:
            tuning.topology = topology_fingerprint()
        self.tuning = tuning

        self.exact = SecretScanner(config)
        with obs.current().span("secret.rules_build"):
            self.compiled: CompiledRules = compile_rules(self.exact.rules)
            self.tables = build_tables(self.compiled)
        if backend == "auto":
            platform = jax.devices()[0].platform
            backend = "pallas" if platform not in ("cpu", "METAL") else "xla"
        self.backend = backend
        if backend == "pallas":
            self.chunk_len = chunk_len or PALLAS_CHUNK_LEN
            self.batch_size = batch_size or PALLAS_BATCH
            rows_mult = PALLAS_ROWS_MULTIPLE
        else:
            self.chunk_len = chunk_len or DEFAULT_CHUNK_LEN
            self.batch_size = batch_size or DEFAULT_BATCH
            rows_mult = 1
        from trivy_tpu.ops.rules import build_rules_fn

        rules_fn = build_rules_fn(self.tables.margin, self.tables.kw_bits)
        self._guarded_ids = frozenset(
            self.compiled.rule_ids[i] for i in np.nonzero(self.compiled.guarded)[0]
        )
        self.overlap = max(64, self.compiled.span + 1)
        if self.overlap > self.chunk_len // 2:
            raise ValueError(
                f"chunk_len={self.chunk_len} too small for ruleset: the widest "
                f"device window needs overlap {self.overlap}; use chunk_len "
                f">= {2 * self.overlap}"
            )
        self._rules_by_id = {r.id: r for r in self.exact.rules}
        self._rule_pos = {r.id: i for i, r in enumerate(self.exact.rules)}
        # windowed confirmation is sound only when flagged chunks bound the
        # match START: always true on the anchored lane; true on the keyword
        # lane only for bounded-width rules whose keyword provably sits
        # inside every match (the keyword occurrence then pins the start
        # within max_match_width). Everything else full-scans on flag.
        anchored = set(self.compiled.anchored_rule_ids)
        self._windowed_ids = anchored | {
            r.id
            for r in self.exact.rules
            if r.id not in anchored
            and r.keywords
            and r.keyword_in_match
            and r.max_match_width is not None
            and r.max_match_width <= 8192
        }
        self._keyword_windowed = self._windowed_ids - anchored
        self.confirm_workers = confirm_workers or CONFIRM_WORKERS

        # -- dedup hit cache ------------------------------------------------
        # ruleset fingerprint: the hit vector is a pure function of
        # (row bytes, compiled tables); keying the row hash with this
        # fingerprint makes any rule add/remove/regex/keyword change — and
        # any reordering, which renumbers rule indices — flip every key
        fp = hashlib.blake2b(digest_size=16)
        fp.update(f"v{HIT_CACHE_VERSION}:{self.chunk_len}:".encode())
        for r in self.exact.rules:
            fp.update(repr((r.id, r.regex, r.keywords, r.path)).encode())
            fp.update(b"\x00")
        # the FULL effective config: the --secret-config file's content
        # digest (allow rules / exclude blocks / disables change findings
        # without changing hit vectors, and a persisted manifest keyed on
        # this fingerprint caches findings) — a changed rule file flips
        # every persisted namespace, loudly (hitstore namespace marker)
        fp.update(b"cfg:")
        fp.update(getattr(config, "source_digest", "").encode() or b"-")
        # keyword table fingerprint: cached vectors carry candidate masks
        # derived from it, so a keyword add/remove/edit must flip every key
        fp.update(b"pf:")
        fp.update(self.compiled.prefilter_fingerprint())
        self.ruleset_fingerprint = fp.digest()
        self._dedup = dedup
        self._pack_small = pack_small
        # persistent cross-scan dedup store (secret/hitstore.py): the
        # in-process LRU is BYTE-bounded (--secret-dedup-mb, entry count
        # as a backstop) so streaming multi-GB scans keep flat RSS, and
        # backend lookups/writes are batched per assembled/resolved batch
        from trivy_tpu.secret.hitstore import DEFAULT_STORE_MB, HitStore

        store_bytes = hit_cache_bytes or (
            (tuning.dedup_store_mb or DEFAULT_STORE_MB) << 20
        )
        self._hit_store = HitStore(
            self.ruleset_fingerprint,
            backend=hit_cache,
            max_entries=hit_cache_entries,
            max_bytes=store_bytes,
        )
        self._host_fallback = host_fallback
        self._batch_retries = batch_retries
        self.stats = ScanStats()

        from trivy_tpu.parallel.mesh import StagedDispatch, pad_batch

        if dispatch not in ("auto", "single", "round_robin"):
            raise ValueError(
                f"dispatch={dispatch!r}: use 'auto', 'single', or 'round_robin'"
            )
        rr_devices = None
        local = list(devices) if devices is not None else jax.local_devices()
        platform = local[0].platform if local else "cpu"
        if mesh is None and dispatch != "single":
            # 'auto' opts in only on real multi-accelerator hosts; the CPU
            # backend's virtual devices share one memory bus, so multi-stream
            # dispatch there only adds copies (tests opt in explicitly)
            if len(local) > 1 and (
                dispatch == "round_robin" or platform not in ("cpu",)
            ):
                rr_devices = local

        # fused-pass dispatch: ONE placement per batch, every device
        # detector (the rule pass, the license gram gate) runs
        # against the resident rows — the upload is shared, not repeated
        self._staged = StagedDispatch(
            mesh=mesh, devices=rr_devices, rows_multiple=rows_mult
        )
        self._staged.add_stage(
            "rules", rules_fn, out_axes=2, operands=self.tables.operands()
        )
        self._stage_lock = threading.Lock()
        row_multiple = self._staged.pad_to

        # bench/back-compat surface: the rule pass on host rows (pads short
        # batches itself) plus the stream/breaker attributes tests and
        # warm-up loops key off
        pad_to = self._staged.pad_to

        def _compat_match(chunks):
            return self._staged.run("rules", pad_batch(chunks, pad_to))

        if rr_devices is not None:
            _compat_match.n_streams = len(rr_devices)
            _compat_match.breaker = self._staged.breaker
            _compat_match.devices = rr_devices
        self._match = _compat_match

        # transfer-stream sizing: explicit ctor arg > TuningConfig (which
        # folds CLI/env/autotune) > topology default — one worker thread
        # per round-robin device (per-device copies overlap each other),
        # several streams on one accelerator (concurrent device_puts
        # overlap each other's transfers), two on the CPU
        # backend (keeps the async machinery exercised in tests without
        # thrashing one memory bus)
        if feed_streams <= 0:
            feed_streams = tuning.feed_streams
        if feed_streams <= 0:
            if rr_devices is not None:
                feed_streams = len(rr_devices)
            elif platform in ("cpu", "METAL"):
                feed_streams = 2
            else:
                feed_streams = SINGLE_DEVICE_STREAMS
        self.feed_streams = max(1, feed_streams)
        if inflight <= 0:
            inflight = tuning.inflight
        self.inflight = max(1, inflight or FEED_INFLIGHT)
        # arena override (0 = the derived queue+windows+margin bound in
        # _ScanRun); clamped there to keep at least a double-buffer cycling
        self.arena_slabs = max(0, arena_slabs or tuning.arena_slabs)
        # dispatch-shape bucket ladder: every shape compiles exactly once
        # (variable trailing-batch shapes would recompile per distinct size).
        # The default ladder stops at B/4 (3 rungs): each extra rung costs
        # a full compile of every stage (tens of seconds for the rule pass,
        # whatever the ruleset), while padding a short trailing batch up
        # to the smallest rung costs microseconds of device time. The
        # depth is a tuning knob (--secret-bucket-rungs) because the
        # tradeoff flips on corpora dominated by tiny trailing batches.
        rungs = max(1, bucket_rungs or tuning.bucket_rungs or 3)
        self.bucket_rungs = rungs
        min_bucket = max(
            8, row_multiple, self.batch_size // (1 << (rungs - 1))
        )
        buckets = [self.batch_size]
        while buckets[-1] // 2 >= min_bucket:
            buckets.append(buckets[-1] // 2)
        self._buckets = sorted(buckets)

    # -- dedup hit cache ----------------------------------------------------
    #
    # Cached value per row digest (the "row verdict"): a 4-tuple
    #   (hit_rules, cand_rules, nfa_ran, lic)
    # - hit_rules: device hit vector (anchored hits + keyword-lane hits)
    # - cand_rules: keyword candidate rules (== hit_rules' keyword part
    #   plus anchored-lane keyword presences)
    # - nfa_ran: always True (the rule pass checks every rule on every
    #   row); kept so the persisted verdict schema is unchanged
    # - lic: fused license-gate verdict: True/False, or None when the
    #   row's batch never ran the gate (consumers must not trust None)
    # The digest is keyed with the ruleset fingerprint (which now folds in
    # the prefilter table) plus a ':lic' namespace when a license gate is
    # active, so entries can never cross modes.

    @property
    def _hit_lru(self):
        # introspection surface for tests/bench (entry count, reuse proofs)
        return self._hit_store._lru

    @property
    def _hit_persist(self):
        return self._hit_store.backend

    def _hit_get(self, key: bytes):
        """Cached row verdict for a row digest from the IN-PROCESS LRU, or
        None. Persistent-store lookups are batched at slab-flush time
        (one pipelined round trip per batch — see ``_ScanRun._feed``)."""
        return self._hit_store.get(key)

    def clear_hit_cache(self) -> None:
        """Drop the in-process hit LRU (persisted entries are untouched) —
        used by bench to measure the cold feed path."""
        self._hit_store.clear_local()

    def _hit_put(self, key: bytes, verdict) -> None:
        self._hit_store.put(key, verdict)

    def seed_hit_entries(self, entries: list) -> int:
        """Pre-warm the dedup store from a peer's export (fleet
        cross-replica warming); returns entries accepted. Entries from a
        different fingerprint namespace are dropped loudly in the store."""
        return self._hit_store.seed(entries)

    def export_warm_hits(self, limit: int = 0) -> list:
        """Warm dedup entries (``[[persist_key, doc], ...]``) for peer
        seeding."""
        from trivy_tpu.secret.hitstore import WARM_EXPORT_LIMIT

        return self._hit_store.export_warm(limit or WARM_EXPORT_LIMIT)

    # -- async feed pipeline ------------------------------------------------

    def warm_buckets(self) -> None:
        """Compile every (bucket shape × placement × stage) combination
        outside any timed region — the rule pass (+ the license gram gate,
        when registered) per rung — so the first real batch never pays a
        compile.

        The compiles run concurrently: each is an independent XLA/Mosaic
        compile that releases the GIL, and a cold start of the built-in
        ruleset is minutes of compile done one at a time. Warm batches are
        placed directly, so the breaker, the busy tracker and the
        per-device batch counters see only real traffic."""
        from trivy_tpu.parallel.mesh import pad_batch

        # one job per executable, the rule pass first
        stages = ["rules"] + [
            s for s in ("license",) if self._staged.has_stage(s)
        ]
        jobs = []
        for name in stages:
            for didx, place in enumerate(self._staged.placements()):
                for b in self._buckets:
                    rows = pad_batch(
                        np.zeros((b, self.chunk_len), dtype=np.uint8),
                        self._staged.pad_to,
                    )
                    jobs.append((place, didx, rows, name))

        def warm(job) -> None:
            place, didx, rows, name = job
            np.asarray(self._staged.run(name, place(rows), didx))

        workers = max(1, min(len(jobs), os.cpu_count() or 1))
        with ThreadPoolExecutor(workers, thread_name_prefix="warm") as pool:
            for fut in [pool.submit(warm, j) for j in jobs]:
                fut.result()

    def _ensure_license_stage(self) -> None:
        """Register the license gram-gate kernel as a fused stage (once per
        scanner; the jitted gate itself is process-cached per chunk_len).
        Output is per-BLOCK ([B, chunk_len/block]) so packed-row segments
        resolve to their own verdicts."""
        with self._stage_lock:
            if not self._staged.has_stage("license"):
                from trivy_tpu.licensing.fused import get_gate_fn

                fn = get_gate_fn(self.chunk_len)
                self._lic_block = fn.block
                self._staged.add_stage("license", fn, out_axes=2)

    def scan_files(
        self, files: Iterable[tuple[str, bytes]], license_gate=None
    ) -> Iterator[Secret]:
        """Scan many files; yields per-file results in input order.

        The input iterable is consumed on a dedicated feeder thread, so a
        slow consumer of this generator (or a slow head-of-line
        confirmation) never stalls chunking, hashing, or device transfers
        — backpressure comes only from the bounded arena, dispatch queue,
        and confirm semaphore. See :class:`_ScanRun` for the pipeline.

        ``license_gate`` (a :class:`trivy_tpu.licensing.fused.
        FusedLicenseGate`) opts this scan into the shared-arena fused pass:
        the license gram gate runs over the same resident rows and the
        gate accumulates per-file candidate verdicts for the license
        analyzer — each scanned byte crosses the link once for both
        detectors.
        """
        if license_gate is not None:
            self._ensure_license_stage()
        ctx = obs.current()
        # rules per lane: on the device (anchored or keyword) and scanned
        # by the host on every file
        ctx.gauge("secret.rules_device", self.compiled.num_rules)
        ctx.gauge("secret.rules_host_lane", len(self.compiled.host_rule_ids))
        run = _ScanRun(self, files, ctx, license_gate)
        run.start()
        try:
            next_emit = 0
            while True:
                with run.cond:
                    while True:
                        if run.error is not None:
                            raise run.error
                        if next_emit in run.results:
                            r = run.results.pop(next_emit)
                            break
                        if run.total is not None and next_emit >= run.total:
                            return
                        run.cond.wait(0.2)
                yield r.result() if isinstance(r, Future) else r
                next_emit += 1
        finally:
            run.close()

    def scan_bytes(self, path: str, data: bytes) -> Secret:
        """Single-file convenience (still device-prefiltered)."""
        return next(iter(self.scan_files([(path, data)])))

    def tuning_snapshot(self) -> dict:
        """The EFFECTIVE knob set this scanner runs with — post-resolution
        values, per-knob provenance, and (after a scan) the final values
        the online controller left behind. Embedded in BENCH rep details,
        ``--metrics-out``, and heartbeat lines so differently-tuned rounds
        stay comparable and ``--check-regression`` can annotate knob drift
        alongside a throughput change."""
        doc = {
            "feed_streams": self.feed_streams,
            "inflight": self.inflight,
            "arena_slabs": self.arena_slabs,  # 0 = derived per scan
            "bucket_ladder": list(self._buckets),
            "controller": bool(self.tuning.controller),
            "topology": self.tuning.topology,
            "source": dict(self.tuning.source),
        }
        last = getattr(self, "_last_tuning", None)
        if last:
            doc["effective"] = dict(last)
        return doc

    def _note_degraded(self, ctx, err: BaseException) -> None:
        logger.warning(
            "device pipeline failed (%s); completing the scan on the exact "
            "host confirm path — slower, findings identical", err,
        )
        self.stats.add(degraded=1)
        ctx.count("secret.degraded")
        obs.note_scan_degraded()

    # -- host confirmation --------------------------------------------------

    def _confirm(self, st: _FileState, prof=None) -> Secret:
        # span recording happens in scan_files' confirm_task (which holds
        # the scan's trace context); direct callers time themselves
        return self._confirm_inner(st, prof)

    def _confirm_inner(self, st: _FileState, prof=None) -> Secret:
        from trivy_tpu.secret.rules import ascii_lower

        windows_by_id = {
            self.compiled.rule_ids[i]: w for i, w in st.rules.items()
        }
        host_ids = set(self.compiled.host_rule_ids)
        rid = self.compiled.rule_ids
        cand_ids = {rid[i] for i in st.cand}
        if not windows_by_id and not host_ids:
            return Secret(file_path=st.path)
        content = st.data.decode("latin-1")
        lower = ascii_lower(content)
        global_blocks = self.exact.global_block_spans(content)
        hits = []
        # only flagged and host-lane rules can confirm: walk those, in rule
        # order, through the path filters (a ruleset of thousands of rules
        # would otherwise cost thousands of path tests per file)
        flagged = sorted(set(windows_by_id) | host_ids, key=self._rule_pos.__getitem__)
        for rule in (self._rules_by_id[i] for i in flagged):
            if not rule.match_path(st.path) or rule.allow_path(st.path):
                continue
            if rule.id in self._guarded_ids and rule.id not in cand_ids:
                # no keyword of this rule occurs anywhere in the file: the
                # exact engine's match_keywords would reject it, so the
                # confirm (and its wasted_confirm cost) is skipped outright
                # — this is the prefilter's answer to the PR 5 fp_rate rows
                continue
            t0 = time.perf_counter() if prof is not None else 0.0
            if rule.id in windows_by_id:
                if rule.id in self._windowed_ids:
                    # regex runs only around the device-flagged chunk windows;
                    # on the keyword lane, around the keyword occurrences in
                    # them (every match holds one, within its width)
                    windows = windows_by_id[rule.id]
                    if rule.id in self._keyword_windowed:
                        windows = _keyword_spans(rule.lower_keywords, lower, windows)
                    locs = self.exact.find_rule_locations_in_windows(
                        rule, content, lower, windows, global_blocks
                    )
                else:
                    # keyword lane without a start bound: the flagged chunk
                    # locates the keyword, not the match — full-content scan
                    # (detector-accelerated for unbounded-width rules)
                    locs = self.exact.find_rule_locations_fullscan(
                        rule, content, lower, global_blocks
                    )
            elif rule.id in host_ids:
                locs = self.exact.find_rule_locations(
                    rule, content, lower, global_blocks
                )
            else:
                continue
            if prof is not None:
                prof.confirm(rule.id, time.perf_counter() - t0, len(locs))
            hits.extend((rule, loc) for loc in locs)
        return self.exact.build_findings(st.path, content, hits)


# sentinel a worker receives when the pipeline is shutting down or has
# switched to the host fallback (distinct from the end-of-input None)
_ABORT = object()


class _ScanRun:
    """One ``scan_files`` invocation's async pipeline.

    Threads (all daemon, all scoped to this run):

    - **feeder**: consumes the caller's file iterable; chunks, hashes
      (dedup keys), packs small files, and assembles rows into
      :class:`~trivy_tpu.secret.feed.ChunkArena` slabs — large files via
      one vectorized strided gather per slab run, not per-row Python —
      then hands full slabs to the bounded dispatch queue.
    - **transfer streams** (``scanner.feed_streams`` workers): each pulls
      slabs, places each through ``scanner._staged`` (round-robin
      across devices or concurrent streams into one device), keeps a
      bounded in-flight window (double buffering: transfer N+1 overlaps
      kernel N), fetches the oldest result, releases the slab, and
      resolves hits inline. The per-batch retry ladder (re-dispatch,
      OOM halving, circuit-breaker feedback) runs here, per stream.
    - **confirm pool**: exact host confirmation, bounded by a semaphore
      so retained file bytes stay flat on streaming scans.

    The generator side of ``scan_files`` only emits: completed results
    land in ``results`` (the reorder buffer) keyed by input index and are
    yielded in order. Emission never blocks the feeder.

    Failure ladder: a stream that exhausts its retries calls
    :meth:`_degrade` (host fallback: every unresolved and unread file is
    rescanned by the exact host engine — the parity oracle) or, with
    ``host_fallback=False``, :meth:`_fail` so the generator re-raises.
    """

    def __init__(self, sc: TpuSecretScanner, files, ctx, license_gate=None):
        self.sc = sc
        self.files = files
        self.ctx = ctx
        self.enabled = ctx.enabled
        self.prof = ctx.profile() if ctx.enabled else None
        self.lock = threading.Lock()
        self.cond = threading.Condition(self.lock)
        self.states: dict[int, _FileState] = {}
        # fused license pass: per-scan candidate gate + fidx -> query path
        # for files the license analyzer will ask about
        self.lic_gate = license_gate
        self.lic_paths: dict[int, str] = {}
        # reorder buffer: input index -> Secret | in-flight Future
        self.results: dict[int, Secret | Future] = {}
        # row digest -> waiting segment lists: identical rows already
        # dispatched but not yet resolved coalesce here instead of being
        # uploaded again (zero pages recur within a single batch)
        self.row_waiters: dict[bytes, list] = {}
        self.total: int | None = None  # set once the input is exhausted
        self.error: BaseException | None = None
        self.degraded = False
        self.stop = threading.Event()
        self.feed_done = threading.Event()  # input exhausted (or failed)
        streams = sc.feed_streams
        # online tuning (trivy_tpu/tuning.py): the controller adapts the
        # ACTIVE stream count, the per-stream in-flight window, and the
        # arena size mid-scan. Controller-off scans allocate nothing extra
        # — exactly `streams` worker threads, the derived arena bound, no
        # controller thread or decision buffers (the zero-cost-when-off
        # bar bench --smoke enforces)
        self._controller_on = (
            bool(sc.tuning.controller) and sc.tuning.tuning_interval > 0
        )
        self.controller = None
        if self._controller_on:
            from trivy_tpu.tuning import inflight_limit, stream_limit

            n_alloc = stream_limit(streams)
            self._max_inflight = inflight_limit(sc.inflight)
        else:
            n_alloc = streams
            self._max_inflight = sc.inflight
        self.active_streams = streams
        self.inflight = sc.inflight  # run-level window; controller-mutable
        self.in_q: queue.Queue = queue.Queue(maxsize=FEED_QUEUE_DEPTH)
        slabs = sc.arena_slabs or (
            FEED_QUEUE_DEPTH + streams * sc.inflight + ARENA_MARGIN
        )
        # a 1-slab arena cannot double-buffer: the feeder would block on
        # the single slab a worker still holds — keep a cycling pair
        slabs = max(2, slabs)
        self._max_arena_slabs = max(
            slabs, FEED_QUEUE_DEPTH + n_alloc * self._max_inflight
            + ARENA_MARGIN,
        )
        self.arena = ChunkArena(slabs, sc.batch_size, sc.chunk_len)
        # HBM ledger: the arena bound is the worst-case device residency
        # of in-flight batch rows (every slab's rows may be device-side at
        # once across the dispatch windows); released at close()
        flight.note_resident("arena", slabs * sc.batch_size * sc.chunk_len)
        self.pool = ThreadPoolExecutor(max_workers=sc.confirm_workers)
        # backpressure: bounds queued+running confirms so a slow confirm
        # pool cannot accumulate unbounded _FileState.data on a large
        # streaming scan (file bytes are released once its confirm runs)
        self.confirm_slots = threading.Semaphore(sc.confirm_workers * 4)
        # live-telemetry state (obs/timeseries.py): per-stream in-flight
        # window depths and the confirm queue depth, updated per batch /
        # per confirm — cheap enough to keep on untraced scans, read only
        # by an attached sampler's probe
        self._stream_inflight = [0] * n_alloc
        self._confirm_inflight = 0
        self.workers = [
            threading.Thread(
                target=self._worker, args=(i,), daemon=True,
                name=f"secret-xfer-{i}",
            )
            for i in range(n_alloc)
        ]
        self.feeder = threading.Thread(
            target=self._feed_guarded, daemon=True, name="secret-feeder"
        )

    def start(self) -> None:
        self.ctx.add_probe(self._telemetry_probe)
        for w in self.workers:
            w.start()
        self.feeder.start()
        if self._controller_on:
            from trivy_tpu.tuning import TuningController

            self.controller = TuningController(
                self, ctx=self.ctx,
                interval=self.sc.tuning.tuning_interval,
            ).start()

    # -- online-tuning adapter (trivy_tpu.tuning.TuningController) ----------

    def knobs(self) -> dict:
        return {
            "feed_streams": self.active_streams,
            "inflight": self.inflight,
            "arena_slabs": self.arena.n_slabs,
        }

    def limits(self) -> dict:
        return {
            "max_streams": len(self.workers),
            "max_inflight": self._max_inflight,
            "max_arena_slabs": self._max_arena_slabs,
        }

    def raw_gauges(self) -> dict:
        s = self.sc.stats.snapshot()
        busy = self.sc._staged.busy.busy_seconds()
        return {
            "queue_depth": float(self.in_q.qsize()),
            "arena_free": float(self.arena.free_slabs),
            "bytes_uploaded_total": float(s["bytes_uploaded"]),
            "batch_splits_total": float(s["batch_splits"]),
            # mean across dispatch targets: the controller reasons about
            # "the device side" as one saturation fraction
            "busy_seconds_total": sum(busy) / max(1, len(busy)),
        }

    def set_streams(self, n: int) -> None:
        # growth wakes parked workers (they poll the active count);
        # shrink parks the highest-numbered streams after they drain
        # their in-flight windows
        self.active_streams = max(1, min(len(self.workers), int(n)))

    def set_inflight(self, n: int) -> None:
        self.inflight = max(1, min(self._max_inflight, int(n)))

    def grow_arena(self, k: int) -> int:
        before = self.arena.n_slabs
        n = self.arena.grow(int(k), self._max_arena_slabs)
        if n > before:
            flight.note_resident(
                "arena", (n - before) * self.arena.rows * self.arena.row_len
            )
        return n

    def _telemetry_probe(self) -> dict[str, float]:
        """In-flight pipeline state for the telemetry sampler: arena
        occupancy, queue depths, per-stream windows, link-byte and
        per-device busy counters. Called only from a sampler thread
        (a few times per second); every read is a lock-or-GIL snapshot."""
        sc = self.sc
        vals = {
            "secret.arena_free_slabs": float(self.arena.free_slabs),
            "secret.arena_slabs": float(self.arena.n_slabs),
            "secret.feed_queue_depth": float(self.in_q.qsize()),
            "secret.files_pending": float(len(self.states)),
            "secret.results_buffered": float(len(self.results)),
            "secret.confirm_inflight": float(self._confirm_inflight),
            "secret.bytes_uploaded_total": float(
                sc.stats.snapshot()["bytes_uploaded"]
            ),
            "secret.active_streams": float(self.active_streams),
            "secret.inflight_window": float(self.inflight),
        }
        for i, n in enumerate(self._stream_inflight):
            vals[f"secret.stream{i}.inflight"] = float(n)
        vals.update(sc._staged.busy.probe())
        return vals

    def close(self) -> None:
        # the controller stops FIRST: it mutates active_streams/inflight/
        # arena, and its final doc() must freeze before the snapshot below
        if self.controller is not None:
            self.controller.stop()
        self.ctx.remove_probe(self._telemetry_probe)
        self.stop.set()
        self.feeder.join(timeout=10.0)
        for w in self.workers:
            w.join(timeout=10.0)
        self.pool.shutdown(wait=False)
        # push the dedup store's write-behind tail (one final round trip)
        # so the NEXT scan — possibly another process — starts warm
        self.sc._hit_store.flush_writes(force=True)
        # slabs still parked in the dispatch queue after an early close
        while True:
            try:
                item = self.in_q.get_nowait()
            except queue.Empty:
                break
            if item is not None and item is not _ABORT:
                self.arena.release(item[0])
        flight.release_resident(
            "arena", self.arena.n_slabs * self.arena.rows * self.arena.row_len
        )
        # feed-path introspection for tests and bench debugging: on a
        # clean scan every slab is back in the arena (no leak into the
        # streaming-RSS budget) and acquires ≫ slabs proves reuse
        self.sc._last_feed_stats = {
            "arena_slabs": self.arena.n_slabs,
            "arena_free": self.arena.free_slabs,
            "arena_acquires": self.arena.acquires,
            "streams": len(self.workers),
        }
        # effective-knob record: what this scan actually ran with at the
        # end (controller-adapted or static) — tuning_snapshot() surfaces
        # it into bench reps, --metrics-out, and heartbeat lines
        ctl_summary = None
        if self.controller is not None:
            d = self.controller.doc()
            # summary only: the full decision log rides the ctx exports
            # (--trace-out instants, --metrics-out tuning block); this
            # snapshot goes into compact bench rep details
            ctl_summary = {
                "ticks": d.get("ticks", 0),
                "decisions": d.get("decisions", 0),
                "initial": d.get("initial"),
                "final": d.get("final"),
            }
        self.sc._last_tuning = {
            "feed_streams": self.active_streams,
            "inflight": self.inflight,
            "arena_slabs": self.arena.n_slabs,
            "controller": ctl_summary,
        }

    # -- shared control -----------------------------------------------------

    def _aborted(self) -> bool:
        return (
            self.stop.is_set() or self.error is not None or self.degraded
        )

    def _put_slab(self, item) -> bool:
        while not self._aborted():
            try:
                self.in_q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def _put_sentinel(self) -> None:
        while not self._aborted():
            try:
                self.in_q.put(None, timeout=0.2)
                return
            except queue.Full:
                continue

    def _get_work(self):
        while True:
            if self._aborted():
                return _ABORT
            try:
                return self.in_q.get(timeout=0.2)
            except queue.Empty:
                continue

    def _fail(self, err: BaseException) -> None:
        with self.cond:
            if self.error is None:
                self.error = err
            self.cond.notify_all()
        self.stop.set()
        if self.lic_gate is not None:
            self.lic_gate.degrade()

    def _degrade(self, cause: BaseException) -> None:
        """Last rung: move every file with unresolved device work onto the
        exact host confirm path (partial device results are discarded),
        once. The feeder notices ``degraded`` and routes the rest of the
        input stream straight to the host engine."""
        with self.lock:
            if self.degraded or self.error is not None:
                return
            self.degraded = True
            moved = [(i, self.states.pop(i)) for i in sorted(self.states)]
            self.row_waiters.clear()
        if self.lic_gate is not None:
            # device verdicts are incomplete from here on: the license
            # analyzer must classify everything it collected
            self.lic_gate.degrade()
        self.sc._note_degraded(self.ctx, cause)
        for fidx, st in moved:
            self._submit_host(fidx, st.path, st.data)
        with self.cond:
            self.cond.notify_all()

    # -- result plumbing ----------------------------------------------------

    def _acquire_slot(self) -> bool:
        while not (self.stop.is_set() or self.error is not None):
            if self.confirm_slots.acquire(timeout=0.2):
                with self.lock:
                    self._confirm_inflight += 1
                return True
        return False

    def _release_slot(self) -> None:
        with self.lock:
            self._confirm_inflight -= 1
        self.confirm_slots.release()

    def _set_result(self, fidx: int, value) -> None:
        with self.cond:
            self.results[fidx] = value
            self.cond.notify_all()

    def _confirm_task(self, st: _FileState) -> Secret:
        try:
            with obs.activate(self.ctx), self.ctx.span("secret.confirm"):
                return self.sc._confirm(st, self.prof)
        finally:
            self._release_slot()

    def _host_task(self, path: str, data: bytes) -> Secret:
        # degraded-mode rung: the exact host engine IS the parity oracle,
        # so fallback findings are byte-identical by definition
        try:
            with obs.activate(self.ctx), self.ctx.span("secret.host_fallback"):
                return self.sc.exact.scan_bytes(path, data)
        finally:
            self._release_slot()

    def _submit_confirm(self, fidx: int, st: _FileState) -> None:
        if not self._acquire_slot():
            return  # shutting down; nobody will wait on this result
        self._set_result(fidx, self.pool.submit(self._confirm_task, st))

    def _submit_host(self, fidx: int, path: str, data: bytes) -> None:
        if not self._acquire_slot():
            return
        self._set_result(fidx, self.pool.submit(self._host_task, path, data))

    def _apply_lic(self, segs, lic) -> None:
        """Fold one row's fused license verdict into the gate. ``lic`` is
        a tuple of hit BLOCK indices from the gram gate (usually empty),
        or None when the row's batch never ran it — a wanted file
        replaying an ungated cached row falls back to exact classification
        (gate.skip), never to a silent miss.

        Segment row-offsets are reconstructed from the packing layout
        (cumulative ``len + gap``, exactly how emit_pack laid them out) so
        a hit block flags only the file(s) it overlaps."""
        gate = self.lic_gate
        if gate is None:
            return
        if lic is None:
            for fidx, _, _ in segs:
                path = self.lic_paths.get(fidx)
                if path is not None:
                    gate.skip(path)
            return
        if not lic:
            return
        blk = self.sc._lic_block
        gap = self.sc.overlap
        chunk_len = self.sc.chunk_len
        off = 0
        for i, (fidx, ws, we) in enumerate(segs):
            seg_len = we - ws
            if len(segs) == 1:
                lo, hi = 0, chunk_len  # whole-row segment (big-file chunk)
            else:
                lo, hi = off, off + seg_len
                off += seg_len + gap
            path = self.lic_paths.get(fidx)
            if path is None:
                continue
            # a hit block overlapping [lo, hi) flags this segment's file;
            # boundary-straddling blocks flag both neighbors (FP-only)
            if any(b * blk < hi and (b + 1) * blk > lo for b in lic):
                gate.flag(path)

    def _apply_hits(self, batch: list) -> None:
        """Credit resolved rows to their file segments; ``batch`` is
        ``[(segs, hit_rules, cand_rules, nfa_ran, lic)]`` (the row-verdict
        schema of the dedup cache; ``nfa_ran`` is always true since every
        batch runs the whole rule pass). Every row hit applies to every
        segment — cross-segment false candidates are discarded by the
        exact confirm. Files whose last pending row resolved here go to the
        confirm pool (the semaphore is taken OUTSIDE the pipeline lock so
        a full confirm queue stalls only the calling thread, not
        resolution bookkeeping on other streams)."""
        sc = self.sc
        prof = self.prof
        if prof is not None:
            rule_ids = sc.compiled.rule_ids
            for _, hit_rules, cand_rules, _, _ in batch:
                # one logical device hit per (row, rule) — dedup-cache and
                # coalesced rows count too: they cost a confirm all the same
                for r in hit_rules:
                    prof.gate_hit(rule_ids[r])
                for r in cand_rules:
                    prof.prefilter_hit(rule_ids[r])
        # license verdicts land BEFORE the rows count as resolved: once a
        # file's last row resolves, its result can be emitted and the
        # license analyzer reads the gate, while another stream may still
        # be folding an earlier batch of the same file
        for segs, _, _, _, lic in batch:
            self._apply_lic(segs, lic)
        ready: list[tuple[int, _FileState]] = []
        with self.lock:
            for segs, hit_rules, cand_rules, _, _ in batch:
                for fidx, ws, we in segs:
                    st = self.states.get(fidx)
                    if st is None:
                        continue  # already moved to the host path
                    for r in hit_rules:
                        st.rules.setdefault(r, []).append((ws, we))
                    st.cand.update(cand_rules)
                for fidx, _, _ in segs:
                    st = self.states.get(fidx)
                    if st is None:
                        continue
                    st.pending -= 1
                    if st.pending == 0:
                        del self.states[fidx]
                        ready.append((fidx, st))
        for fidx, st in ready:
            self._submit_confirm(fidx, st)

    def _resolve(
        self,
        out: np.ndarray,
        batch_meta: list,
        lic_arr: np.ndarray | None = None,
        lic_ran: bool = False,
    ) -> None:
        """Fold one fetched batch into file state. ``out`` is the rule
        pass's ``[B, out_words]`` result, ``lic_arr`` the license gate
        flags; both sliced to the live rows here (rows past
        ``len(batch_meta)`` are bucket padding)."""
        sc = self.sc
        n = len(batch_meta)
        out = np.asarray(out[:n])
        hit_rows, hit_rules, cand_rows, cand_rules = sc.tables.pairs(out)
        by_row: dict[int, list[int]] = {}
        cand_by_row: dict[int, list[int]] = {}
        for row, r in zip(hit_rows.tolist(), hit_rules.tolist()):
            by_row.setdefault(row, []).append(r)
        for row, r in zip(cand_rows.tolist(), cand_rules.tolist()):
            cand_by_row.setdefault(row, []).append(r)
        hit_n = len(cand_by_row)
        sc.stats.add(rows_prefiltered=n, rows_prefilter_hit=hit_n)
        if self.prof is not None:
            self.prof.prefilter_rows(n, hit_n)
        if self.enabled:
            R = sc.compiled.num_rules
            flagged = np.unique(np.concatenate(
                [hit_rows * R + hit_rules, cand_rows * R + cand_rules]
            ))
            self.ctx.count("secret.rule_candidates", len(flagged))
            self.ctx.count("secret.rule_spill_hits", sc.tables.spill_hits(out))
            ovf = int(out[:, -1].sum())
            if ovf:
                self.ctx.count("secret.rule_probe_overflow", ovf)
        lic_by_row: dict[int, tuple[int, ...]] = {}
        if lic_ran and lic_arr is not None:
            lic_arr = np.asarray(lic_arr[:n], dtype=bool)
            rows, blks = np.nonzero(lic_arr)
            for row, b in zip(rows.tolist(), blks.tolist()):
                lic_by_row.setdefault(row, ())
                lic_by_row[row] = lic_by_row[row] + (b,)
            sc.stats.add(
                license_rows_gated=n,
                license_rows_flagged=int(lic_arr.any(axis=1).sum()),
            )
        apply: list = []
        for row, (key, segs, _) in enumerate(batch_meta):
            hit_rules_r = tuple(by_row.get(row, ()))
            cand_rules_r = tuple(cand_by_row.get(row, ()))
            lic = lic_by_row.get(row, ()) if lic_ran else None
            verdict = (hit_rules_r, cand_rules_r, True, lic)
            apply.append((segs,) + verdict)
            if key is not None:
                self.sc._hit_put(key, verdict)
                with self.lock:
                    waiting = self.row_waiters.pop(key, ())
                for w in waiting:
                    apply.append((w,) + verdict)
        # write-behind flush: one pipelined backend round trip per batch
        # (no-op without a persistent backend / below the batch threshold)
        self.sc._hit_store.flush_writes()
        self._apply_hits(apply)

    # -- transfer-stream workers --------------------------------------------

    def _worker(self, wid: int) -> None:
        """One transfer stream: place slabs once, run the fused device
        stages against the resident rows, keep a bounded in-flight window
        (double buffering), fetch the oldest, resolve inline.

        Per-batch staging: the rule pass (and, when fused, the license
        gram gate) dispatches immediately with the upload. The slab
        releases only after the LAST stage reading the resident input has
        fetched (device_put may alias host memory on the CPU backend — an
        earlier release would let the feeder refill bytes a later-stage
        kernel still reads).

        Per-batch failure ladder as in README "Robustness": re-dispatch up
        to ``batch_retries`` times (under round-robin the retry lands on
        the next healthy device and the breaker hears about it),
        OOM-shaped errors split the batch in half, and only an exhausted
        ladder (or every device circuit-broken) escalates to the
        scan-level host fallback.

        Stall instrumentation (all on the spawning scan's context):
        ``secret.feed_wait`` is time blocked on the host feed
        (feed-starved), ``secret.dispatch`` the enqueue/transfer handoff
        (upload-bound), ``secret.device_wait`` the blocking rule-pass
        fetch (device-bound)."""
        from trivy_tpu.parallel.mesh import DevicesUnavailable

        sc = self.sc
        ctx = self.ctx
        staged = sc._staged
        lic_gate = self.lic_gate
        prof = self.prof
        stats = sc.stats
        chunk_len = sc.chunk_len
        # (dev_input, meta, batch, slab_id, device_idx, retries, handles);
        # slab_id is None for retry copies, which own their arrays outright
        pending: deque = deque()

        def rebatch(batch: np.ndarray, meta: list) -> np.ndarray:
            """Fresh bucket-padded copy of a failed batch's live rows —
            the source slab is released right after, so retries never
            alias arena memory the feeder may refill."""
            n = next(b for b in sc._buckets if b >= len(meta))
            out = np.zeros((n, chunk_len), dtype=np.uint8)
            out[: len(meta)] = batch[: len(meta)]
            return out

        def recover(batch, meta, slab_id, retries, err) -> list:
            """Ladder decision for one failed batch: work items to
            re-dispatch, or raise when the ladder is exhausted. Always
            ends the source slab's ownership."""
            if isinstance(err, DevicesUnavailable):
                if slab_id is not None:
                    self.arena.release(slab_id)
                raise _DeviceFailed(err)  # no device left to retry on
            if _is_oom(err) and len(meta) > 1:
                stats.add(batch_splits=1)
                if self.enabled:
                    ctx.count("secret.batch_splits")
                flight.record(
                    "oom", "secret.batch_split",
                    {"rows": len(meta), "error": str(err)},
                )
                logger.warning(
                    "device OOM on a %d-row batch (%s); splitting and "
                    "re-dispatching the halves", len(meta), err,
                )
                mid = (len(meta) + 1) // 2
                halves = [
                    (rebatch(batch[:mid], meta[:mid]), meta[:mid], None, retries),
                    (rebatch(batch[mid:], meta[mid:]), meta[mid:], None, retries),
                ]
                if slab_id is not None:
                    self.arena.release(slab_id)
                return halves
            if retries < sc._batch_retries:
                stats.add(batch_retries=1)
                if self.enabled:
                    ctx.count("secret.batch_retries")
                flight.record(
                    "retry", "secret.batch_retry",
                    {"rows": len(meta), "attempt": retries + 1,
                     "error": str(err)},
                )
                logger.warning(
                    "device error on a %d-row batch (retry %d/%d): %s",
                    len(meta), retries + 1, sc._batch_retries, err,
                )
                fresh = rebatch(batch, meta)
                if slab_id is not None:
                    self.arena.release(slab_id)
                return [(fresh, meta, None, retries + 1)]
            if slab_id is not None:
                self.arena.release(slab_id)
            raise _DeviceFailed(err)

        def want_lic(meta) -> bool:
            if lic_gate is None:
                return False
            lp = self.lic_paths
            return any(
                fidx in lp for _, segs, _ in meta for fidx, _, _ in segs
            )

        def dispatch_batch(batch, meta, slab_id, retries) -> None:
            work = [(batch, meta, slab_id, retries)]
            while work:
                b, m, sid, r = work.pop()
                placed = False
                didx = None
                try:
                    with ctx.span("secret.dispatch"):
                        dev, didx = staged.put(b)
                        placed = True
                    h: dict = {}
                    with ctx.span("secret.dispatch"):
                        h["rules"] = staged.run("rules", dev, didx)
                        if want_lic(m):
                            h["lic"] = staged.run("license", dev, didx)
                except Exception as e:
                    # dispatch-time failure (breaker already notified by
                    # the placement layer); walk the ladder. A batch that
                    # placed but failed at stage launch closes its busy
                    # interval here (no fetch will ever report it)
                    if placed:
                        staged.busy.end(didx)
                    work.extend(recover(b, m, sid, r, e))
                    continue
                pending.append((dev, m, b, sid, didx, r, h))

        def fetch_oldest() -> None:
            dev, meta, batch, sid, didx, retries, h = pending.popleft()
            try:
                faults.check(
                    "device.fetch", key=f"d{didx if didx is not None else 0}"
                )
                t0 = time.perf_counter() if prof is not None else 0.0
                with ctx.span("secret.device_wait"):
                    arr = np.asarray(h["rules"])
                if self.enabled:
                    # the pass's shapes, as bytes: input rows, result rows,
                    # and the tables each execution reads
                    ctx.count("secret.rule_row_bytes", arr.shape[0] * chunk_len)
                    ctx.count("secret.rule_out_bytes", arr.nbytes)
                    ctx.count("secret.rule_table_bytes", sc.tables.nbytes())
                lic_ran = "lic" in h
                lic_arr = np.asarray(h["lic"]) if lic_ran else None
                # every stage that reads the resident input has now fetched
                # — only here is the slab provably free of zero-copy device
                # views (jax.device_put may ALIAS host memory on the CPU
                # backend, so "the transfer finished" is not enough while a
                # later-stage kernel could still read the input)
                if sid is not None:
                    self.arena.release(sid)
                    sid = None
                if prof is not None:
                    # per-bucket dispatch cost: the bucket is the padded
                    # batch shape (the compile-once ladder rung), rows are
                    # the live rows it carried
                    prof.bucket_dispatch(
                        batch.shape[0], len(meta), time.perf_counter() - t0
                    )
            except Exception as e:
                staged.record_result(didx, False)
                for item in recover(batch, meta, sid, retries, e):
                    dispatch_batch(*item)
                return
            staged.record_result(didx, True)
            if sid is not None:
                self.arena.release(sid)
            if not self.degraded:
                self._resolve(arr, meta, lic_arr=lic_arr, lic_ran=lic_ran)

        def release_pending() -> None:
            while pending:
                _, _, _, sid, didx, _, _ = pending.popleft()
                if sid is not None:
                    self.arena.release(sid)
                # close the dropped batch's busy interval: a degraded scan
                # runs on for minutes, and an unclosed interval would pin
                # the dead device's busy_ratio gauge at 1.0 the whole time
                staged.busy.end(didx)

        with obs.activate(ctx):
            try:
                while True:
                    if wid >= self.active_streams:
                        # parked by the online controller: drain this
                        # stream's in-flight window, then idle until
                        # unparked, shutdown, or end of input — a parked
                        # stream takes no new work, which is exactly how
                        # "shrink streams" reduces link concurrency
                        while pending and not self._aborted():
                            fetch_oldest()
                            self._stream_inflight[wid] = len(pending)
                        if self._aborted() or self.feed_done.is_set():
                            break
                        self.stop.wait(0.1)
                        continue
                    with ctx.span("secret.feed_wait"):
                        item = self._get_work()
                    if item is _ABORT:
                        break
                    if item is None:
                        # end-of-input sentinel: re-post it so the next
                        # active worker sees it too (one sentinel cascades
                        # through however many streams are active; parked
                        # workers exit on feed_done instead)
                        self._put_sentinel()
                        break
                    slab_id, batch, meta = item
                    dispatch_batch(batch, meta, slab_id, 0)
                    self._stream_inflight[wid] = len(pending)
                    while len(pending) >= self.inflight:
                        fetch_oldest()
                        self._stream_inflight[wid] = len(pending)
                while pending and not self._aborted():
                    fetch_oldest()
                    self._stream_inflight[wid] = len(pending)
            except _DeviceFailed as e:
                release_pending()
                if sc._host_fallback:
                    self._degrade(e.cause)
                else:
                    self._fail(e.cause)
            except BaseException as e:  # unexpected: surface it loudly
                release_pending()
                self._fail(e)
            finally:
                release_pending()
                self._stream_inflight[wid] = 0
                if self.degraded:
                    # return whatever the feeder parked before it noticed
                    while True:
                        try:
                            item = self.in_q.get_nowait()
                        except queue.Empty:
                            break
                        if item is not None and item is not _ABORT:
                            self.arena.release(item[0])

    # -- feeder -------------------------------------------------------------

    def _feed_guarded(self) -> None:
        with obs.activate(self.ctx):
            try:
                self._feed()
            except BaseException as e:
                self._fail(e)

    def _feed(self) -> None:
        sc = self.sc
        ctx = self.ctx
        enabled = self.enabled
        stats = sc.stats
        chunk_len = sc.chunk_len
        B = sc.batch_size
        dedup = sc._dedup
        # fused-license scans use a disjoint digest namespace: their cached
        # row verdicts carry a license-gate bit that plain scans never set
        fp_key = (
            sc.ruleset_fingerprint
            if self.lic_gate is None
            else sc.ruleset_fingerprint + b":lic"
        )
        lic_gate = self.lic_gate
        # widest gram/anchor byte window the device gate provably sees
        # interior to some chunk (licensing/fused.py host patch covers the
        # rest)
        lic_span_bound = sc.overlap - 2
        gap = sc.overlap
        pack_max = chunk_len - gap
        blake2b = hashlib.blake2b

        def lic_register(fidx: int, path: str, data: bytes) -> None:
            """Fused pass bookkeeping for one file entering the device
            feed: coverage + the host wide-window patch, and the fidx ->
            path mapping row resolution flags against."""
            if lic_gate is not None and lic_gate.wants(path):
                self.lic_paths[fidx] = path
                lic_gate.feed_file(path, data, lic_span_bound)

        def lic_skip(path: str) -> None:
            """This path's bytes will not (all) ride the device pass —
            the license analyzer must classify it itself."""
            if lic_gate is not None and lic_gate.wants(path):
                lic_gate.skip(path)

        persist_on = dedup and sc._hit_store.backend is not None
        slab_id: int | None = None
        slab: np.ndarray | None = None
        used = 0
        # per-row feed metadata:
        # (digest | None, [(fidx, win_start, win_end)], corpus_bytes)
        meta: list[tuple[bytes | None, list[tuple[int, int, int]], int]] = []
        # slab rows awaiting the bulk strided gather from the current file
        copy_rows: list[int] = []
        copy_starts: list[int] = []
        copy_win = None  # row_windows view over the current file's bytes
        pack_pending: list[tuple[int, bytes]] = []
        pack_len = 0
        total = 0

        class _FeedAbort(Exception):
            pass

        def flush_copies() -> None:
            nonlocal copy_rows, copy_starts
            if copy_rows:
                # ONE vectorized gather for every full row the current
                # file placed in this slab
                slab[np.asarray(copy_rows)] = copy_win[np.asarray(copy_starts)]
                copy_rows = []
                copy_starts = []

        def ensure_slab() -> None:
            nonlocal slab_id, slab, used
            if slab is None:
                with ctx.span("secret.arena_wait"):
                    got = self.arena.acquire(self._aborted)
                if got is None:
                    raise _FeedAbort
                slab_id, slab = got
                used = 0

        def register_state(fidx: int, st: _FileState) -> bool:
            """False when the scan degraded concurrently — the caller
            must route the file to the host path instead (a state added
            after :meth:`_degrade` swept the table would never resolve)."""
            with self.lock:
                if self.degraded:
                    return False
                self.states[fidx] = st
                return True

        def route_row(key, segs, nbytes) -> bool:
            """True when the row resolved without an upload: served from
            the hit cache or coalesced onto an identical in-flight row."""
            if key is None:
                return False
            cached = sc._hit_get(key)
            if cached is not None:
                stats.add(chunks_dedup_hit=1, bytes_dedup_hit=nbytes)
                if enabled:
                    ctx.count("secret.bytes_dedup_hit", nbytes)
                self._apply_hits([(segs,) + cached])
                return True
            with self.lock:
                waiting = self.row_waiters.get(key)
                if waiting is not None:
                    waiting.append(segs)
                    coalesced = True
                else:
                    self.row_waiters[key] = []
                    coalesced = False
            if coalesced:
                stats.add(chunks_dedup_hit=1, bytes_dedup_hit=nbytes)
                if enabled:
                    ctx.count("secret.bytes_dedup_hit", nbytes)
            return coalesced

        def warm_filter() -> None:
            """Persistent-store lookup for the assembled slab's rows: ONE
            pipelined backend round trip per batch (never per row). Rows
            whose verdict is already persisted resolve right here — no
            upload, no kernel — and the slab compacts over the survivors
            with one vectorized gather."""
            nonlocal meta
            keys = [k for k, _, _ in meta if k is not None]
            if not keys:
                return
            with ctx.span("secret.warm_hit"):
                found = sc._hit_store.lookup_batch(keys)
            if not found:
                return
            live: list[int] = []
            warm_apply: list = []
            warm_rows = 0
            warm_bytes = 0
            for i, (k, segs, nbytes) in enumerate(meta):
                v = found.get(k) if k is not None else None
                if v is None:
                    live.append(i)
                    continue
                warm_rows += 1
                warm_bytes += nbytes
                warm_apply.append((segs,) + v)
                with self.lock:
                    waiting = self.row_waiters.pop(k, ())
                for w in waiting:
                    warm_apply.append((w,) + v)
            if not warm_apply:
                return
            # chunks_uploaded was counted at assembly; correct it so the
            # dedup-hit-rate denominators stay exact
            stats.add(
                chunks_dedup_hit=warm_rows, bytes_dedup_hit=warm_bytes,
                chunks_warm_hit=warm_rows, bytes_warm_hit=warm_bytes,
                chunks_uploaded=-warm_rows,
            )
            if enabled:
                ctx.count("secret.bytes_dedup_hit", warm_bytes)
                ctx.count("secret.bytes_warm_hit", warm_bytes)
            self._apply_hits(warm_apply)
            if live:
                slab[: len(live)] = slab[np.asarray(live)]
            meta = [meta[i] for i in live]

        def flush() -> None:
            nonlocal slab_id, slab, used, meta
            flush_copies()
            if persist_on and meta:
                warm_filter()
            if not meta:
                # empty slab: padding-only batches are never sent (and a
                # fully-warm slab resolved above with no upload at all)
                if slab is not None:
                    self.arena.release(slab_id)
                    slab_id = None
                    slab = None
                    used = 0
                # a fully-warm flush is still a batch boundary: the pack
                # staleness bound below must hold on warm streaming scans
                # too, or a lone small file would stall in-order emission
                # until end-of-input
                if pack_pending:
                    emit_pack()
                return
            n = next(b for b in sc._buckets if b >= len(meta))
            stats.add(bytes_uploaded=n * chunk_len)
            if enabled:
                ctx.count("secret.bytes_uploaded", n * chunk_len)
                ctx.sample("secret.queue_depth", self.in_q.qsize())
            ok = self._put_slab((slab_id, slab[:n], meta))
            if not ok:
                self.arena.release(slab_id)
            slab_id = None
            slab = None
            used = 0
            meta = []
            if not ok:
                raise _FeedAbort
            # bound pack-row staleness to one batch: a lone small file must
            # not sit in pack_pending while big files stream past it — its
            # unresolved state would stall in-order emission and let results
            # accumulate unbounded on a streaming scan. The partial pack row
            # rides the next batch instead (re-entry is shallow: the fresh
            # meta holds one row, far below batch_size, so no second flush)
            if pack_pending:
                emit_pack()

        def emit_pack() -> None:
            nonlocal pack_len, used
            if not pack_pending:
                return
            items = list(pack_pending)
            pack_pending.clear()
            pack_len = 0
            key = None
            if dedup:
                if len(items) == 1:
                    # single-segment row == plain chunk-row layout: share the
                    # plain digest domain so it dedups across both paths
                    key = blake2b(
                        items[0][1], digest_size=16, key=fp_key
                    ).digest()
                else:
                    h = blake2b(
                        digest_size=16, key=fp_key, person=b"packed-row"
                    )
                    for _, d in items:
                        h.update(len(d).to_bytes(4, "little"))
                        h.update(d)
                    key = h.digest()
            segs = [(fidx, 0, len(d)) for fidx, d in items]
            nbytes = sum(len(d) for _, d in items)
            stats.add(chunks=1)
            if route_row(key, segs, nbytes):
                return
            ensure_slab()
            row = slab[used]
            row[:] = 0  # zero guard gaps + stale tail (slabs are reused)
            off = 0
            for _, d in items:
                row[off : off + len(d)] = np.frombuffer(d, dtype=np.uint8)
                off += len(d) + gap
            meta.append((key, segs, nbytes))
            used += 1
            stats.add(chunks_uploaded=1)
            if len(segs) > 1:
                stats.add(
                    rows_packed=1, files_packed=len(segs), bytes_packed=nbytes
                )
                if enabled:
                    ctx.count("secret.bytes_packed", nbytes)
            if used == B:
                flush()

        def add_small(fidx: int, data: bytes) -> None:
            # small-file packing: files below a row's size accumulate and
            # share one row, separated by >=span zero gaps (see module
            # docstring for why packing cannot suppress a real match)
            nonlocal pack_len
            if pack_len and pack_len + gap + len(data) > chunk_len:
                emit_pack()
            pack_pending.append((fidx, data))
            pack_len += (gap if pack_len else 0) + len(data)

        def feed_big(fidx: int, path: str, data: bytes) -> None:
            nonlocal used, copy_win
            starts = chunk_spans(len(data), chunk_len, sc.overlap)
            if not register_state(
                fidx,
                _FileState(path=path, data=data, pending=len(starts)),
            ):
                lic_skip(path)
                self._submit_host(fidx, path, data)
                return
            lic_register(fidx, path, data)
            arr = np.frombuffer(data, dtype=np.uint8)
            n = arr.size
            stats.add(bytes_in=len(data), chunks=len(starts))
            copy_win = row_windows(arr, chunk_len)
            uploaded = 0
            for s in starts:
                end = min(s + chunk_len, n)
                key = (
                    blake2b(arr[s:end], digest_size=16, key=fp_key).digest()
                    if dedup
                    else None
                )
                segs = [(fidx, s, s + chunk_len)]
                if route_row(key, segs, end - s):
                    continue
                ensure_slab()
                if end - s == chunk_len:
                    copy_rows.append(used)
                    copy_starts.append(s)
                else:
                    # short tail row: copy, then zero the stale remainder
                    slab[used, : end - s] = arr[s:end]
                    slab[used, end - s :] = 0
                meta.append((key, segs, end - s))
                used += 1
                uploaded += 1
                if used == B:
                    flush()
            flush_copies()  # the view dies with this file's scope
            copy_win = None
            if uploaded:
                stats.add(chunks_uploaded=uploaded)

        feed_ok = True
        try:
            for fidx, (path, data) in enumerate(self.files):
                total = fidx + 1
                if self.stop.is_set() or self.error is not None:
                    total -= 1  # not processed; the generator is closing
                    break
                if self.degraded:
                    # device path is gone: route straight to the exact host
                    # engine under the same confirm backpressure (files
                    # already swept by _degrade keep their host results)
                    pack_pending.clear()
                    lic_skip(path)
                    self._submit_host(fidx, path, data)
                    continue
                try:
                    with ctx.span("secret.assemble"):
                        if sc.exact.allow_path(path):
                            # path-level global allowlist: skip the whole
                            # file (ref: scanner.go:388-392) — no device work
                            lic_skip(path)
                            self._set_result(fidx, Secret(file_path=path))
                        elif not data:
                            # empty file: nothing for the device to match —
                            # resolve host-side immediately (host-lane rules
                            # still run there); zero bytes means the fused
                            # license gate misses nothing either
                            if lic_gate is not None and lic_gate.wants(path):
                                lic_gate.cover(path)
                            self._submit_confirm(
                                fidx,
                                _FileState(path=path, data=data, pending=0),
                            )
                        elif sc._pack_small and len(data) <= pack_max:
                            stats.add(bytes_in=len(data))
                            if register_state(
                                fidx,
                                _FileState(path=path, data=data, pending=1),
                            ):
                                lic_register(fidx, path, data)
                                add_small(fidx, data)
                            else:
                                lic_skip(path)
                                self._submit_host(fidx, path, data)
                        else:
                            feed_big(fidx, path, data)
                except _FeedAbort:
                    # mid-file abort: a registered state was already swept
                    # onto the host path by _degrade; on plain shutdown the
                    # generator is closing and nobody waits on this file
                    if not self.degraded:
                        break
            if not self._aborted():
                try:
                    emit_pack()  # flush the partial pack row
                    flush()  # dispatch the final partial slab
                except _FeedAbort:
                    pass
        except BaseException:
            # do NOT publish `total` on a failed feed: emission must see
            # the error (set by _feed_guarded), not a truncated-but-
            # "complete" input count that would silently swallow it
            feed_ok = False
            raise
        finally:
            if slab is not None:
                # an unflushed (empty or aborted) slab goes straight back:
                # padding rows never reach the dispatch queue or dedup keys
                self.arena.release(slab_id)
            with self.cond:
                if feed_ok:
                    self.total = total
                self.cond.notify_all()
            # end-of-input: parked workers exit on feed_done; ONE sentinel
            # cascades through the active workers (each re-posts it before
            # exiting), so the count stays right however many streams the
            # online controller parked or woke mid-scan
            self.feed_done.set()
            self._put_sentinel()
