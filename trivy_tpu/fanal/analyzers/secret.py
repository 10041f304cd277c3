"""Secret analyzer: file eligibility + streaming device scanning.

Mirrors the reference's pre-filters exactly (ref:
pkg/fanal/analyzer/secret/secret.go:152-190 — min size 10 bytes, skip dirs
.git/node_modules, skip lockfiles, skip binary-ish extensions, global allow
paths) and its content normalization (ref: secret.go:103-150 — binary sniff
with printable-strings fallback for allowed binaries, CR stripping, leading
'/' for image layers). The scan itself is the TPU-first divergence: files
*stream* from the walk into a persistent ``TpuSecretScanner.scan_files``
call running on a background thread (a byte-bounded
:class:`trivy_tpu.secret.feed.FileStream` is the handoff), so walking and
reading overlap chunking, transfers, and device matching instead of
alternating in buffer-sized bursts — the reference's walker-goroutines →
bounded-channel → workers shape, with the device pipeline as the worker
pool. Exact host confirm keeps findings byte-identical.
"""

from __future__ import annotations

import os.path
import threading

from trivy_tpu.fanal import utils
from trivy_tpu.fanal.analyzer import (
    AnalysisInput,
    AnalysisResult,
    AnalyzerType,
    BatchAnalyzer,
    register_analyzer,
)
from trivy_tpu import log
from trivy_tpu.secret.engine import ScannerConfig, SecretScanner

logger = log.logger("secret")

# process-wide scanner cache: layer-parallel image analysis builds one
# analyzer group per layer, and each group must NOT compile its own device
# match program (concurrent per-layer compiles through a remote-compile
# service can wedge; scan_files keeps all mutable state per-call, so one
# scanner instance serves concurrent scans safely)
_scanner_lock = __import__("threading").Lock()
_scanner_cache: dict = {}


# parsed --secret-config files by content digest: each scan of a CLI loop
# or a server gets the same config object for the same file, so the
# scanner built for it (compiled rules, device tables) is found again
_config_cache: dict = {}
_CONFIG_CACHE_ENTRIES = 8


def _load_config(path: str) -> ScannerConfig:
    import hashlib

    with open(path, "rb") as f:
        raw = f.read()
    digest = hashlib.sha256(raw).hexdigest()
    with _scanner_lock:
        cfg = _config_cache.get(digest)
    if cfg is None:
        cfg = ScannerConfig.from_yaml_bytes(raw)
        with _scanner_lock:
            if len(_config_cache) >= _CONFIG_CACHE_ENTRIES:
                _config_cache.pop(next(iter(_config_cache)))
            cfg = _config_cache.setdefault(digest, cfg)
    return cfg


def _shared_scanner(
    config, backend: str, parallel: int,
    dedup: bool = True, pack_small: bool = True, hit_cache=None,
    host_fallback: bool = True, feed_streams: int = 0, inflight: int = 0,
    tuning=None,
):
    # the resolved TuningConfig participates in the cache key by VALUE:
    # two scans tuned differently must not share one compiled scanner's
    # stream topology (same fields, same scanner — autotune records make
    # this common)
    tuning_key = None
    if tuning is not None:
        tuning_key = (
            tuning.feed_streams, tuning.inflight, tuning.arena_slabs,
            tuning.bucket_rungs, tuning.controller, tuning.tuning_interval,
            tuning.dedup_store_mb,
        )
    key = (
        (config.source_digest or id(config)) if config is not None else None,
        backend, parallel, dedup, pack_small,
        id(hit_cache) if hit_cache is not None else None,
        host_fallback, feed_streams, inflight, tuning_key,
    )
    with _scanner_lock:
        if key not in _scanner_cache:
            init_degraded = False
            if backend == "cpu":
                scanner = SecretScanner(config)
            else:
                try:
                    from trivy_tpu.secret.tpu_scanner import TpuSecretScanner

                    scanner = TpuSecretScanner(
                        config, backend=backend, confirm_workers=parallel,
                        dedup=dedup, pack_small=pack_small,
                        hit_cache=hit_cache, host_fallback=host_fallback,
                        feed_streams=feed_streams, inflight=inflight,
                        tuning=tuning,
                    )
                    if scanner.backend == "pallas":
                        # device compiles take seconds to minutes per
                        # shape: pay them once per process, concurrently,
                        # before the first batch instead of mid-scan
                        scanner.warm_buckets()
                except Exception as e:
                    # --backend failed at init (jax import, device probe,
                    # kernel compile): the ladder's last rung applies here
                    # too — scan on the exact host engine instead of dying
                    if not host_fallback:
                        raise
                    logger.warning(
                        "device backend %r failed to initialize (%s); "
                        "scanning on the exact host engine", backend, e,
                    )
                    scanner = SecretScanner(config)
                    init_degraded = True
            _scanner_cache[key] = (scanner, init_degraded)
        scanner, init_degraded = _scanner_cache[key]
        if init_degraded:
            # every scan served by this fallback engine is a degraded scan
            from trivy_tpu import obs

            obs.note_scan_degraded()
        return scanner


# ref: secret.go:28-62
SKIP_FILES = {
    "go.mod",
    "go.sum",
    "package-lock.json",
    "yarn.lock",
    "pnpm-lock.yaml",
    "Pipfile.lock",
    "Gemfile.lock",
}
SKIP_DIRS = {".git", "node_modules"}
SKIP_EXTS = {
    ".jpg", ".png", ".gif", ".doc", ".pdf", ".bin", ".svg", ".socket",
    ".deb", ".rpm", ".zip", ".gz", ".gzip", ".tar",
}
ALLOWED_BINARIES = {".pyc"}

LARGE_FILE_WARN = 10 * 1024 * 1024  # ref: secret.go:110
# byte budget of the walk→device handoff stream: the walk blocks once this
# much collected content is waiting on the device pipeline, bounding host
# memory on large trees (formerly the synchronous 64 MB flush batch)
STREAM_BUFFER_BYTES = 64 * 1024 * 1024


class _StreamScan:
    """One walk's background device scan: a byte-bounded FileStream feeds
    a persistent ``scan_files`` call on a worker thread, so collection
    (walk + read) and device scanning overlap. ``finish`` closes the
    stream, joins the consumer, and re-raises any scan failure. With a
    fused license gate, the same device pass also accumulates license
    candidate verdicts against the shared arena rows."""

    def __init__(self, scanner, ctx, license_gate=None):
        from trivy_tpu.secret.feed import FileStream

        self.stream = FileStream(STREAM_BUFFER_BYTES)
        self.found: list = []
        self.error: BaseException | None = None
        self._scanner = scanner
        self._ctx = ctx
        self._license_gate = license_gate
        self.thread = threading.Thread(
            target=self._run, daemon=True, name="secret-stream-scan"
        )
        self.thread.start()

    def _run(self) -> None:
        from trivy_tpu import obs

        try:
            with obs.activate(self._ctx):
                for s in self._scanner.scan_files(
                    self.stream, license_gate=self._license_gate
                ):
                    if s.findings:
                        self.found.append(s)
        except BaseException as e:
            self.error = e
            # unblock (and poison) any producer waiting on the byte budget
            self.stream.fail(e)

    def put(self, path: str, content: bytes) -> None:
        self.stream.put(path, content)

    def finish(self) -> list:
        self.stream.close()
        self.thread.join()
        if self.error is not None:
            raise self.error
        return self.found

    def abort(self) -> None:
        """End the background scan without results: poisoning the stream
        ends the feeder's input, the pipeline drains, and the consumer
        thread exits — no leaked threads or arena slabs."""
        self.stream.fail(RuntimeError("artifact scan aborted"))
        self.thread.join(timeout=10.0)
        self.found = []


class SecretAnalyzer(BatchAnalyzer):
    type = AnalyzerType.SECRET
    version = 1
    # the fused-pass license gate must be fully populated before the
    # license analyzers' finalize reads it (see AnalyzerGroup.finalize)
    finalize_order = 10

    def __init__(self, options):
        cfg = None
        self.config_path = getattr(options, "secret_config_path", None)
        # resolve the config file to a scan-root-relative path so the
        # self-exclusion matches at any nesting depth (the reference compares
        # the full path, not the basename)
        self._config_rel_path = None
        if self.config_path:
            root = getattr(options, "root", None) or "."
            rel = os.path.relpath(
                os.path.abspath(self.config_path), os.path.abspath(root)
            )
            if not rel.startswith(".."):
                self._config_rel_path = os.path.normpath(rel)
        if self.config_path and os.path.exists(self.config_path):
            cfg = _load_config(self.config_path)
        backend = getattr(options, "backend", "auto")
        self._config = cfg
        self._backend = backend
        extra = getattr(options, "extra", {}) or {}
        self._parallel = int(extra.get("parallel", 0))
        # feed-path knobs (--no-secret-dedup / --no-secret-pack /
        # --secret-hit-cache), defaulting to dedup+packing on
        self._dedup = bool(extra.get("secret_dedup", True))
        self._pack = bool(extra.get("secret_pack", True))
        self._hit_cache = extra.get("secret_hit_cache")
        # --no-host-fallback: fail the scan on device errors instead of
        # degrading to the exact host path (CI parity gates want loud)
        self._host_fallback = bool(extra.get("host_fallback", True))
        # async feed-path knobs (--secret-streams / --secret-inflight)
        self._feed_streams = int(extra.get("secret_streams", 0) or 0)
        self._inflight = int(extra.get("secret_inflight", 0) or 0)
        # the consolidated TuningConfig (commands.py resolves the full
        # CLI > env > autotune > topology chain once per run); the legacy
        # per-knob extras above stay as explicit overrides for library
        # callers that never touch the flag layer
        self._tuning = extra.get("tuning")
        # fused license gate (shared-arena pass), created by commands.py
        # when --scanners includes both secret and license
        self._lic_gate = extra.get("fused_license")
        # cross-replica dedup warming: a peer's exported hit-store entries
        # to pre-seed the scanner's store with (fleet shard wire)
        self._hit_seed = extra.get("secret_hit_seed")
        self._scanner = None  # built lazily so CPU-only runs never touch jax
        self._stream: _StreamScan | None = None
        self._found: list = []

    def required(self, file_path: str, info) -> bool:
        ok = self._required_inner(file_path, info)
        if not ok and self._lic_gate is not None and self._lic_gate.wants(
            file_path
        ):
            # this file will never ride the device feed, so the fused gate
            # can have no verdict for it — the license analyzer (whose
            # eligibility rules differ: no size floor, no skip-dirs) must
            # classify it itself
            self._lic_gate.skip(file_path)
        return ok

    def _required_inner(self, file_path: str, info) -> bool:
        if info.size < 10:
            return False
        parts = file_path.split("/")
        if any(p in SKIP_DIRS for p in parts[:-1]):
            return False
        name = parts[-1]
        if name in SKIP_FILES:
            return False
        if self.config_path and self._config_rel_path == os.path.normpath(file_path):
            return False
        ext = os.path.splitext(name)[1]
        if ext in SKIP_EXTS:
            return False
        # global allow paths checked with the exact engine's rule set
        if self._exact().allow_path(self._normalize(file_path, dir_="x")):
            return False
        return True

    def _exact(self) -> SecretScanner:
        if self._scanner is None:
            self._scanner = _shared_scanner(
                self._config, self._backend, self._parallel,
                dedup=self._dedup, pack_small=self._pack,
                hit_cache=self._hit_cache,
                host_fallback=self._host_fallback,
                feed_streams=self._feed_streams, inflight=self._inflight,
                tuning=self._tuning,
            )
            if self._hit_seed and hasattr(self._scanner, "seed_hit_entries"):
                n = self._scanner.seed_hit_entries(self._hit_seed)
                logger.info("dedup store warm-seeded with %d entr%s",
                            n, "y" if n == 1 else "ies")
                self._hit_seed = None
        return self._scanner.exact if hasattr(self._scanner, "exact") else self._scanner

    @staticmethod
    def _normalize(file_path: str, dir_: str) -> str:
        # files extracted from image layers get a leading '/' (ref:
        # secret.go:131-137)
        return file_path if dir_ else f"/{file_path}"

    def collect(self, inp: AnalysisInput) -> None:
        head = inp.content[:300]
        binary = utils.is_binary(head)
        ext = os.path.splitext(inp.file_path)[1]
        if binary and ext not in ALLOWED_BINARIES:
            if self._lic_gate is not None and self._lic_gate.wants(
                inp.file_path
            ):
                # binary-sniffed out of the secret feed: the fused gate
                # never sees these bytes
                self._lic_gate.skip(inp.file_path)
            return
        if len(inp.content) > LARGE_FILE_WARN:
            logger.warning(
                "large file in secret scan (%d MB): %s — consider --skip-files",
                len(inp.content) >> 20,
                inp.file_path,
            )
        if binary:
            content = utils.extract_printable_bytes(inp.content)
        else:
            content = inp.content.replace(b"\r", b"")
        path = self._normalize(inp.file_path, inp.dir)
        self._exact()  # ensure scanner exists
        scanner = self._scanner
        if not hasattr(scanner, "scan_files"):
            # plain host engine: scan inline, nothing worth overlapping
            # (no device pass ⇒ the fused gate stays unfed and the license
            # analyzer classifies everything it collected — default-safe)
            s = scanner.scan_bytes(path, content)
            if s.findings:
                self._found.append(s)
            return
        if self._stream is None:
            from trivy_tpu import obs

            # the background consumer re-enters this walk's trace context
            self._stream = _StreamScan(
                scanner, obs.current(), license_gate=self._lic_gate
            )
        # blocks only once STREAM_BUFFER_BYTES of content is waiting on
        # the device pipeline (walk-side backpressure); raises the scan
        # thread's error instead of buffering into a dead pipeline
        try:
            self._stream.put(path, content)
        except Exception as e:
            self._raise_scan_error(e)

    def _raise_scan_error(self, e: Exception) -> None:
        """With ``--no-host-fallback`` the user asked device failures to be
        loud: wrap so the analyzer group's containment layers re-raise
        instead of downgrading the failure to a warning (which would report
        a 'clean' scan with every secret finding silently dropped)."""
        from trivy_tpu.fanal.analyzer import FatalAnalyzerError

        if not self._host_fallback:
            raise FatalAnalyzerError(e) from e
        raise e

    def finalize(self) -> AnalysisResult | None:
        if self._stream is not None:
            stream, self._stream = self._stream, None
            try:
                self._found.extend(stream.finish())
            except Exception as e:
                self._raise_scan_error(e)
        found, self._found = self._found, []
        return AnalysisResult(secrets=found) if found else AnalysisResult()

    def abort(self) -> None:
        if self._stream is not None:
            stream, self._stream = self._stream, None
            stream.abort()
        if self._lic_gate is not None:
            self._lic_gate.degrade()
        self._found = []


register_analyzer(SecretAnalyzer)
