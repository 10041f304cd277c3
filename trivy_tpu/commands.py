"""Command orchestration (ref: pkg/commands/artifact/run.go).

Mode selection (standalone vs client/server × target kind), scanner
construction, scan → filter → report → exit-code — the reference Runner's
responsibilities (ref: run.go:337-400), minus wire DI.
"""

from __future__ import annotations

import sys

from trivy_tpu import log
from trivy_tpu.scanner import ScanOptions, Scanner

logger = log.logger("commands")


def _make_cache(opts):
    from trivy_tpu.cache import new_cache

    backend = opts.get("cache_backend") or "fs"
    kwargs = {}
    if backend.startswith(("redis://", "rediss://")):
        kwargs = {
            "ttl": int(opts.get("cache_ttl") or 0),
            "ca_cert": opts.get("redis_ca") or "",
            "client_cert": opts.get("redis_cert") or "",
            "client_key": opts.get("redis_key") or "",
            "insecure_skip_verify": bool(opts.get("redis_insecure")),
        }
    return new_cache(backend, opts.get("cache_dir"), **kwargs)


def _resolve_tuning(opts):
    """One TuningConfig per run (CLI > env > autotune record > topology
    default), shared by the secret feed, the artifact read-ahead, and the
    online controller — and registered on the scan context so every export
    surface (--metrics-out, --trace-out, Trace responses) carries the
    effective knob set."""
    from trivy_tpu import obs
    from trivy_tpu.tuning import resolve_tuning

    cfg = resolve_tuning(opts={
        "secret_streams": opts.get("secret_streams"),
        "secret_inflight": opts.get("secret_inflight"),
        "secret_arena_slabs": opts.get("secret_arena_slabs"),
        "secret_bucket_rungs": opts.get("secret_bucket_rungs"),
        "parallel": opts.get("parallel"),
        "fleet_inflight": opts.get("fleet_inflight"),
        "secret_dedup_mb": opts.get("secret_dedup_mb"),
        "tuning_file": opts.get("tuning_file"),
        # the store_true default (False) must not shadow the env layer:
        # only an EXPLICIT --tune is a CLI-level decision
        "tuning_controller": opts.get("tune") or None,
        "tuning_interval": opts.get("tuning_interval"),
        "fleet_telemetry_interval": opts.get("fleet_telemetry_interval"),
        "fleet_split_threshold": opts.get("fleet_split_threshold"),
    })
    obs.current().tuning = {"config": cfg.to_dict()}
    return cfg


def _artifact_option(ns, opts):
    from trivy_tpu.artifact.local_fs import ArtifactOption

    backend = opts.get("backend", "auto")
    if backend == "cpu":
        device_backend = "cpu"
    elif backend == "auto":
        device_backend = "auto"
    else:
        device_backend = backend
    disabled = []
    scanners = opts.get("scanners", [])
    from trivy_tpu.fanal.analyzer import AnalyzerType

    if "secret" not in scanners:
        disabled.append(AnalyzerType.SECRET)
    # loose LICENSE/COPYING files classify whenever the license scanner is
    # on; only header/full-content classification is the expensive opt-in
    # behind --license-full (ref: run.go:436-440 disables TypeLicenseFile
    # solely when the scanner is off)
    if "license" not in scanners:
        disabled.append(AnalyzerType.LICENSE_FILE)
        disabled.append(AnalyzerType.LICENSE_HEADER)
    elif not opts.get("license_full"):
        disabled.append(AnalyzerType.LICENSE_HEADER)
    if "misconfig" not in scanners:
        disabled.append(AnalyzerType.CONFIG)
    import os.path

    secret_cfg = opts.get("secret_config")
    if secret_cfg and not os.path.exists(secret_cfg):
        secret_cfg = None
    # fused device pass (README "Fused device pass"): when one scan runs
    # both the secret and license scanners on a device backend, the secret
    # feed's arena rows also carry the license gram gate so each scanned
    # byte crosses the link ONCE for both detectors (--no-shared-arena
    # opts out; backend=cpu has no device feed to share)
    fused_license = None
    if (
        "secret" in scanners
        and "license" in scanners
        and device_backend != "cpu"
        and not opts.get("no_shared_arena")
    ):
        from trivy_tpu.licensing.fused import FusedLicenseGate

        fused_license = FusedLicenseGate(
            license_full=bool(opts.get("license_full"))
        )
    tuning = _resolve_tuning(opts)
    return ArtifactOption(
        skip_files=opts.get("skip_files", []),
        skip_dirs=opts.get("skip_dirs", []),
        disabled_analyzers=disabled,
        secret_config_path=secret_cfg,
        backend=device_backend,
        analyzer_extra={
            # the consolidated knob config (CLI > env > autotune record >
            # topology default): the secret scanner, the fs read-ahead,
            # and the online controller all read this one object
            "tuning": tuning,
            "check_paths": list(opts.get("config_check") or []),
            "misconfig_scanners": list(opts.get("misconfig_scanners") or []),
            "parallel": max(0, int(opts.get("parallel") or 0)),
            "java_db_path": opts.get("java_db"),
            "secret_dedup": not opts.get("no_secret_dedup"),
            "secret_pack": not opts.get("no_secret_pack"),
            "secret_streams": max(0, int(opts.get("secret_streams") or 0)),
            "secret_inflight": max(0, int(opts.get("secret_inflight") or 0)),
            "host_fallback": not opts.get("no_host_fallback"),
            "fused_license": fused_license,
            # own cache handle: the hit-vector store outlives any single
            # artifact's cache usage and redis/fs backends are cheap to dup
            "secret_hit_cache": (
                _make_cache(opts) if opts.get("secret_hit_cache") else None
            ),
        },
        parallel=max(0, int(opts.get("parallel") or 0)),
        insecure_registry=bool(opts.get("insecure")),
        registry_username=opts.get("username", "") or "",
        registry_password=opts.get("password", "") or "",
        platform=opts.get("platform", "") or "",
        docker_host=opts.get("docker_host", "") or "",
        podman_host=opts.get("podman_host", "") or "",
        containerd_host=opts.get("containerd_host", "") or "",
        **(
            {"image_src": list(opts.get("image_src"))}
            if opts.get("image_src")
            else {}  # unset flag -> the ArtifactOption default order
        ),
    )


def _scan_options(opts) -> ScanOptions:
    # SBOM/snapshot formats need the full package inventory (ref:
    # flag/report_flags.go forces ListAllPkgs for sbom formats)
    list_all = (
        bool(opts.get("list_all_pkgs"))
        or bool(opts.get("dependency_tree"))  # the tree needs the inventory
        or opts.get("format") in ("cyclonedx", "spdx", "spdx-json", "github")
    )
    return ScanOptions(
        scanners=opts.get("scanners", ["secret"]),
        license_full=bool(opts.get("license_full")),
        list_all_pkgs=list_all,
        host_fallback=not opts.get("no_host_fallback"),
    )


def _vuln_client(opts):
    """Advisory DB client, when the vuln scanner is enabled and a DB exists."""
    if "vuln" not in opts.get("scanners", []):
        return None
    from trivy_tpu.db import load_default_db

    db = load_default_db(opts.get("db_repository"), opts.get("cache_dir"))
    if db is None:
        logger.warning("vulnerability DB not available; skipping vuln detection")
        return None
    return db


def _setup_span():
    """Span ``commands.setup`` for a scan command's set-up before its
    artifact's ``inspect()`` (cache and DB open, driver, artifact and
    scanner construction), and count the run in ``commands.scans``."""
    from trivy_tpu import obs

    obs.count("commands.scans")
    return obs.span("commands.setup")


class ScanTimeout(BaseException):
    """``--timeout`` expired. A BaseException, like KeyboardInterrupt: the
    alarm lands in whatever the main thread runs, often inside a per-file
    or per-analyzer ``except Exception`` containment, which would log it
    and drop that analyzer's findings from a scan that then exits 0."""


def run(command: str, ns, opts) -> int:
    import signal

    timeout = int(opts.get("timeout") or 0)

    def on_timeout(signum, frame):
        raise ScanTimeout(f"scan exceeded --timeout={timeout}s")

    # long-running commands (server, watch loop) are not one scan — the
    # per-scan --timeout alarm does not apply to them
    if timeout > 0 and command not in ("server", "watch"):
        signal.signal(signal.SIGALRM, on_timeout)
        signal.alarm(timeout)
    from trivy_tpu.result import IgnorePolicy, PolicyError

    from trivy_tpu import obs

    # every run gets its own trace context (contextvar-scoped): back-to-back
    # run() calls in one process and concurrent library scans record into
    # disjoint tables instead of one global one. Span recording turns on
    # for --trace and whenever an export destination is given.
    trace_on = bool(
        opts.get("trace") or opts.get("trace_out")
        or opts.get("metrics_out") or opts.get("profile_out")
    )
    from trivy_tpu.obs import timeseries as obs_timeseries

    # live telemetry: the sampler thread spawns only when something will
    # consume it (a trace/metrics export, --timeseries-out, or --live) AND
    # the interval is nonzero — plain scans stay sampler-free, provably
    # (bench --smoke asserts no sampler thread on untraced reps)
    telemetry_interval = opts.get("telemetry_interval")
    if telemetry_interval is None:
        telemetry_interval = obs_timeseries.default_interval()
    # the server command is excluded: ScanServer.scan runs one sampler per
    # request — a process-lifetime sampler here would keep the shared
    # gauges (and the live-sampler refcount) pinned while the fleet idles
    telemetry_on = (
        trace_on or bool(opts.get("timeseries_out")) or bool(opts.get("live"))
    ) and telemetry_interval > 0 and command != "server"
    from trivy_tpu import faults
    from trivy_tpu.obs import recorder as flight

    # flight-recorder forensics destination (--debug-dir wins over
    # TRIVY_TPU_DEBUG_DIR); without one, auto-emitted bundles stay off
    if opts.get("debug_dir"):
        flight.set_debug_dir(opts["debug_dir"])
    # arm the fault-injection harness for this run (--fault-inject /
    # TRIVY_TPU_FAULT_INJECT); disarmed again in the finally below so
    # library callers running several commands don't leak scripted faults
    if opts.get("fault_inject"):
        try:
            faults.configure(opts["fault_inject"])
        except ValueError as e:
            logger.error("%s", e)
            return 2
        logger.warning(
            "fault injection armed: %s", opts["fault_inject"]
        )
    with obs.scan_context(name=command, enabled=trace_on or None) as ctx:
        sampler = (
            obs_timeseries.start_sampler(ctx, telemetry_interval)
            if telemetry_on
            else None
        )
        live = (
            obs_timeseries.LiveProgress(ctx).start()
            if opts.get("live") and telemetry_on and command != "server"
            else None
        )
        completed = False
        try:
            # validate the ignore policy up front: a broken policy file must
            # not cost the user a full scan before failing
            if opts.get("ignore_policy"):
                IgnorePolicy(opts["ignore_policy"])
            if command in ("fs", "rootfs", "repo"):
                rc = _run_fs_like(command, ns, opts)
            elif command == "watch":
                rc = _run_watch(ns, opts)
            elif command == "image":
                rc = _run_image(ns, opts)
            elif command == "vm":
                rc = _run_vm(ns, opts)
            elif command == "sbom":
                rc = _run_sbom(ns, opts)
            elif command == "convert":
                rc = _run_convert(ns, opts)
            elif command == "debug":
                rc = _run_debug(ns, opts)
            elif command == "server":
                rc = _run_server(ns, opts)
            elif command == "clean":
                rc = _run_clean(ns, opts)
            else:
                raise ValueError(f"unknown command {command}")
            completed = True
            return rc
        except (ScanTimeout, TimeoutError) as e:
            logger.error("%s", e)
            return 1
        except PolicyError as e:
            logger.error("%s", e)
            return 2
        except ModuleNotFoundError as e:
            if (e.name or "").startswith("trivy_tpu"):
                logger.error(
                    "this feature is not implemented yet (missing %s)", e.name
                )
                return 2
            raise
        finally:
            if opts.get("fault_inject"):
                faults.clear()
            if timeout > 0 and command not in ("server", "watch"):
                signal.alarm(0)
            # failure forensics: a scan that died emits its black box; a
            # scan that completed on a degraded path emits one too (the
            # degradation is the story). auto_emit never raises and is a
            # no-op without a debug dir
            if not completed:
                import sys as _sys

                flight.auto_emit(
                    "terminal-failure", ctx=ctx, error=_sys.exc_info()[1]
                )
            elif ctx.health_snapshot().get("scan.degraded"):
                flight.auto_emit("degraded-completion", ctx=ctx)
            # telemetry teardown runs on EVERY exit path (completion, scan
            # death, timeout): stop the sampler (one final tick), then the
            # live line — no leaked threads. Progress is marked finished
            # only on real completion: a scan that died at 40% must export
            # its last honest ratio, not a forced 1.0 (the rpc server's
            # finished table follows the same rule)
            if completed and ctx.progress_peek() is not None:
                ctx.progress().finish()
            if live is not None:
                live.stop()
            if sampler is not None:
                sampler.stop()
            if opts.get("timeseries_out"):
                from trivy_tpu.obs import export

                export.write_timeseries_json(ctx, opts["timeseries_out"])
                logger.info(
                    "telemetry time series written to %s",
                    opts["timeseries_out"],
                )
            if ctx.enabled:
                from trivy_tpu.obs import export

                if opts.get("trace"):
                    ctx.report()
                if opts.get("trace_out"):
                    export.write_chrome_trace(ctx, opts["trace_out"])
                    logger.info("chrome trace written to %s", opts["trace_out"])
                if opts.get("metrics_out"):
                    export.write_metrics_json(ctx, opts["metrics_out"])
                    logger.info("metrics written to %s", opts["metrics_out"])
                if opts.get("profile_out"):
                    export.write_profile_json(ctx, opts["profile_out"])
                    logger.info(
                        "scan profile written to %s", opts["profile_out"]
                    )


def _emit(report, ns, opts) -> int:
    from trivy_tpu import obs

    with obs.span("report.write"):
        return _write_report(report, ns, opts)


def _write_report(report, ns, opts) -> int:
    """Filter ``report`` and render it (or its compliance view) to
    ``--output`` or stdout; returns the exit code."""
    from trivy_tpu import report as report_pkg
    from trivy_tpu.result import FilterOptions, filter_report

    filter_report(
        report,
        FilterOptions(
            severities=opts.get("severity") or [],
            ignore_file=opts.get("ignorefile"),
            vex_sources=opts.get("vex") or [],
            policy_file=opts.get("ignore_policy"),
            show_suppressed=bool(opts.get("show_suppressed")),
            cache_dir=opts.get("cache_dir") or "",
        ),
    )
    compliance = opts.get("compliance")
    if compliance:
        from trivy_tpu.compliance import apply_spec, load_spec, write_report

        fmt = opts.get("format", "table")
        if fmt not in ("table", "json"):
            logger.error(
                "--compliance supports only table and json output, not %s", fmt
            )
            return 2
        try:
            spec = load_spec(compliance)
        except (ValueError, OSError) as e:
            logger.error("%s", e)
            return 2
        creport = apply_spec(spec, report)
        output = opts.get("output")
        if output:
            with open(output, "w") as f:
                write_report(creport, f, fmt)
        else:
            write_report(creport, sys.stdout, fmt)
        exit_code = opts.get("exit_code", 0)
        if exit_code and any(r.status == "FAIL" for r in creport.results):
            return exit_code
        return 0
    output = opts.get("output")
    kw = {}
    if opts.get("template"):
        kw["template"] = opts["template"]
    if opts.get("show_suppressed"):
        kw["show_suppressed"] = True
    if opts.get("dependency_tree"):
        kw["dependency_tree"] = True
    if output:
        with open(output, "w") as f:
            report_pkg.write(report, opts.get("format", "table"), f, **kw)
    else:
        report_pkg.write(report, opts.get("format", "table"), sys.stdout, **kw)
    exit_code = opts.get("exit_code", 0)
    if exit_code and any(not r.is_empty for r in report.results):
        return exit_code
    return 0


def _incremental_options(opts):
    """IncrementalOptions when any incremental flag is set, else None —
    incremental-off scans must allocate NOTHING (no manifest I/O, no unit
    planner, not even the module import; bench --smoke asserts this)."""
    if not (
        opts.get("incremental") or opts.get("diff_base")
        or opts.get("since_last")
    ):
        return None
    from trivy_tpu.incremental import IncrementalOptions

    return IncrementalOptions.from_opts(opts)


def _run_fs_like(command: str, ns, opts) -> int:
    from trivy_tpu.artifact.local_fs import LocalFSArtifact

    target = ns.target
    art_opt = _artifact_option(ns, opts)

    if command == "repo" and (
        target.startswith(("http://", "https://", "git://", "file://", "ssh://"))
        or target.endswith(".git")
    ):
        from trivy_tpu.artifact.repo import RepoError, checkout_repo

        try:
            target = checkout_repo(
                target,
                branch=getattr(ns, "branch", None),
                tag=getattr(ns, "tag", None),
                commit=getattr(ns, "commit", None),
            )
        except RepoError as e:
            logger.error("%s", e)
            return 1

    server = opts.get("server")
    incr = _incremental_options(opts)
    if opts.get("fleet"):
        # fleet mode: the artifact splits into shards that fan out across
        # the replica set; blobs merge back through the standard local
        # driver (README "Distributed scanning")
        if server:
            logger.error("--fleet and --server are mutually exclusive")
            return 2
        if incr is not None:
            logger.error(
                "--incremental/--diff-base/--since-last do not compose "
                "with --fleet yet (replicas already skip cached layers; "
                "use the shared cache backend for cross-scan reuse)"
            )
            return 2
        return _run_fleet("fs", target, ns, opts, art_opt)
    if server and incr is not None:
        # client-mode analysis ships blobs to the server's cache; the
        # unit-level diff needs a readable local cache — refuse loudly
        # instead of silently full-scanning
        logger.error(
            "--incremental/--diff-base/--since-last require a local "
            "scan path (drop --server or run the scan on the server)"
        )
        return 2
    with _setup_span():
        if server:
            # client mode: analysis is local, blobs ship to the SERVER's
            # cache and detection runs there (ref: run.go:348-355 split)
            from trivy_tpu.rpc.client import RemoteCache, RemoteDriver

            cache = RemoteCache(server, token=opts.get("token") or "")
            driver = RemoteDriver(server, token=opts.get("token") or "")
        else:
            from trivy_tpu.scanner.local_driver import LocalDriver

            cache = _make_cache(opts)
            driver = LocalDriver(cache, vuln_client=_vuln_client(opts))
        if incr is not None:
            from trivy_tpu.incremental.fs import IncrementalFSArtifact

            artifact = IncrementalFSArtifact(target, cache, art_opt, incr)
        else:
            artifact = LocalFSArtifact(target, cache, art_opt)
        scanner = Scanner(artifact, driver)
    if incr is not None:
        from trivy_tpu.incremental.manifest import GitDiffError

        try:
            report = scanner.scan_artifact(_scan_options(opts))
        except GitDiffError as e:
            # typoed --diff-base ref / not a git worktree: a clean error,
            # not a traceback — and never a silent full scan
            logger.error("--diff-base %s: %s", incr.diff_base, e)
            return 1
        return _emit(report, ns, opts)
    report = scanner.scan_artifact(_scan_options(opts))
    return _emit(report, ns, opts)


def _run_watch(ns, opts) -> int:
    """``trivy-tpu watch <path>``: scan, then re-scan on an interval —
    each iteration is a ``--since-last`` incremental scan, so an unchanged
    tree costs a stat-walk and a report is emitted only when something
    actually changed (the unit diff is the change detector)."""
    import time as time_mod

    from trivy_tpu.incremental import IncrementalOptions
    from trivy_tpu.incremental.fs import IncrementalFSArtifact
    from trivy_tpu.scanner.local_driver import LocalDriver

    interval = float(getattr(ns, "watch_interval", 0) or 2.0)
    max_scans = int(getattr(ns, "watch_count", 0) or 0)  # 0 = forever
    art_opt = _artifact_option(ns, opts)
    with _setup_span():
        cache = _make_cache(opts)
        driver = LocalDriver(cache, vuln_client=_vuln_client(opts))
    incr = IncrementalOptions(enabled=True, since_last=True)
    rc = 0
    n = 0
    prev_keys: tuple = ()
    try:
        while True:
            n += 1
            artifact = IncrementalFSArtifact(ns.target, cache, art_opt, incr)
            report = Scanner(artifact, driver).scan_artifact(
                _scan_options(opts)
            )
            # the unit diff is the change detector: edits/new files dirty
            # a unit; deletions change the unit-key set, which the NEXT
            # scan's artifact id reflects — compare it across iterations
            changed = artifact.last_stats.get("units_analyzed", 0) > 0
            key_set = tuple(sorted(artifact.last_stats.get("unit_keys", ())))
            if n == 1 or changed or key_set != prev_keys:
                rc = _emit(report, ns, opts)
                logger.info(
                    "watch scan #%d: %d/%d unit(s) re-analyzed", n,
                    artifact.last_stats.get("units_analyzed", 0),
                    artifact.last_stats.get("units_total", 0),
                )
            else:
                logger.info("watch scan #%d: no changes", n)
            prev_keys = key_set
            if max_scans and n >= max_scans:
                return rc
            time_mod.sleep(interval)
    except KeyboardInterrupt:
        logger.info("watch stopped after %d scan(s)", n)
        return rc


def _run_fleet(kind: str, target: str, ns, opts, art_opt) -> int:
    """Scatter-gather scan across a ``--fleet`` replica set: shard plan →
    async fan-out with work-stealing/speculation/breakers → blobs merged
    into the local cache → the ordinary LocalDriver detection + report
    path (findings byte-identical to a single-host scan)."""
    from trivy_tpu.fleet import FleetError
    from trivy_tpu.fleet.coordinator import FleetConfig
    from trivy_tpu.fleet.merge import FleetArtifact
    from trivy_tpu.scanner.local_driver import LocalDriver

    tuning = (art_opt.analyzer_extra or {}).get("tuning")
    try:
        fleet_cfg = FleetConfig.from_opts(opts, tuning=tuning)
    except ValueError as e:
        logger.error("%s", e)
        return 2
    cache = _make_cache(opts)
    if opts.get("secret_hit_cache"):
        # cross-replica dedup warming: export the coordinator's persisted
        # hit-store namespaces (no scanner build, no jax) and ship them on
        # each replica's first shard — a fresh replica joins re-scans warm
        from trivy_tpu.secret.hitstore import export_backend_warm

        try:
            fleet_cfg.warm_seed = export_backend_warm(cache)
        except Exception as e:
            logger.warning("dedup warm export skipped: %s", e)
        if fleet_cfg.warm_seed:
            logger.info(
                "fleet dedup warming: %d entr%s exported for replica "
                "pre-seeding", len(fleet_cfg.warm_seed),
                "y" if len(fleet_cfg.warm_seed) == 1 else "ies",
            )
    with _setup_span():
        artifact = FleetArtifact(
            kind, target, cache, art_opt, fleet_cfg, _scan_options(opts)
        )
        driver = LocalDriver(cache, vuln_client=_vuln_client(opts))
        scanner = Scanner(artifact, driver)
    try:
        report = scanner.scan_artifact(_scan_options(opts))
    except FleetError as e:
        logger.error("fleet scan failed: %s", e)
        return 1
    return _emit(report, ns, opts)


def _run_image(ns, opts) -> int:
    from trivy_tpu.artifact.image import ImageArchiveArtifact, new_image_artifact
    from trivy_tpu.scanner.local_driver import LocalDriver

    target = getattr(ns, "input", None) or ns.target
    if not target:
        logger.error("specify an image archive path (positional or --input)")
        return 1
    if opts.get("fleet"):
        if opts.get("server"):
            logger.error("--fleet and --server are mutually exclusive")
            return 2
        return _run_fleet("image", target, ns, opts,
                          _artifact_option(ns, opts))
    cache = _make_cache(opts)
    art_opt = _artifact_option(ns, opts)
    artifact = new_image_artifact(target, cache, art_opt)
    diff_base = opts.get("diff_base")
    if diff_base:
        # diff-scan for images: seed the cache with the base image's
        # layers under the derived plan's keys so inspect()'s
        # MissingBlobs diff analyzes only layers absent from the base
        from trivy_tpu.artifact.image import preseed_from_base

        try:
            preseed_from_base(artifact, diff_base, cache, art_opt)
        except Exception as e:
            # unreadable archive, daemon/registry resolution failure
            # (DaemonError), bad layout — the user asked for a diff scan
            # against this base, so fail loud, never silently full-scan
            logger.error("--diff-base %s: %s", diff_base, e)
            return 1
    with _setup_span():
        driver = LocalDriver(cache, vuln_client=_vuln_client(opts))
        scanner = Scanner(artifact, driver)
    report = scanner.scan_artifact(_scan_options(opts))
    return _emit(report, ns, opts)


def _run_vm(ns, opts) -> int:
    from trivy_tpu.artifact.vm import VMImageArtifact
    from trivy_tpu.scanner.local_driver import LocalDriver

    with _setup_span():
        cache = _make_cache(opts)
        artifact = VMImageArtifact(
            ns.target, cache, _artifact_option(ns, opts)
        )
        driver = LocalDriver(cache, vuln_client=_vuln_client(opts))
        scanner = Scanner(artifact, driver)
    report = scanner.scan_artifact(_scan_options(opts))
    return _emit(report, ns, opts)


def _run_sbom(ns, opts) -> int:
    from trivy_tpu.artifact.sbom import SBOMArtifact
    from trivy_tpu.scanner.local_driver import LocalDriver

    opts = dict(opts)
    opts.setdefault("scanners", ["vuln"])
    if "vuln" not in opts["scanners"]:
        opts["scanners"] = ["vuln"]
    with _setup_span():
        cache = _make_cache(opts)
        artifact = SBOMArtifact(ns.target, cache)
        driver = LocalDriver(cache, vuln_client=_vuln_client(opts))
        scanner = Scanner(artifact, driver)
    report = scanner.scan_artifact(_scan_options(opts))
    return _emit(report, ns, opts)


def _run_debug(ns, opts) -> int:
    """``trivy-tpu debug <bundle>``: render a flight-recorder diagnostic
    bundle (auto-emitted under ``--debug-dir``, or pulled from a replica
    via ``GET /debug/bundle``) as the machine verdict plus a relative
    event timeline and the device-lane/stall summaries."""
    import datetime

    from trivy_tpu.obs import recorder as flight

    try:
        doc = flight.read_bundle(ns.target)
    except (OSError, ValueError) as e:
        logger.error("cannot read bundle %s: %s", ns.target, e)
        return 1
    out = sys.stdout
    w = out.write
    w(f"bundle:  {ns.target}\n")
    w(f"schema:  {doc.get('schema', '?')}\n")
    w(f"reason:  {doc.get('reason', '?')}\n")
    w(f"created: {doc.get('created', '?')}\n")
    w(f"scan:    {doc.get('name', '?')} "
      f"(trace {str(doc.get('trace_id', ''))[:8]})\n")
    if doc.get("error"):
        w(f"error:   {doc['error']}\n")
    w("\nverdict\n  " + str(doc.get("verdict", "(none)")) + "\n")
    events = doc.get("events") or doc.get("process_events") or []
    if events:
        w(f"\ntimeline ({len(events)} event(s))\n")
        t0 = events[0].get("t", 0.0)
        for ev in events:
            ts = datetime.datetime.fromtimestamp(
                ev.get("t", 0.0), datetime.timezone.utc
            ).strftime("%H:%M:%S")
            line = (f"  +{ev.get('t', 0.0) - t0:8.2f}s {ts} "
                    f"{ev.get('kind', '?'):8s} {ev.get('what', '')}")
            detail = ev.get("detail")
            if detail:
                line += "  " + " ".join(
                    f"{k}={v}" for k, v in detail.items()
                )
            w(line + "\n")
    dev = doc.get("device")
    if dev:
        w("\ndevice lane\n")
        w(f"  compiles: {dev.get('compile_total', 0)} "
          f"({dev.get('compile_wall_s', 0.0)}s wall) across "
          f"{len(dev.get('compiles', {}))} kernel(s)\n")
        for kern, row in sorted((dev.get("compiles") or {}).items()):
            w(f"    {kern}: {row.get('count', 0)} compile(s), "
              f"{row.get('wall_s', 0.0)}s\n")
        storms = dev.get("recompile_storms") or []
        if storms:
            w(f"  RECOMPILE STORMS: {', '.join(storms)} "
              f"(threshold {dev.get('storm_threshold')})\n")
        hbm = dev.get("hbm") or {}
        if hbm:
            w(f"  hbm: {hbm}\n")
    stall = doc.get("stall")
    if stall:
        w(f"\nstall attribution\n  {stall}\n")
    replicas = doc.get("replica_bundles")
    if replicas:
        w(f"\nreplica bundles ({len(replicas)})\n")
        for host, sub in sorted(replicas.items()):
            if "error" in sub and "verdict" not in sub:
                w(f"  {host}: pull failed: {sub['error']}\n")
            else:
                w(f"  {host}: {sub.get('verdict', '(no verdict)')}\n")
    return 0


def _run_convert(ns, opts) -> int:
    import json

    from trivy_tpu.types import Report

    with open(ns.target) as f:
        report = Report.from_dict(json.load(f))
    return _emit(report, ns, opts)


def _run_server(ns, opts) -> int:
    from trivy_tpu.rpc.admission import resolve_admission
    from trivy_tpu.rpc.server import serve

    host, _, port = ns.listen.rpartition(":")
    # resolve the admission knob set at boot (CLI > env > derived budget):
    # a garbage quota/tenant spec kills startup with a clear error here,
    # never the Nth request with a 500
    try:
        admission = resolve_admission(opts)
    except ValueError as e:
        logger.error("%s", e)
        return 1
    serve(
        host or "0.0.0.0",
        int(port),
        cache_dir=opts.get("cache_dir"),
        token=getattr(ns, "token", "") or "",
        token_header=getattr(ns, "token_header", None) or "Trivy-Token",
        db_repository=opts.get("db_repository"),
        admission=admission,
    )
    return 0


def _run_clean(ns, opts) -> int:
    """Selective cleanup (ref: pkg/commands/clean/run.go — requires an
    explicit selector)."""
    import shutil

    clean_all = getattr(ns, "clean_all", False)
    scan_cache = getattr(ns, "scan_cache", False) or clean_all
    vuln_db = getattr(ns, "vuln_db", False) or clean_all
    if not (scan_cache or vuln_db):
        logger.error("specify what to clean: --scan-cache, --vuln-db or --all")
        return 1
    from trivy_tpu.cache.fs import default_cache_dir

    base = opts.get("cache_dir") or default_cache_dir()
    if scan_cache:
        from trivy_tpu.cache import new_cache

        new_cache("fs", opts.get("cache_dir")).clear()
        logger.info("scan cache cleared")
    if vuln_db:
        import os.path

        target = os.path.join(base, "db")
        if os.path.isdir(target):
            shutil.rmtree(target)
            logger.info("%s removed", target)
        else:
            logger.info("%s not present", target)
    return 0
