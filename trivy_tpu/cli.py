"""CLI entry point (ref: pkg/commands/app.go cobra tree).

Command tree: fs / rootfs / repo / image / sbom / convert / server / clean /
version, sharing flag groups the way the reference composes FlagGroups per
command (ref: app.go:247+ per-target constructors).

Run as ``python -m trivy_tpu.cli <command> ...``.
"""

from __future__ import annotations

import argparse
import sys

from trivy_tpu import log
from trivy_tpu.flag import Flag, FlagGroup, load_config_file, resolve_all

VERSION = "0.1.0"


def _interval_validator(v):
    # reject negative/NaN/inf cadences at flag-resolution time (tuning.py
    # owns the rule; the Flag layer prefixes the flag name on failure)
    from trivy_tpu.tuning import validate_interval

    return validate_interval(v, "interval")


def _count_validator(v):
    # non-negative integer knobs (admission budgets, quotas); the rule
    # lives in rpc/admission.py so env-only resolution validates the same
    from trivy_tpu.rpc.admission import validate_count

    return validate_count(v, "value")


def _seconds_validator(v):
    from trivy_tpu.rpc.admission import validate_seconds

    return validate_seconds(v, "value")

SCANNERS = ["vuln", "misconfig", "secret", "license"]
FORMATS = ["table", "json", "sarif", "cyclonedx", "spdx", "spdx-json", "github", "template", "cosign-vuln"]


def global_flags() -> FlagGroup:
    return FlagGroup(
        "global",
        [
            Flag("debug", default=False, value_type=bool, help="debug logging",
                 config_name="debug", short="d"),
            Flag("quiet", default=False, value_type=bool, help="errors only",
                 config_name="quiet", short="q"),
            Flag("cache-dir", default=None, help="cache directory",
                 config_name="cache.dir"),
            Flag("cache-backend", default=None, config_name="cache.backend",
                 help="scan cache backend: fs, memory, redis://host:port"),
            Flag("cache-ttl", default=None, config_name="cache.ttl",
                 help="redis cache TTL in seconds"),
            Flag("redis-ca", default=None, config_name="cache.redis.ca",
                 help="redis TLS CA certificate path"),
            Flag("redis-cert", default=None, config_name="cache.redis.cert",
                 help="redis TLS client certificate path"),
            Flag("redis-key", default=None, config_name="cache.redis.key",
                 help="redis TLS client key path"),
            Flag("redis-insecure", default=False, value_type=bool,
                 config_name="cache.redis.insecure",
                 help="skip redis TLS certificate verification (rediss:// "
                      "verifies against system roots by default)"),
            Flag("config", default=None, help="config file path", short="c"),
            Flag("timeout", default=300, value_type=int, config_name="timeout",
                 help="scan timeout seconds (ref default 5m)"),
            Flag("trace", default=False, value_type=bool, config_name="trace",
                 help="print per-stage timing spans, histograms, and the "
                      "stall-attribution verdict after the scan"),
            Flag("trace-out", default=None, config_name="trace.out",
                 help="write spans as Chrome trace-event JSON (Perfetto-"
                      "loadable; implies span recording; client mode merges "
                      "the server's tracks; .gz path gzips)"),
            Flag("metrics-out", default=None, config_name="trace.metrics-out",
                 help="write aggregate span/counter metrics as JSON "
                      "(implies span recording; .gz path gzips)"),
            Flag("profile-out", default=None, config_name="trace.profile-out",
                 help="write the per-rule / per-bucket cost profile (gate "
                      "hits, confirm time, false-positive rate, dispatch-"
                      "bucket timing) as JSON (implies span recording; "
                      ".gz path gzips)"),
            Flag("telemetry-interval", default=None, value_type=float,
                 config_name="telemetry.interval",
                 validator=_interval_validator,
                 help="live-telemetry sampling interval in seconds "
                      "(default 0.25; 0 disables the sampler entirely)"),
            Flag("timeseries-out", default=None,
                 config_name="telemetry.timeseries-out",
                 help="write the scan's live-telemetry time series (link "
                      "MB/s, arena occupancy, queue depths, device busy, "
                      "progress) as JSON (implies the sampler; .gz gzips)"),
            Flag("live", default=False, value_type=bool,
                 config_name="telemetry.live",
                 help="print a live progress line (progress %, MB/s, ETA, "
                      "device busy, arena occupancy) to stderr during the "
                      "scan"),
            Flag("log-format", default="plain", choices=["plain", "json"],
                 config_name="log.format",
                 help="log line format: plain, or one JSON object per line"),
            Flag("fault-inject", default=None, config_name="fault-inject",
                 help="arm the deterministic fault-injection harness, e.g. "
                      "'device.dispatch@d3:times=-1,cache.redis.get:at=2' "
                      "(see trivy_tpu/faults.py for the grammar)"),
            Flag("debug-dir", default=None, config_name="debug.dir",
                 help="directory for auto-emitted flight-recorder "
                      "diagnostic bundles (terminal failure, degraded "
                      "completion, breaker trip, dead replica); bounded "
                      "retention (TRIVY_TPU_DEBUG_KEEP, default 8); env "
                      "TRIVY_TPU_DEBUG_DIR; render with "
                      "`trivy-tpu debug <bundle>`"),
        ],
    )


def scan_flags() -> FlagGroup:
    return FlagGroup(
        "scan",
        [
            Flag("scanners", default=["secret"], is_list=True, choices=SCANNERS,
                 config_name="scan.scanners", help="comma-separated scanners"),
            Flag("skip-dirs", default=[], is_list=True, config_name="scan.skip-dirs",
                 help="directories to skip"),
            Flag("skip-files", default=[], is_list=True, config_name="scan.skip-files",
                 help="files to skip"),
            Flag("backend", default="auto", choices=["auto", "pallas", "xla", "cpu"],
                 config_name="scan.backend",
                 help="device backend for batched engines"),
            Flag("parallel", default=0, value_type=int, config_name="scan.parallel",
                 help="host worker count (0 = auto)"),
            Flag("no-host-fallback", default=False, value_type=bool,
                 config_name="scan.no-host-fallback",
                 help="fail the scan on unrecoverable device errors instead "
                      "of degrading to the exact host engine"),
        ],
    )


def report_flags() -> FlagGroup:
    return FlagGroup(
        "report",
        [
            Flag("format", default="table", choices=FORMATS, short="f",
                 config_name="format", help="output format"),
            Flag("output", default=None, short="o", config_name="output",
                 help="output file (default stdout)"),
            Flag("severity", default=[], is_list=True,
                 choices=["UNKNOWN", "LOW", "MEDIUM", "HIGH", "CRITICAL"],
                 config_name="severity", help="severities to include"),
            Flag("exit-code", default=0, value_type=int, config_name="exit-code",
                 help="exit code when findings exist"),
            Flag("ignorefile", default=".trivyignore", config_name="ignorefile",
                 help="ignore file path"),
            Flag("ignore-policy", default=None, config_name="ignore-policy",
                 help="suppress findings with a Python predicate file "
                      "(ignore_vulnerability/ignore_secret/... or ignore())"),
            Flag("vex", default=[], is_list=True, config_name="vex",
                 help="VEX document paths (OpenVEX / CycloneDX VEX / CSAF)"),
            Flag("show-suppressed", default=False, value_type=bool,
                 config_name="show-suppressed",
                 help="list VEX/policy-suppressed findings in table output"),
            Flag("template", default=None, short="t", config_name="template",
                 help="go-template style output template (for --format template)"),
            Flag("list-all-pkgs", default=False, value_type=bool,
                 config_name="list-all-pkgs", help="include all packages in report"),
            Flag("dependency-tree", default=False, value_type=bool,
                 config_name="dependency-tree",
                 help="show the reversed dependency origin tree for "
                      "vulnerable packages (table format)"),
            Flag("compliance", default=None, config_name="compliance",
                 help="render a compliance report (docker-cis-1.6.0, "
                      "k8s-nsa-1.0, or @spec.yaml)"),
        ],
    )


def secret_flags() -> FlagGroup:
    return FlagGroup(
        "secret",
        [
            Flag("secret-config", default="trivy-secret.yaml",
                 config_name="secret.config", help="secret rules config file"),
            Flag("no-secret-dedup", default=False, value_type=bool,
                 config_name="secret.no-dedup",
                 help="disable the chunk-dedup hit cache on the device feed"),
            Flag("no-secret-pack", default=False, value_type=bool,
                 config_name="secret.no-pack",
                 help="disable small-file row packing on the device feed"),
            Flag("secret-hit-cache", default=False, value_type=bool,
                 config_name="secret.hit-cache",
                 help="persist chunk hit vectors in the scan cache backend "
                      "(fs/redis) for cross-scan dedup"),
            Flag("secret-streams", default=0, value_type=int,
                 config_name="secret.streams",
                 help="transfer streams feeding the device (0 = auto: one "
                      "per device, several on a single accelerator)"),
            Flag("secret-inflight", default=0, value_type=int,
                 config_name="secret.inflight",
                 help="batches in flight per transfer stream "
                      "(0 = auto: 2, double-buffered)"),
            Flag("no-shared-arena", default=False, value_type=bool,
                 config_name="secret.no-shared-arena",
                 help="disable the fused secret+license device pass "
                      "(license gram rows then upload separately)"),
            Flag("secret-arena-slabs", default=0, value_type=int,
                 config_name="secret.arena-slabs",
                 help="chunk-arena slab count for the device feed "
                      "(0 = derived from streams x in-flight windows)"),
            Flag("secret-bucket-rungs", default=0, value_type=int,
                 config_name="secret.bucket-rungs",
                 help="dispatch bucket-ladder depth (0 = default 3: "
                      "B, B/2, B/4; each rung costs one kernel compile)"),
            Flag("secret-dedup-mb", default=0, value_type=int,
                 config_name="secret.dedup-mb",
                 help="byte budget (MB) for the in-process dedup hit-store "
                      "LRU (0 = default 32; env TRIVY_TPU_DEDUP_STORE_MB; "
                      "the bound is bytes, not entries, so streaming scans "
                      "keep flat RSS)"),
        ],
    )


def incremental_flags() -> FlagGroup:
    """Incremental scanning (README "Incremental scanning"): unit-level
    content-addressed re-scans, git diff-scan, and stat-walk repeats."""
    return FlagGroup(
        "incremental",
        [
            Flag("incremental", default=False, value_type=bool,
                 config_name="incremental.enabled",
                 help="unit-level incremental scan: directory-atomic units "
                      "are cached by content + analysis fingerprint and "
                      "unchanged units merge out of the cache (findings "
                      "byte-identical to a full scan)"),
            Flag("diff-base", default=None, config_name="incremental.diff-base",
                 help="scan only what changed since this base: a git "
                      "commit-ish (fs/repo targets; unchanged files keyed "
                      "from the manifest recorded at that commit) or a "
                      "base image ref/archive (image targets; layers "
                      "present in the base are seeded from it, only new "
                      "layers are analyzed)"),
            Flag("since-last", default=False, value_type=bool,
                 config_name="incremental.since-last",
                 help="stat-walk repeat scan: files whose (size, mtime) "
                      "match the last scan's manifest are not even read — "
                      "an unchanged tree re-scans as a near-no-op"),
        ],
    )


def tuning_flags() -> FlagGroup:
    """The telemetry→tuning loop (README "Autotuning"): offline records
    and the online mid-scan controller."""
    return FlagGroup(
        "tuning",
        [
            Flag("tuning-file", default=None, config_name="tuning.file",
                 help="AUTOTUNE.json with per-topology swept optima "
                      "(written by `bench.py --autotune`; default: "
                      "./AUTOTUNE.json when present). Unset knobs resolve "
                      "from the record for this topology fingerprint"),
            Flag("tune", default=False, value_type=bool,
                 config_name="tuning.controller",
                 help="enable the online tuning controller: adapt stream "
                      "count / in-flight windows / arena sizing mid-scan "
                      "from live gauge feedback (every decision is logged "
                      "and exported — see README 'Autotuning')"),
            Flag("tuning-interval", default=None, value_type=float,
                 config_name="tuning.interval",
                 validator=_interval_validator,
                 help="online-controller decision cadence in seconds "
                      "(default 0.5; 0 disables the controller)"),
        ],
    )


def misconf_flags() -> FlagGroup:
    return FlagGroup(
        "misconfiguration",
        [
            Flag("config-check", default=[], is_list=True,
                 config_name="misconfiguration.config-check",
                 help="paths to custom check files/dirs (Python check API)"),
            Flag("misconfig-scanners", default=[], is_list=True,
                 config_name="misconfiguration.scanners",
                 choices=["dockerfile", "terraform", "cloudformation",
                          "kubernetes", "helm", "azure-arm", "yaml", "json"],
                 help="limit misconfig file types (e.g. terraform,dockerfile)"),
        ],
    )


def license_flags() -> FlagGroup:
    return FlagGroup(
        "license",
        [
            Flag("license-full", default=False, value_type=bool,
                 config_name="license.full",
                 help="also classify licenses in loose files/headers"),
        ],
    )


def db_flags() -> FlagGroup:
    return FlagGroup(
        "db",
        [
            Flag("skip-db-update", default=False, value_type=bool,
                 config_name="db.skip-update", help="do not refresh the vuln DB"),
            Flag("db-repository", default=None, config_name="db.repository",
                 help="advisory DB location (dir or archive)"),
            Flag("java-db", default=None, config_name="db.java-repository",
                 help="java DB directory (jar sha1 -> maven coordinates)"),
            Flag("offline-scan", default=False, value_type=bool,
                 config_name="offline-scan", help="no network access"),
        ],
    )


def image_flags() -> FlagGroup:
    return FlagGroup(
        "image",
        [
            Flag("insecure", default=False, value_type=bool,
                 config_name="image.insecure",
                 help="allow plain-HTTP / self-signed registries"),
            Flag("username", default=None, config_name="image.username",
                 help="registry basic-auth username"),
            Flag("password", default=None, config_name="image.password",
                 help="registry basic-auth password"),
            Flag("platform", default=None, config_name="image.platform",
                 help="platform for multi-arch images (os/arch)"),
            Flag("image-src", default=None, is_list=True,
                 config_name="image.source",
                 help="image source resolution order "
                      "(docker,containerd,podman,remote)"),
            Flag("docker-host", default=None, config_name="image.docker.host",
                 help="docker daemon socket/host (unix path or tcp:// URL)"),
            Flag("podman-host", default=None, config_name="image.podman.host",
                 help="podman service socket"),
            Flag("containerd-host", default=None,
                 config_name="image.containerd.host",
                 help="containerd socket path"),
        ],
    )


def admission_flags() -> FlagGroup:
    """Overload-safe multi-tenant serving (README "Multi-tenant serving"):
    the admission queue, per-tenant quotas, and the async job API. Every
    knob is validated at flag resolution — garbage values (including the
    TRIVY_TPU_* env spellings) kill server startup, not the Nth request."""
    return FlagGroup(
        "admission",
        [
            Flag("max-concurrent-scans", default=0, value_type=int,
                 config_name="admission.max-concurrent-scans",
                 validator=_count_validator,
                 help="concurrent-scan budget; > 0 enables admission "
                      "control + the async job API (0 = off, today's "
                      "unbounded behavior)"),
            Flag("admission-queue-depth", default=None, value_type=int,
                 config_name="admission.queue-depth",
                 validator=_count_validator,
                 help="max queued jobs before submits shed with 503 "
                      "(default 64)"),
            Flag("admission-queued-mb", default=None, value_type=int,
                 config_name="admission.queued-mb",
                 validator=_count_validator,
                 help="queued-bytes budget in MB (default: "
                      "TRIVY_TPU_HBM_BUDGET_MB, 1024, x device count — "
                      "queue no more than one device-budget's worth; the "
                      "arena-slab HBM proxy sizes the concurrent-scan "
                      "budget, not this one)"),
            Flag("tenants", default=None, is_list=True,
                 config_name="admission.tenants",
                 help="tenant map, comma-separated "
                      "name:token[:weight[:max_inflight[:queued_mb]]] "
                      "entries; tokens authenticate like --token and key "
                      "per-tenant quotas + weighted fair dequeue "
                      "(per-tenant quota fields override the config-wide "
                      "--tenant-max-inflight/--tenant-queued-mb)"),
            Flag("tenant-max-inflight", default=None, value_type=int,
                 config_name="admission.tenant-max-inflight",
                 validator=_count_validator,
                 help="per-tenant concurrent-scan quota (default: the "
                      "full concurrency budget — fairness comes from the "
                      "weighted dequeue, quotas only cap abuse)"),
            Flag("tenant-queued-mb", default=None, value_type=int,
                 config_name="admission.tenant-queued-mb",
                 validator=_count_validator,
                 help="per-tenant queued-bytes quota in MB (default: the "
                      "global queued-bytes budget)"),
            Flag("job-retention", default=None, value_type=int,
                 config_name="admission.job-retention",
                 validator=_count_validator,
                 help="finished async jobs kept for result polling "
                      "(default 64; oldest evicted first)"),
            Flag("job-deadline", default=None, value_type=float,
                 config_name="admission.job-deadline",
                 validator=_seconds_validator,
                 help="default queue deadline in seconds for jobs that "
                      "supply none (0 = queued jobs never expire); a "
                      "client DeadlineSeconds always wins"),
        ],
    )


def server_client_flags() -> FlagGroup:
    return FlagGroup(
        "client/server",
        [
            Flag("server", default=None, config_name="server.addr",
                 help="server address for client mode (http://host:port)"),
            Flag("token", default=None, config_name="server.token",
                 help="server auth token"),
        ],
    )


def fleet_flags() -> FlagGroup:
    """Distributed scan fabric (README "Distributed scanning"): scatter
    one giant artifact across server replicas and merge the results."""
    return FlagGroup(
        "fleet",
        [
            Flag("fleet", default=None, is_list=True,
                 config_name="fleet.replicas",
                 help="comma-separated replica addresses (host:port) for a "
                      "scatter-gather distributed scan: the artifact splits "
                      "at natural boundaries (image layers, byte-balanced "
                      "walk partitions) and shards fan out as async jobs, "
                      "with work-stealing, speculative re-dispatch, and "
                      "per-replica circuit breakers"),
            Flag("fleet-inflight", default=0, value_type=int,
                 config_name="fleet.inflight",
                 help="async shard jobs in flight per replica (0 = auto: "
                      "2; resolves through TuningConfig like every other "
                      "perf knob — env TRIVY_TPU_FLEET_INFLIGHT)"),
            Flag("fleet-shards-per-replica", default=0, value_type=int,
                 config_name="fleet.shards-per-replica",
                 help="fs-tree overpartition factor: target shard count is "
                      "replicas x this (0 = auto: 4); more shards = finer "
                      "steal grain, more per-shard RPC overhead"),
            Flag("fleet-speculate", default=None, value_type=float,
                 config_name="fleet.speculate",
                 help="straggler multiplier: an in-flight shard running "
                      "past this x the median shard wall time is "
                      "speculatively re-dispatched to an idle replica, "
                      "first result wins (default 2.0; 0 disables)"),
            Flag("fleet-telemetry-interval", default=None, value_type=float,
                 config_name="fleet.telemetry-interval",
                 validator=_interval_validator,
                 help="replica health-poll cadence in seconds: the "
                      "coordinator scrapes each replica's /metrics and "
                      "live progress into per-replica headroom series "
                      "(default 1.0; 0 disables the poller entirely — no "
                      "thread, no fleet gauges; env "
                      "TRIVY_TPU_FLEET_TELEMETRY_INTERVAL)"),
            Flag("fleet-split-threshold", default=None, value_type=float,
                 config_name="fleet.split-threshold",
                 validator=_interval_validator,
                 help="mid-scan re-planning multiplier: an in-flight fs "
                      "shard running past this x the median shard wall "
                      "while its replica has no headroom is split at a "
                      "directory boundary and the fragments re-scattered "
                      "(default 3.0 — above --fleet-speculate, a twin is "
                      "cheaper than a re-plan; 0 disables; env "
                      "TRIVY_TPU_FLEET_SPLIT_THRESHOLD)"),
            Flag("fleet-register-token", default=None,
                 config_name="fleet.register-token",
                 help="dedicated bearer token for the POST /fleet/register "
                      "live-join seam (default: the scan --token gates it; "
                      "a bad token answers 403)"),
        ],
    )


_TARGET_GROUPS = {
    "fs": [global_flags, scan_flags, report_flags, secret_flags, license_flags,
           misconf_flags, db_flags, server_client_flags, fleet_flags,
           tuning_flags, incremental_flags],
    "rootfs": [global_flags, scan_flags, report_flags, secret_flags,
               license_flags, misconf_flags, db_flags, server_client_flags,
               fleet_flags, tuning_flags, incremental_flags],
    "repo": [global_flags, scan_flags, report_flags, secret_flags,
             license_flags, misconf_flags, db_flags, server_client_flags,
             fleet_flags, tuning_flags, incremental_flags],
    "watch": [global_flags, scan_flags, report_flags, secret_flags,
              license_flags, misconf_flags, db_flags, tuning_flags],
    "image": [global_flags, scan_flags, report_flags, secret_flags,
              license_flags, misconf_flags, db_flags, server_client_flags,
              image_flags, fleet_flags, tuning_flags, incremental_flags],
    "vm": [global_flags, scan_flags, report_flags, secret_flags,
           license_flags, misconf_flags, db_flags, server_client_flags,
           tuning_flags],
    "sbom": [global_flags, scan_flags, report_flags, db_flags,
             server_client_flags],
    "convert": [global_flags, report_flags],
    "debug": [global_flags],
    "server": [global_flags, db_flags, admission_flags],
    "clean": [global_flags],
}


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="trivy-tpu", description="TPU-native security scanner"
    )
    sub = parser.add_subparsers(dest="command")
    groups_by_cmd: dict[str, list[FlagGroup]] = {}

    help_by_cmd = {
        "fs": "scan a local filesystem",
        "rootfs": "scan an exported root filesystem",
        "repo": "scan a git repository (local path or remote URL)",
        "watch": "watch a directory: incremental re-scan on change (CI mode)",
        "image": "scan a container image (archive, OCI layout, or registry ref)",
        "vm": "scan a VM disk image (raw; MBR/GPT + ext4)",
        "sbom": "scan an SBOM (CycloneDX/SPDX) for vulnerabilities",
        "convert": "convert a saved JSON report into another format",
        "debug": "render a flight-recorder diagnostic bundle "
                 "(timeline + verdict)",
        "server": "run the scan server",
        "clean": "clean caches and databases",
    }
    for cmd, factories in _TARGET_GROUPS.items():
        p = sub.add_parser(cmd, help=help_by_cmd.get(cmd, cmd))
        groups = [f() for f in factories]
        for g in groups:
            g.add_to_parser(p)
        groups_by_cmd[cmd] = groups
        if cmd == "server":
            p.add_argument("--listen", default="0.0.0.0:4954",
                           help="listen address")
            p.add_argument("--token", default="",
                           help="auth token required from clients")
            p.add_argument("--token-header", default="Trivy-Token",
                           help="header carrying the auth token")
        elif cmd == "clean":
            p.add_argument("--all", action="store_true", dest="clean_all")
            p.add_argument("--scan-cache", action="store_true")
            p.add_argument("--vuln-db", action="store_true", dest="vuln_db")
        elif cmd == "repo":
            p.add_argument("--branch", default=None, help="branch to check out")
            p.add_argument("--tag", default=None, help="tag to check out")
            p.add_argument("--commit", default=None, help="commit to check out")
            p.add_argument("target", help="repository path or URL")
        elif cmd == "watch":
            p.add_argument("--watch-interval", default=2.0, type=float,
                           dest="watch_interval",
                           help="seconds between re-scans (default 2)")
            p.add_argument("--watch-count", default=0, type=int,
                           dest="watch_count",
                           help="stop after N scans (0 = run until ^C)")
            p.add_argument("target", help="directory to watch")
        elif cmd == "image":
            # ref: trivy image --input for archives; positional for names
            p.add_argument("--input", default=None,
                           help="image archive (docker save tar / OCI layout)")
            p.add_argument("target", nargs="?", default=None,
                           help="image archive path")
        elif cmd == "debug":
            p.add_argument("target",
                           help="diagnostic bundle path (.json.gz or .json)")
        else:
            p.add_argument("target", help="scan target")

    kp = sub.add_parser("k8s", help="scan Kubernetes workloads (manifests dump or kubectl)")
    kp.add_argument("--manifests", default=None,
                    help="manifest file/dir or cluster dump (kubectl get -o yaml/json)")
    kp.add_argument("--context", default=None, help="kubectl context (live cluster)")
    kp.add_argument("--format", default="table", choices=["table", "json"])
    kp.add_argument("-o", "--output", default=None)
    kp.add_argument("--scan-images", action="store_true",
                    help="also pull and scan workload images (registry source)")
    kp.add_argument("--insecure", action="store_true",
                    help="allow plain-HTTP registries for image pulls")
    kp.add_argument("--db-repository", default=None,
                    help="advisory DB location for image vulnerability scans")
    kp.add_argument("--compliance", default=None,
                    help="compliance spec over the scan (k8s-cis-1.23, "
                         "eks-cis-1.4, k8s-nsa-1.0, @path)")

    pp = sub.add_parser("plugin", help="manage plugins (install/list/run/uninstall)")
    psub = pp.add_subparsers(dest="plugin_cmd")
    pi = psub.add_parser("install"); pi.add_argument("source")
    psub.add_parser("list")
    pu = psub.add_parser("uninstall"); pu.add_argument("name")
    pr = psub.add_parser("run")
    pr.add_argument("name")
    pr.add_argument("plugin_args", nargs=argparse.REMAINDER)

    vp = sub.add_parser("version", help="print version")
    vp.add_argument("--format", default="text", choices=["text", "json"])
    parser._groups_by_cmd = groups_by_cmd  # type: ignore[attr-defined]
    return parser


def main(argv: list[str] | None = None) -> int:
    from trivy_tpu import enable_compile_cache

    enable_compile_cache()
    parser = build_parser()
    ns = parser.parse_args(argv)
    if ns.command is None:
        parser.print_help()
        return 0
    if ns.command == "version":
        if ns.format == "json":
            import json

            print(json.dumps({"Version": VERSION}))
        else:
            print(f"trivy-tpu version {VERSION}")
        return 0
    if ns.command == "k8s":
        import sys as _sys

        from trivy_tpu import k8s

        try:
            if ns.manifests:
                docs = k8s.load_manifests(ns.manifests)
            else:
                docs = k8s.load_cluster(ns.context)
        except RuntimeError as e:
            log.logger("cli").error("%s", e)
            return 1
        rows = k8s.scan_workloads(docs)
        if ns.compliance:
            from trivy_tpu.compliance import apply_spec, load_spec, write_report
            from trivy_tpu.types import Report, Result

            try:
                spec = load_spec(ns.compliance)
            except (OSError, ValueError) as e:
                log.logger("cli").error("%s", e)
                return 1
            report = Report(
                artifact_name="k8s cluster",
                results=[
                    Result(
                        target=f"{r['namespace']}/{r['kind']}/{r['name']}",
                        cls="config",
                        misconfigurations=(
                            list(r["failures"]) + list(r.get("successes", []))
                        ),
                    )
                    for r in rows
                ],
            )
            creport = apply_spec(spec, report)
            if ns.output:
                with open(ns.output, "w") as f:
                    write_report(creport, f, ns.format)
            else:
                write_report(creport, _sys.stdout, ns.format)
            return 0
        image_rows = None
        if ns.scan_images:
            from trivy_tpu.db import load_default_db

            db = load_default_db(ns.db_repository, None)
            if db is None:
                log.logger("cli").warning(
                    "no advisory DB found; image scans report secrets only "
                    "(--db-repository to supply one)"
                )
            image_rows = k8s.scan_images(
                k8s.workload_images(docs), insecure=ns.insecure, db=db,
            )
        if ns.output:
            with open(ns.output, "w") as f:
                k8s.write_summary(rows, f, ns.format, image_rows)
        else:
            k8s.write_summary(rows, _sys.stdout, ns.format, image_rows)
        return 0
    if ns.command == "plugin":
        from trivy_tpu import plugin

        try:
            if ns.plugin_cmd == "install":
                manifest = plugin.install(ns.source)
                print(f"installed {manifest['name']} {manifest.get('version', '')}")
            elif ns.plugin_cmd == "list":
                for m in plugin.list_installed():
                    print(f"{m['name']}\t{m.get('version', '')}\t{m.get('summary', '')}")
            elif ns.plugin_cmd == "uninstall":
                ok = plugin.uninstall(ns.name)
                print("removed" if ok else f"{ns.name} is not installed")
            elif ns.plugin_cmd == "run":
                return plugin.run(ns.name, list(ns.plugin_args or []))
            else:
                parser.parse_args(["plugin", "--help"])
            return 0
        except plugin.PluginError as e:
            log.logger("cli").error("%s", e)
            return 1
        except OSError as e:  # unreadable archive, non-executable bin, ...
            log.logger("cli").error("plugin %s failed: %s", ns.plugin_cmd, e)
            return 1

    groups = parser._groups_by_cmd[ns.command]  # type: ignore[attr-defined]
    try:
        config = load_config_file(getattr(ns, "config", None))
        opts = resolve_all(groups, ns, config)
    except (ValueError, FileNotFoundError) as e:
        parser.error(str(e))
    log.init(
        debug=opts.get("debug", False),
        quiet=opts.get("quiet", False),
        fmt=opts.get("log_format") or "plain",
    )

    from trivy_tpu.commands import run

    return run(ns.command, ns, opts)


if __name__ == "__main__":
    sys.exit(main())
