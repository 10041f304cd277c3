#!/usr/bin/env python3
"""Chip smoke test: trivy-tpu's main path, once, on one TPU v5e.

Two legs run through the CLI entry point a user calls
(``trivy_tpu.cli.main``), all in this one process, because a chip
belongs to one process at a time:

(a) ``fs --scanners secret,license --license-full`` over a seeded tree
    of at least 256 MiB, shaped like a CI source tree. It runs on the
    device backend, and its ``Results`` must be byte-identical to
    ``--backend cpu`` on the same tree.
(b) ``sbom`` of a CycloneDX file with at least 50k packages, against a
    seeded advisory DB of ``bench_cve``'s shape: about 106k advisories
    over ten trivy-db buckets. Its vulnerabilities must equal those of
    the host comparator (``_is_vulnerable``), computed without device
    code.

Every input is generated from ``--seed``. Any failure exits non-zero.
The last line of stdout is the one JSON object the driver reads:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
``--four-chips`` runs leg (a) alone, with batches round-robined over the
four chips of a v5e-4 host, and checks that every chip received batches.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
# scratch for the generated inputs, caches and reports (in .gitignore and
# .chiprunignore); removed when the run ends
WORK = os.path.join(ROOT, ".chip_smoke")

FS_MIB = 256  # leg (a): tree size floor
# per-scan --timeout: a cold chip spends minutes compiling before the
# first batch, past the CLI's 300 s default
SCAN_TIMEOUT_S = 900
SBOM_PKGS = 50_000  # leg (b): package count floor
# leg (b): bench_cve's trivy-db bucket plan (real source-bucket names)
DB_BUCKETS = (
    ("npm::GitHub Security Advisory Npm", 30_000),
    ("npm::Node.js Ecosystem Security Working Group", 10_000),
    ("pip::GitHub Security Advisory Pip", 20_000),
    ("pip::OSV/PyPA Advisory Database", 8_000),
    ("go::GitHub Security Advisory Go", 15_000),
    ("go::GitLab Advisory Database Community", 7_000),
    ("composer::GitHub Security Advisory Composer", 6_000),
    ("composer::php-security-advisories", 2_000),
    ("rubygems::ruby-advisory-db", 4_000),
    ("cargo::GitHub Security Advisory Rust", 4_000),
)
# SBOM share per ecosystem: (db prefix, purl type, share of packages)
SBOM_MIX = (
    ("npm", "npm", 0.40),
    ("pip", "pypi", 0.18),
    ("go", "golang", 0.20),
    ("composer", "composer", 0.08),
    ("rubygems", "gem", 0.06),
    ("cargo", "cargo", 0.08),
)


def say(key: str, value) -> None:
    print(f"{key}: {json.dumps(value, sort_keys=True)}", flush=True)


# -- inputs --------------------------------------------------------------------


def _code_lines(rng) -> list[bytes]:
    """A few thousand distinct source-like lines to draw file bodies from."""
    import numpy as np

    letters = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz_", dtype=np.uint8)
    words = [
        rng.choice(letters[:26], size=1).tobytes()
        + rng.choice(letters, size=int(n)).tobytes()
        for n in rng.integers(1, 11, size=1024)
    ]
    templates = (
        b"    %s = %s(%s, %d)",
        b"def %s(%s, %s):",
        b"// %s %s %s, see %s.%s",
        b"    if (%s > %d) { return %s; }",
        b"import %s.%s.%s",
        b'    "%s": "%s-%s-%d",',
        b"    return %s + %s * %d",
        b"func (%s *%s) %s(ctx context.Context) error {",
        b"#include <%s/%s.h>",
        b"",
        b"    }",
        b"SELECT %s, %s FROM %s WHERE %s = %d;",
    )
    lines = []
    for i in range(4096):
        t = templates[i % len(templates)]
        args = []
        for spec in t.split(b"%")[1:]:
            if spec.startswith(b"d"):
                args.append(int(rng.integers(0, 10_000)))
            else:
                args.append(words[int(rng.integers(0, len(words)))])
        lines.append(t % tuple(args))
    return lines


def make_tree(root: str, seed: int, total_bytes: int) -> dict:
    """A CI-shaped source tree of at least ``total_bytes`` bytes: packages
    of source files with license headers (most files 1-64 KiB), a tail of
    1-8 MiB generated files, planted secrets, a LICENSE per package, one
    vendored duplicate subtree and a few binaries."""
    import numpy as np

    from tests.secret_samples import SAMPLES
    from trivy_tpu.licensing.corpus_texts import FULL_TEXTS

    rng = np.random.default_rng(seed)
    lines = _code_lines(rng)
    secrets = sorted(SAMPLES.values())
    lic_ids = sorted(FULL_TEXTS)
    exts = (".py", ".go", ".js", ".c", ".java", ".rs", ".ts", ".rb")
    comment = {".py": b"# ", ".rb": b"# "}
    stats = {"files": 0, "bytes": 0, "secrets_planted": 0, "binaries": 0,
             "vendored_bytes": 0, "large_files": 0}

    def body(n: int) -> bytes:
        idx = rng.integers(0, len(lines), size=max(1, n // 24))
        return b"\n".join([lines[i] for i in idx.tolist()])[:n] + b"\n"

    def write(rel: str, data: bytes) -> None:
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(data)
        stats["files"] += 1
        stats["bytes"] += len(data)

    def source(n: int, lic: str, ext: str) -> bytes:
        data = body(n)
        if rng.random() < 0.8:
            prefix = comment.get(ext, b"// ")
            head = FULL_TEXTS[lic].encode("latin-1", "replace")[:1200]
            data = b"\n".join(prefix + ln for ln in head.split(b"\n")) + b"\n" + data
        if rng.random() < 1 / 60:
            s = secrets[stats["secrets_planted"] % len(secrets)].encode()
            at = data.find(b"\n", int(rng.integers(0, len(data)))) + 1
            data = data[:at] + s + b"\n" + data[at:]
            stats["secrets_planted"] += 1
        return data

    packages = [f"pkg{i:02d}" for i in range(48)]
    lic_of = {p: lic_ids[int(rng.integers(0, len(lic_ids)))] for p in packages}
    for p in packages:
        write(f"src/{p}/LICENSE", FULL_TEXTS[lic_of[p]].encode("latin-1", "replace"))
    # the tail: generated fixtures, bundles and logs, 1-8 MiB each, about
    # 60% of the bytes
    while stats["bytes"] < 0.6 * total_bytes:
        n = int(np.exp(rng.uniform(np.log(1 << 20), np.log(8 << 20))))
        p = packages[int(rng.integers(0, len(packages)))]
        kind = ("fixtures/data.json", "dist/bundle.min.js", "logs/run.log")[
            stats["large_files"] % 3
        ]
        write(f"src/{p}/{stats['large_files']:03d}-{kind.replace('/', '_')}",
              source(n, lic_of[p], ".js"))
        stats["large_files"] += 1
    # the body: source files, log-uniform 1-64 KiB
    vendored_pkg = packages[0]
    i = 0
    while stats["bytes"] < 0.97 * total_bytes:
        n = int(np.exp(rng.uniform(np.log(1 << 10), np.log(64 << 10))))
        p = packages[int(rng.integers(0, len(packages)))]
        ext = exts[int(rng.integers(0, len(exts)))]
        write(f"src/{p}/mod{i % 7}/file{i:05d}{ext}", source(n, lic_of[p], ext))
        i += 1
    # one vendored duplicate subtree: byte-identical copy of a package
    src = os.path.join(root, "src", vendored_pkg)
    for dirpath, _dirs, names in os.walk(src):
        for name in names:
            rel = os.path.relpath(os.path.join(dirpath, name), src)
            with open(os.path.join(dirpath, name), "rb") as f:
                data = f.read()
            write(f"third_party/vendor/{vendored_pkg}/{rel}", data)
            stats["vendored_bytes"] += len(data)
    # a few binaries
    for j, ext in enumerate((".so", ".o", ".wasm", ".pyc")):
        n = int(rng.integers(256 << 10, 2 << 20))
        blob = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
        write(f"build/out{j}{ext}", blob)
        stats["binaries"] += 1
    while stats["bytes"] < total_bytes:
        p = packages[int(rng.integers(0, len(packages)))]
        write(f"src/{p}/extra/file{i:05d}.go", source(64 << 10, lic_of[p], ".go"))
        i += 1
    return stats


def make_db(db_dir: str, seed: int) -> tuple[dict, int]:
    """Flattened advisory DB (db/convert.py layout) at bench_cve's shape.
    Returns ``(names per ecosystem, advisory count)``."""
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    suffixes = ("", "", "", "-beta.1", "-rc2", "")
    os.makedirs(os.path.join(db_dir, "advisories"), exist_ok=True)
    manifest: dict[str, str] = {}
    names: dict[str, int] = {}
    n_adv = 0
    for bi, (bucket, count) in enumerate(DB_BUCKETS):
        eco = bucket.split("::", 1)[0]
        n_names = count // 2
        names[eco] = max(names.get(eco, 0), n_names)
        rows: dict[str, list] = {}
        majors = rng.integers(0, 9, size=count)
        minors = rng.integers(0, 10, size=count)
        for i in range(count):
            lo = f"{majors[i]}.{minors[i]}.0{suffixes[i % len(suffixes)]}"
            hi = f"{majors[i] + 1}.{minors[i]}.0"
            rows.setdefault(_pkg_name(eco, i % n_names), []).append({
                "VulnerabilityID": f"CVE-2024-{n_adv:06d}",
                "VulnerableVersions": [f">={lo}, <{hi}"],
                "PatchedVersions": [hi],
            })
            n_adv += 1
        rel = f"advisories/{bi:02d}.json"
        with open(os.path.join(db_dir, rel), "w") as f:
            json.dump({bucket: rows}, f)
        manifest[bucket] = rel
    with open(os.path.join(db_dir, "manifest.json"), "w") as f:
        json.dump({"buckets": manifest}, f)
    with open(os.path.join(db_dir, "metadata.json"), "w") as f:
        json.dump({"Version": 2, "NextUpdate": "2999-01-01T00:00:00Z"}, f)
    return names, n_adv


def _pkg_name(eco: str, i: int) -> str:
    if eco == "go":
        return f"github.com/acme{i % 97}/go-pkg-{i:05d}"
    if eco == "composer":
        return f"acme{i % 31}/composer-pkg-{i:05d}"
    return f"{eco}-pkg-{i:05d}"


def make_sbom(path: str, seed: int, names: dict, n_pkgs: int) -> int:
    """CycloneDX JSON with ``n_pkgs`` unique (ecosystem, name, version)
    components; about one name in six has no advisory at all."""
    import numpy as np

    rng = np.random.default_rng(seed + 2)
    comps = []
    for eco, ptype, share in SBOM_MIX:
        want = int(round(n_pkgs * share))
        seen: set[tuple[str, str]] = set()
        while len(seen) < want:
            k = want - len(seen)
            idx = rng.integers(0, int(names[eco] * 1.2), size=k)
            ver = rng.integers(0, 10, size=(k, 3))
            pre = rng.random(k) < 0.05
            for j in range(k):
                v = f"{ver[j, 0]}.{ver[j, 1]}.{ver[j, 2]}" + ("-rc1" if pre[j] else "")
                seen.add((_pkg_name(eco, int(idx[j])), v))
        for name, v in sorted(seen):
            pv = f"v{v}" if eco == "go" else v
            comps.append({
                "type": "library", "name": name, "version": pv,
                "purl": f"pkg:{ptype}/{name}@{pv}",
                "bom-ref": f"pkg:{ptype}/{name}@{pv}",
            })
    with open(path, "w") as f:
        json.dump({"bomFormat": "CycloneDX", "specVersion": "1.5",
                   "version": 1, "components": comps}, f)
    return len(comps)


# -- legs ----------------------------------------------------------------------


class _Contained(logging.Handler):
    """Analyzer errors are logged and contained by design (one bad file
    must not kill a scan); here any such error fails the run."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.messages: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.messages.append(record.getMessage())


def _cli(args: list[str]) -> float:
    """One CLI run; raises unless it exits 0 with no contained analyzer
    error. Returns its wall seconds."""
    from trivy_tpu import cli

    contained = _Contained()
    watched = logging.getLogger("trivy_tpu.analyzer")
    watched.addHandler(contained)
    t0 = time.perf_counter()
    try:
        rc = cli.main(args + ["--timeout", str(SCAN_TIMEOUT_S)])
    finally:
        watched.removeHandler(contained)
    wall = time.perf_counter() - t0
    if rc != 0:
        raise RuntimeError(f"trivy-tpu {' '.join(args)} exited {rc}")
    if contained.messages:
        raise RuntimeError(f"trivy-tpu {args[0]}: {contained.messages[:3]}")
    return wall


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _compile_ledger() -> dict:
    from trivy_tpu.obs import recorder

    doc = recorder.device_doc() or {}
    return {k: dict(v) for k, v in (doc.get("compiles") or {}).items()}


def _ledger_delta(before: dict, after: dict) -> dict:
    out = {}
    for k, v in after.items():
        b = before.get(k, {"count": 0, "wall_s": 0.0})
        if v["count"] != b["count"]:
            out[k] = {"count": v["count"] - b["count"],
                      "wall_s": round(v["wall_s"] - b["wall_s"], 3)}
    return out


def _check_clean(leg: str, report: dict, metrics: dict) -> None:
    """No fallback may hide the device: no degraded report, no degraded
    counter, no open breaker."""
    if report.get("Degraded"):
        raise RuntimeError(f"{leg}: report is Degraded")
    bad = {k: v for k, v in (metrics.get("counters") or {}).items()
           if "degraded" in k and v}
    if bad:
        raise RuntimeError(f"{leg}: degraded counters {bad}")


def _results_diff(got: list, want: list, limit: int = 8) -> dict:
    """Finding-level differences between two ``Results`` lists: counts of
    secret and license findings only one side has, and a few of each."""
    from collections import Counter

    def findings(results):
        out = Counter()
        for r in results:
            for f in r.get("Secrets") or []:
                out[("secret", r.get("Target"), f.get("RuleID"),
                     f.get("StartLine"), f.get("Match"))] += 1
            for f in r.get("Licenses") or []:
                out[("license", f.get("FilePath"), f.get("Name"),
                     f.get("Confidence"))] += 1
        return out

    a, b = findings(got), findings(want)
    only_dev, only_cpu = a - b, b - a
    return {
        "only_device": sum(only_dev.values()),
        "only_cpu": sum(only_cpu.values()),
        "device_examples": sorted(only_dev)[:limit],
        "cpu_examples": sorted(only_cpu)[:limit],
    }


def _secret_scanner():
    """The process-cached device secret scanner the fs leg ran on."""
    from trivy_tpu.fanal.analyzers import secret as secret_analyzer

    for scanner, _degraded in secret_analyzer._scanner_cache.values():
        if hasattr(scanner, "_staged"):
            return scanner
    raise RuntimeError("no device secret scanner was built")


def fs_leg(work: str, seed: int, total_bytes: int, n_devices: int,
           secret_backend: str = "pallas") -> dict:
    """Leg (a). ``secret_backend`` is what ``--backend auto`` must pick:
    ``pallas`` on a TPU (the license scorer must then run on the device
    too); the tests rehearse the leg on the CPU, where it picks ``xla``."""
    tree = os.path.join(work, "tree")
    t0 = time.perf_counter()
    stats = make_tree(tree, seed, total_bytes)
    say("fs.tree", dict(stats, setup_s=round(time.perf_counter() - t0, 3)))

    common = ["fs", "--scanners", "secret,license", "--license-full",
              "--no-host-fallback", "--format", "json"]
    ledger0 = _compile_ledger()
    dev_out = os.path.join(work, "fs-device.json")
    dev_metrics = os.path.join(work, "fs-device-metrics.json")
    wall_dev = _cli(common + [
        "--backend", "auto", "--cache-dir", os.path.join(work, "cache-fs-dev"),
        "-o", dev_out, "--metrics-out", dev_metrics, tree,
    ])
    compiles = _ledger_delta(ledger0, _compile_ledger())
    cpu_out = os.path.join(work, "fs-cpu.json")
    wall_cpu = _cli(common + [
        "--backend", "cpu", "--cache-dir", os.path.join(work, "cache-fs-cpu"),
        "-o", cpu_out, tree,
    ])
    dev, cpu, metrics = _read(dev_out), _read(cpu_out), _read(dev_metrics)
    _check_clean("fs", dev, metrics)

    scanner = _secret_scanner()
    if scanner.backend != secret_backend:
        raise RuntimeError(
            f"fs: secret backend {scanner.backend!r}, want {secret_backend!r}"
        )
    counters = metrics.get("counters") or {}
    if secret_backend == "pallas" and not counters.get("license.score_rows"):
        raise RuntimeError("fs: the device license scorer scored no rows")
    if scanner.stats.snapshot()["degraded"]:
        raise RuntimeError("fs: the secret scan degraded to the host path")
    breaker = scanner._staged.breaker
    if breaker is not None and breaker.open_devices():
        raise RuntimeError(f"fs: breaker open on {breaker.open_devices()}")
    batches = scanner._staged.busy.batches()
    busy = scanner._staged.busy.busy_seconds()
    if len(batches) != n_devices or not all(batches):
        raise RuntimeError(
            f"fs: batches per device {batches}, want {n_devices} non-zero"
        )

    dev_res = json.dumps(dev.get("Results") or [], sort_keys=True)
    cpu_res = json.dumps(cpu.get("Results") or [], sort_keys=True)
    parity = dev_res == cpu_res
    results = dev.get("Results") or []
    out = {
        "backend": {
            "secret": scanner.backend,
            "license": "device" if counters.get("license.score_rows") else "host",
        },
        "bytes_scanned": stats["bytes"],
        "files": stats["files"],
        "secret_findings": sum(len(r.get("Secrets") or []) for r in results),
        "license_findings": sum(len(r.get("Licenses") or []) for r in results),
        "parity_vs_cpu": parity,
        "wall_s": {"device": round(wall_dev, 3), "cpu_reference": round(wall_cpu, 3)},
        "compiles": compiles,
        "batches_per_device": batches,
        "busy_s_per_device": [round(b, 3) for b in busy],
    }
    say("fs", out)
    if not parity:
        say("fs.diff", _results_diff(dev.get("Results") or [],
                                     cpu.get("Results") or []))
        raise RuntimeError("fs: device Results differ from --backend cpu")
    return out


def sbom_leg(work: str, seed: int, n_pkgs: int) -> dict:
    from trivy_tpu.db import VulnDB
    from trivy_tpu.detector.library import ECOSYSTEMS, _is_vulnerable, _normalize_name
    from trivy_tpu.sbom.decode import decode

    t0 = time.perf_counter()
    db_dir = os.path.join(work, "db")
    names, n_adv = make_db(db_dir, seed)
    sbom = os.path.join(work, "bom.cdx.json")
    n = make_sbom(sbom, seed, names, n_pkgs)
    say("sbom.inputs", {"packages": n, "advisories": n_adv,
                        "buckets": len(DB_BUCKETS),
                        "setup_s": round(time.perf_counter() - t0, 3)})

    ledger0 = _compile_ledger()
    out_path = os.path.join(work, "sbom.json")
    metrics_path = os.path.join(work, "sbom-metrics.json")
    wall = _cli(["sbom", sbom, "--db-repository", db_dir, "--skip-db-update",
                 "--no-host-fallback", "--format", "json",
                 "--cache-dir", os.path.join(work, "cache-sbom"),
                 "-o", out_path, "--metrics-out", metrics_path])
    compiles = _ledger_delta(ledger0, _compile_ledger())
    report, metrics = _read(out_path), _read(metrics_path)
    _check_clean("sbom", report, metrics)
    counters = metrics.get("counters") or {}
    if not counters.get("cve.resident_rows"):
        raise RuntimeError("sbom: the resident CVE join ran no device rows")
    got = sorted(
        (r.get("Type", ""), v["PkgName"], v["InstalledVersion"], v["VulnerabilityID"])
        for r in report.get("Results") or []
        for v in r.get("Vulnerabilities") or []
    )

    # the oracle: host comparator over the same decoded SBOM and DB
    t0 = time.perf_counter()
    with open(sbom, "rb") as f:
        blob = decode(f.read())
    db = VulnDB.load(db_dir)
    want = []
    for app in blob.applications:
        prefix, scheme = ECOSYSTEMS[app.type]
        index = db.prefix_advisories(f"{prefix}::")
        for pkg in app.packages:
            for adv in index.get(_normalize_name(prefix, pkg.name), ()):
                if pkg.version and _is_vulnerable(scheme, pkg.version, adv):
                    want.append((app.type, pkg.name, pkg.version, adv.vulnerability_id))
    want.sort()
    wall_host = time.perf_counter() - t0
    parity = got == want
    out = {
        "packages": n,
        "vulnerabilities": len(got),
        "resident_rows": counters.get("cve.resident_rows", 0),
        "parity_vs_host": parity,
        "wall_s": {"device": round(wall, 3), "host_oracle": round(wall_host, 3)},
        "compiles": compiles,
    }
    say("sbom", out)
    if not parity:
        raise RuntimeError(
            f"sbom: {len(got)} device vulnerabilities vs {len(want)} "
            "from the host comparator"
        )
    return out


# -- entry ---------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run leg (a) alone over the four chips of one host")
    args = ap.parse_args(argv)
    n_devices = 4 if args.four_chips else 1
    if not args.four_chips:
        # one chip of however many the host has, before JAX initializes
        # (the per-process bounds JAX's own multi-process TPU tests use)
        os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
        os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
        os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")
    sys.path.insert(0, ROOT)
    from trivy_tpu import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: JAX found platform {d0.platform!r} "
              f"({d0.device_kind}), not a TPU", file=sys.stderr)
        return 2
    if len(devices) != n_devices:
        print(f"chip_smoke: {len(devices)} devices, want {n_devices}",
              file=sys.stderr)
        return 2
    say("device", {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices), "compile_cache": cache_dir})

    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    t_all = time.perf_counter()
    try:
        fs_leg(WORK, args.seed, FS_MIB << 20, n_devices)
        if not args.four_chips:
            sbom_leg(WORK, args.seed, SBOM_PKGS)
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    say("memory", {f"d{d.id}": (d.memory_stats() or {}).get("peak_bytes_in_use")
                   for d in devices})
    say("wall_s", round(time.perf_counter() - t_all, 3))
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
