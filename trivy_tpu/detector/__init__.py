"""Vulnerability detection layer (ref: pkg/detector)."""

from __future__ import annotations

from trivy_tpu import log
from trivy_tpu.types import ArtifactDetail, Result, ResultClass

logger = log.logger("detector")


def detect_all(db, target: str, detail: ArtifactDetail, options) -> list[Result]:
    """OS packages + every application (ref: pkg/scanner/local/scan.go:153-247,
    pkg/scanner/langpkg/scan.go:36)."""
    from trivy_tpu.detector import library, ospkg
    from trivy_tpu.vulnerability import fill_infos

    results: list[Result] = []
    if detail.os and detail.packages and "os" in options.pkg_types:
        vulns = ospkg.detect(db, detail.os, detail.packages)
        fill_infos(db, vulns)
        target_name = f"{target} ({detail.os.family} {detail.os.name})"
        results.append(
            Result(
                target=target_name,
                cls=ResultClass.OS_PKGS.value,
                type=detail.os.family,
                vulnerabilities=vulns,
                packages=detail.packages if options_list_all(options) else [],
            )
        )
    if "library" in options.pkg_types:
        apps = sorted(detail.applications, key=lambda a: (a.file_path, a.type))
        # whole-SBOM one-pass join: every app's packages hash-join and
        # dispatch together against the HBM-resident global bound matrix
        verdicts = library.detect_batch(
            db, apps, host_fallback=getattr(options, "host_fallback", True)
        )
        for app, vulns in zip(apps, verdicts):
            fill_infos(db, vulns)
            if not vulns and not options_list_all(options):
                continue
            results.append(
                Result(
                    target=app.file_path or app.type,
                    cls=ResultClass.LANG_PKGS.value,
                    type=app.type,
                    vulnerabilities=vulns,
                    packages=app.packages if options_list_all(options) else [],
                )
            )
    return results


def options_list_all(options) -> bool:
    return bool(getattr(options, "list_all_pkgs", False))
