"""Scanner facade binding an Artifact to a scan Driver
(ref: pkg/scanner/scan.go:134-204)."""

from __future__ import annotations

import datetime
from dataclasses import dataclass, field

from trivy_tpu.types import Report, Result


@dataclass
class ScanOptions:
    """What to scan and how (ref: pkg/types ScanOptions)."""

    scanners: list[str] = field(default_factory=lambda: ["vuln", "secret"])
    license_categories: dict[str, list[str]] = field(default_factory=dict)
    license_full: bool = False
    include_dev_deps: bool = False
    pkg_types: list[str] = field(default_factory=lambda: ["os", "library"])
    detection_priority: str = "precise"
    list_all_pkgs: bool = False
    # --no-host-fallback: a failed device leg fails the scan instead of
    # degrading to the host oracle
    host_fallback: bool = True


class Scanner:
    """Artifact + Driver (local or remote client), ref: scan.go:134-152."""

    def __init__(self, artifact, driver):
        self.artifact = artifact
        self.driver = driver

    def scan_artifact(self, options: ScanOptions) -> Report:
        from trivy_tpu import obs

        # scan-health events (degradations, skipped files) accumulate on
        # the active trace context even with tracing off; the before/after
        # delta is exactly this scan's share, so back-to-back library scans
        # sharing the process-default context stay disjoint
        health0 = obs.current().health_snapshot()
        ref = self.artifact.inspect()
        results, os_info = self.driver.scan(ref.name, ref.id, ref.blob_ids, options)
        health = obs.current().health_snapshot()
        delta = {k: v - health0.get(k, 0) for k, v in health.items()}
        metadata = {
            "ImageID": ref.image_metadata.get("id", ""),
            "DiffIDs": ref.image_metadata.get("diff_ids", []),
        }
        if os_info is not None:
            metadata["OS"] = os_info.to_dict()
        if ref.image_metadata.get("config"):
            metadata["ImageConfig"] = ref.image_metadata["config"]
        skipped = delta.get("walk.skipped", 0)
        if skipped > 0:
            metadata["SkippedFiles"] = skipped
        if delta.get("cache.degraded", 0) > 0:
            metadata["CacheDegraded"] = True
        return Report(
            created_at=datetime.datetime.now(datetime.timezone.utc).isoformat(),
            artifact_name=ref.name,
            artifact_type=ref.type,
            metadata=metadata,
            results=[r for r in results if not r.is_empty],
            degraded=delta.get("scan.degraded", 0) > 0,
        )


__all__ = ["Scanner", "ScanOptions", "Result"]
