"""trivy_tpu: a TPU-native security-scanning framework.

Capabilities modeled on the reference scanner (undistro/trivy v0.57.0): scan
container images, filesystems, repositories, SBOMs, VM images and Kubernetes
clusters for vulnerabilities, secrets, licenses and IaC misconfigurations.

The architecture keeps the reference's load-bearing contracts —
Artifact/Driver split (ref: pkg/scanner/scan.go:134-152), the normalized
BlobInfo intermediate (ref: pkg/fanal/types), the content-addressed cache
(ref: pkg/cache) and the analyzer registry (ref: pkg/fanal/analyzer) — but
re-implements the three data-parallel scan engines TPU-first:

* secret scanning: rules compile into a single batched multi-pattern DFA plus
  a keyword prefilter that runs as one-hot matmuls on the MXU
  (``trivy_tpu.ops``), over fixed-size overlapping chunks of file bytes.
* license classification: n-gram similarity as sharded vmap'd int32 matmul /
  top-k over corpus shards (``trivy_tpu.licensing``).
* SBOM -> CVE matching: version-constraint evaluation vectorized as sharded
  lookups (``trivy_tpu.detector``).

Multi-chip scaling uses ``jax.sharding.Mesh`` + ``shard_map`` with XLA
collectives over ICI (``trivy_tpu.parallel``), not RPC fan-out.
"""

__version__ = "0.1.0"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one home and return
    the directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is the home
    (JAX reads it itself, so nothing else is set); otherwise it is
    ``.jax_cache`` at the checkout root. The path is part of the cache's
    key, so it is fixed: a directory that moved would never hit. Callers
    are entry points (``cli.main``, ``chip_smoke.py``), never imports."""
    import os

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if path:
        return path
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path
