"""CI-shaped source trees for `trivy fs` traffic.

``make_tree`` is copied from ``chip_smoke.py`` (PR 21), whose shape was
proven on the v5e; it reads the benchmark's own copies of the license
texts and the secret samples, so a later change to the program's corpus
or test samples cannot change the inputs. ``tree_pool`` is the generator
that the ``fs_trees`` traffic files parametrise.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _texts() -> tuple[dict, list]:
    corpus = _load(os.path.join(BENCH, "reference", "license_corpus.json"))
    samples = _load(os.path.join(HERE, "secret_samples.json"))["samples"]
    return corpus["full_texts"], sorted(samples.values())


def _code_lines(rng) -> list[bytes]:
    """A few thousand distinct source-like lines to draw file bodies from."""
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


def make_tree(root: str, seed, total_bytes: int) -> dict:
    """A CI-shaped source tree of at least ``total_bytes`` bytes: packages
    of source files with license headers (most files 1-64 KiB), a tail of
    1-8 MiB generated files, planted secrets, a LICENSE per package, one
    vendored duplicate subtree and a few binaries. ``seed`` is anything
    ``np.random.default_rng`` takes.

    Three departures from chip_smoke's generator keep a seed from changing
    the amount of work: the last tail file is cut to the tail's share, tail
    files stay out of the vendored package, and each binary is 0.5% of the
    tree (chip_smoke: 256 KiB-2 MiB)."""
    full_texts, secrets = _texts()
    rng = np.random.default_rng(seed)
    lines = _code_lines(rng)
    lic_ids = sorted(full_texts)
    exts = (".py", ".go", ".js", ".c", ".java", ".rs", ".ts", ".rb")
    comment = {".py": b"# ", ".rb": b"# "}
    stats = {"files": 0, "bytes": 0, "secrets_planted": 0, "binaries": 0,
             "vendored_bytes": 0, "large_files": 0, "planted": []}

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

    def source(n: int, lic: str, ext: str, rel: str) -> bytes:
        data = body(n)
        if rng.random() < 0.8:
            prefix = comment.get(ext, b"// ")
            head = full_texts[lic].encode("latin-1", "replace")[:1200]
            data = b"\n".join(prefix + ln for ln in head.split(b"\n")) + b"\n" + data
        if rng.random() < 1 / 60:
            s = secrets[stats["secrets_planted"] % len(secrets)].encode()
            at = data.find(b"\n", int(rng.integers(0, len(data)))) + 1
            data = data[:at] + s + b"\n" + data[at:]
            stats["secrets_planted"] += 1
            stats["planted"].append(rel)
        return data

    packages = [f"pkg{i:02d}" for i in range(48)]
    lic_of = {p: lic_ids[int(rng.integers(0, len(lic_ids)))] for p in packages}
    for p in packages:
        write(f"src/{p}/LICENSE", full_texts[lic_of[p]].encode("latin-1", "replace"))
    # the tail: generated fixtures, bundles and logs, 1-8 MiB each, about
    # 60% of the bytes, in packages other than the vendored one
    while stats["bytes"] < 0.6 * total_bytes:
        n = int(np.exp(rng.uniform(np.log(1 << 20), np.log(8 << 20))))
        n = min(n, max(1 << 20, int(0.6 * total_bytes) - stats["bytes"]))
        p = packages[int(rng.integers(1, len(packages)))]
        kind = ("fixtures/data.json", "dist/bundle.min.js", "logs/run.log")[
            stats["large_files"] % 3
        ]
        rel = f"src/{p}/{stats['large_files']:03d}-{kind.replace('/', '_')}"
        write(rel, source(n, lic_of[p], ".js", rel))
        stats["large_files"] += 1
    # the body: source files, log-uniform 1-64 KiB
    vendored_pkg = packages[0]
    i = 0
    while stats["bytes"] < 0.97 * total_bytes:
        n = int(np.exp(rng.uniform(np.log(1 << 10), np.log(64 << 10))))
        p = packages[int(rng.integers(0, len(packages)))]
        ext = exts[int(rng.integers(0, len(exts)))]
        rel = f"src/{p}/mod{i % 7}/file{i:05d}{ext}"
        write(rel, source(n, lic_of[p], ext, rel))
        i += 1
    # one vendored duplicate subtree: byte-identical copy of a package
    src = os.path.join(root, "src", vendored_pkg)
    for dirpath, _dirs, names in sorted(os.walk(src)):
        for name in sorted(names):
            rel = os.path.relpath(os.path.join(dirpath, name), src)
            with open(os.path.join(dirpath, name), "rb") as f:
                data = f.read()
            write(f"third_party/vendor/{vendored_pkg}/{rel}", data)
            stats["vendored_bytes"] += len(data)
    # a few binaries, 0.5% of the bytes each
    for j, ext in enumerate((".so", ".o", ".wasm", ".pyc")):
        n = int(0.005 * total_bytes)
        blob = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
        write(f"build/out{j}{ext}", blob)
        stats["binaries"] += 1
    while stats["bytes"] < total_bytes:
        p = packages[int(rng.integers(0, len(packages)))]
        rel = f"src/{p}/extra/file{i:05d}.go"
        write(rel, source(64 << 10, lic_of[p], ".go", rel))
        i += 1
    return stats


def pool_sizes(traffic: dict) -> list[int]:
    """Repository sizes in bytes: the log-uniform quantiles of the mix's
    range, the same set for every seed (a seed only reorders them), so
    runs on different seeds do the same amount of work."""
    n = int(traffic["pool"])
    lo, hi = np.log(traffic["mib_min"] * 2**20), np.log(traffic["mib_max"] * 2**20)
    return [int(np.exp(lo + (hi - lo) * (i + 0.5) / n)) for i in range(n)]


def tree_pool(root: str, traffic: dict, seed: int) -> list[dict]:
    """The seed's pool of repositories, made under ``root``:
    ``[{"path", "bytes", "files", "planted", ...}, ...]`` in the order the
    window scans them. The order of sizes is fixed and the seed chooses
    the content, so every seed gives the same work."""
    sizes = pool_sizes(traffic)
    order = np.random.default_rng(0).permutation(len(sizes)).tolist()
    pool = []
    for k, i in enumerate(order):
        path = os.path.join(root, f"repo{k}")
        stats = make_tree(path, [seed, 1 + k], sizes[i])
        pool.append(dict(stats, path=path))
    return pool
