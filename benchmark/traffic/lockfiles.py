"""Lockfile checkouts for `trivy fs --scanners vuln --server` clients.

``lockfile_pool`` is the generator that the ``lockfile_clients`` traffic
files parametrise: each request is a checkout that holds one service's
lockfile, of an ecosystem drawn from the mix, with a log-uniform number of
packages whose names are Zipf-skewed over that ecosystem's names in the
advisory DB (popular packages recur across services, as in real fleets).
The writers emit the files the way the package managers do, at the shape
the program's parsers (``dependency/parsers.py``) read; the benchmark's
tests check each against its parser on the CPU.
"""

from __future__ import annotations

import json
import os

import numpy as np

from benchmark.traffic.advisories import pkg_name

# lockfile kind -> (file name, advisory DB ecosystem prefix)
KINDS = {
    "npm": ("package-lock.json", "npm"),
    "gomod": ("go.mod", "go"),
    "cargo": ("Cargo.lock", "cargo"),
    "composer": ("composer.lock", "composer"),
    "poetry": ("poetry.lock", "pip"),
    "bundler": ("Gemfile.lock", "rubygems"),
}


def _prerelease(kind: str, core: str) -> str:
    """A release candidate as each ecosystem spells it."""
    if kind == "poetry":
        return core + "rc1"  # PEP 440 normal form
    if kind == "bundler":
        return core + ".rc1"  # RubyGems style
    return core + "-rc1"  # SemVer


def write_lockfile(kind: str, path: str, pkgs: list[tuple[str, str]]) -> None:
    """``pkgs`` is ``[(name, version), ...]`` with distinct names."""
    if kind == "npm":
        doc = {
            "name": "service", "version": "1.0.0", "lockfileVersion": 3,
            "requires": True,
            "packages": {"": {"name": "service", "version": "1.0.0",
                              "dependencies": {n: f"^{v}" for n, v in pkgs}}},
        }
        for n, v in pkgs:
            doc["packages"][f"node_modules/{n}"] = {
                "version": v,
                "resolved": f"https://registry.npmjs.org/{n}/-/{n}-{v}.tgz",
                "integrity": "sha512-AAAA",
            }
        text = json.dumps(doc, indent=2)
    elif kind == "gomod":
        lines = ["module example.com/service", "", "go 1.21", "", "require ("]
        lines += [f"\t{n} v{v}" for n, v in pkgs]
        lines += [")", ""]
        text = "\n".join(lines)
    elif kind in ("cargo", "poetry"):
        parts = ["# This file is automatically @generated.\n"
                 if kind == "cargo" else "# This file is automatically @generated by Poetry.\n"]
        for n, v in pkgs:
            if kind == "cargo":
                parts.append(
                    f'\n[[package]]\nname = "{n}"\nversion = "{v}"\n'
                    'source = "registry+https://github.com/rust-lang/crates.io-index"\n'
                )
            else:
                parts.append(
                    f'\n[[package]]\nname = "{n}"\nversion = "{v}"\n'
                    'description = ""\noptional = false\npython-versions = ">=3.8"\n'
                )
        if kind == "poetry":
            parts.append('\n[metadata]\nlock-version = "2.0"\npython-versions = "^3.8"\n')
        else:
            parts.append("")
        text = "".join(parts)
    elif kind == "composer":
        text = json.dumps({
            "_readme": ["This file locks the dependencies of your project"],
            "packages": [{"name": n, "version": v, "type": "library"}
                         for n, v in pkgs],
            "packages-dev": [],
        }, indent=4)
    elif kind == "bundler":
        lines = ["GEM", "  remote: https://rubygems.org/", "  specs:"]
        lines += [f"    {n} ({v})" for n, v in pkgs]
        lines += ["", "PLATFORMS", "  ruby", "", "DEPENDENCIES"]
        lines += [f"  {n}" for n, _v in pkgs]
        lines += ["", "BUNDLED WITH", "   2.4.10", ""]
        text = "\n".join(lines)
    else:
        raise ValueError(f"unknown lockfile kind {kind!r}")
    with open(path, "w") as f:
        f.write(text)


def _counts(mix: dict, n: int) -> dict:
    """Exact per-kind request counts for a pool of ``n`` (largest
    remainder), the same for every seed."""
    raw = {k: n * float(s) for k, s in mix.items()}
    out = {k: int(v) for k, v in raw.items()}
    for k in sorted(raw, key=lambda k: out[k] - raw[k])[: n - sum(out.values())]:
        out[k] += 1
    return out


def pool_plan(traffic: dict) -> list[tuple[str, int]]:
    """``[(kind, n_packages), ...]`` before the seed's shuffle: every seed
    gets the same kinds and sizes."""
    n = int(traffic["pool"])
    lo, hi = np.log(traffic["pkgs_min"]), np.log(traffic["pkgs_max"])
    sizes = [int(round(np.exp(lo + (hi - lo) * (i + 0.5) / n))) for i in range(n)]
    kinds = [k for k, c in sorted(_counts(traffic["mix"], n).items()) for _ in range(c)]
    # interleave so that sizes spread evenly over kinds
    order = np.random.default_rng(12345).permutation(n).tolist()
    return [(kinds[i], sizes[order[i]]) for i in range(n)]


def draw_packages(rng, kind: str, n_names: int, k: int, zipf_s: float,
                  rank_perm: np.ndarray, pre_share: float) -> list[tuple[str, str]]:
    """``k`` distinct names, Zipf(``zipf_s``)-skewed over ``n_names`` name
    slots (a fixed permutation maps popularity rank to name), each with a
    version drawn as ``make_sbom`` draws them."""
    eco = KINDS[kind][1]
    ranks = np.arange(1, n_names + 1, dtype=np.float64)
    p = ranks ** -zipf_s
    p /= p.sum()
    chosen: list[int] = []
    seen: set[int] = set()
    while len(chosen) < k:
        for r in rng.choice(n_names, size=2 * (k - len(chosen)), p=p).tolist():
            if r not in seen:
                seen.add(r)
                chosen.append(r)
                if len(chosen) == k:
                    break
    ver = rng.integers(0, 10, size=(k, 3))
    pre = rng.random(k) < pre_share
    out = []
    for j, r in enumerate(chosen):
        core = f"{ver[j, 0]}.{ver[j, 1]}.{ver[j, 2]}"
        v = _prerelease(kind, core) if pre[j] else core
        out.append((pkg_name(eco, int(rank_perm[r])), v))
    return out


def lockfile_pool(root: str, traffic: dict, seed: int, names: dict) -> list[dict]:
    """The seed's pool, made under ``root``:
    ``[{"dir", "kind", "file", "packages": [[name, version], ...]}, ...]``.
    One request of ``pkgs_max`` packages per kind in the traffic's
    ``warmup`` list follows the pool, for the clients' warm-up only."""
    # the order of kinds and sizes is the same for every seed (a window
    # holds only part of the pool); the seed chooses names and versions
    plan = pool_plan(traffic)
    kinds = sorted(set(traffic["mix"]) | set(traffic.get("warmup", [])))
    plan += [(k, int(traffic["pkgs_max"])) for k in traffic.get("warmup", [])]
    # name slots: 1.2x the DB's names, so about one in six has no advisory
    slots = {k: int(names[KINDS[k][1]] * 1.2) for k in kinds}
    perms = {k: np.random.default_rng([seed, 3, i]).permutation(slots[k])
             for i, k in enumerate(kinds)}
    pool = []
    for i, (kind, n) in enumerate(plan):
        d = os.path.join(root, f"req{i:04d}")
        os.makedirs(d, exist_ok=True)
        pkgs = draw_packages(np.random.default_rng([seed, 4, i]), kind,
                             slots[kind], n, float(traffic["zipf_s"]),
                             perms[kind], float(traffic["prerelease_share"]))
        fname = KINDS[kind][0]
        write_lockfile(kind, os.path.join(d, fname), pkgs)
        pool.append({"dir": d, "kind": kind, "file": fname,
                     "packages": [list(p) for p in pkgs]})
    return pool
