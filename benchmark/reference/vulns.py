"""Plain reference for `trivy fs --scanners vuln` against an advisory DB:
which (package, version, advisory) triples are vulnerable, and the fixed
version shown. It imports nothing of the program; the advisories come from
the benchmark's generator (traffic/advisories.py), the packages from the
lockfiles it wrote.

A package is vulnerable to an advisory of its ecosystem and name when its
version satisfies one of the advisory's vulnerable ranges (an AND of
``<op><version>`` terms). The fixed version is the smallest patched
version above the installed one. Versions order by each ecosystem's own
rules, written here from their specifications:

- SemVer 2.0.0 section 11 (npm, Go modules, Cargo, Composer): numeric
  major.minor.patch, then a release above any of its pre-releases, whose
  dot-separated identifiers compare numerically when both are numbers,
  numbers below text, text in ASCII order, a shorter list first;
- PEP 440 (pip): release segments, then a pre-release (a < b < rc, then
  its number) below the release;
- RubyGems ``Gem::Version`` (bundler): ``-`` reads as ``.pre.``, segments
  are digit runs or letter runs, a letter segment sorts below any number
  and makes the version a pre-release, trailing zero segments drop.

Node-semver's rule that a pre-release satisfies a range only when a bound
of the range is a pre-release of the same x.y.z, and PEP 440's exclusion of
pre-releases from specifiers, are not applied: the program states plain
ordering for both (PERF.md, Open questions).
"""

from __future__ import annotations

import functools
import re

SCHEMES = {"npm": "semver", "go": "semver", "cargo": "semver",
           "composer": "semver", "pip": "pep440", "rubygems": "gem"}


def _semver_key(v: str):
    v = v.strip()
    if v[:1] in "vV":
        v = v[1:]
    v = v.split("+", 1)[0]
    core, _, pre = v.partition("-")
    nums = [int(x) for x in core.split(".")]
    nums += [0] * (3 - len(nums))
    if not pre:
        return (tuple(nums), (1,))
    ids = []
    for ident in pre.split("."):
        ids.append((0, int(ident), "") if ident.isdigit() else (1, 0, ident))
    return (tuple(nums), (0, tuple(ids)))


_PEP440 = re.compile(r"^v?(\d+(?:\.\d+)*)(?:[-_.]?(a|alpha|b|beta|c|rc|pre|preview)[-_.]?(\d*))?$")
_PRE_ORDER = {"a": 0, "alpha": 0, "b": 1, "beta": 1, "c": 2, "rc": 2, "pre": 2, "preview": 2}


def _pep440_key(v: str):
    m = _PEP440.match(v.strip().lower())
    if not m:
        raise ValueError(f"not a PEP 440 version this reference reads: {v!r}")
    rel = [int(x) for x in m.group(1).split(".")]
    while len(rel) > 1 and rel[-1] == 0:
        rel.pop()
    pre = (1, 0, 0) if m.group(2) is None else (0, _PRE_ORDER[m.group(2)], int(m.group(3) or 0))
    return (tuple(rel), pre)


def _gem_segments(v: str) -> list:
    segs = [int(s) if s.isdigit() else s
            for s in re.findall(r"[0-9]+|[a-z]+", v.strip().replace("-", ".pre."), re.I)]
    while segs and segs[-1] == 0:
        segs.pop()
    return segs


def _gem_cmp(a: str, b: str) -> int:
    sa, sb = _gem_segments(a), _gem_segments(b)
    for i in range(max(len(sa), len(sb))):
        x = sa[i] if i < len(sa) else 0
        y = sb[i] if i < len(sb) else 0
        if x == y:
            continue
        if isinstance(x, str) and isinstance(y, str):
            return -1 if x < y else 1
        if isinstance(x, str):
            return -1
        if isinstance(y, str):
            return 1
        return -1 if x < y else 1
    return 0


def compare(scheme: str, a: str, b: str) -> int:
    if scheme == "gem":
        return _gem_cmp(a, b)
    key = _pep440_key if scheme == "pep440" else _semver_key
    ka, kb = key(a), key(b)
    return (ka > kb) - (ka < kb)


_TERM = re.compile(r"^\s*(>=|<=|>|<|==|=)?\s*(\S+)\s*$")


def satisfies(scheme: str, version: str, ranges: list[str], cmp=compare) -> bool:
    for rng in ranges:
        ok = True
        for term in rng.split(","):
            m = _TERM.match(term)
            op, bound = m.group(1) or "=", m.group(2)
            c = cmp(scheme, version, bound)
            ok = {">=": c >= 0, "<=": c <= 0, ">": c > 0, "<": c < 0,
                  "=": c == 0, "==": c == 0}[op]
            if not ok:
                break
        if ok:
            return True
    return False


def fixed_version(scheme: str, version: str, patched: list[str], cmp=compare) -> str:
    ups = [p for p in patched if cmp(scheme, p, version) > 0]
    if not ups:
        return ", ".join(patched)
    return min(ups, key=functools.cmp_to_key(lambda a, b: cmp(scheme, a, b)))


def normalize_name(eco: str, name: str) -> str:
    if eco == "pip":  # PEP 503
        return re.sub(r"[-_.]+", "-", name).lower()
    return name


class VulnReference:
    """Index the generator's advisories by (ecosystem, name)."""

    def __init__(self, buckets: dict, cmp=compare):
        """``cmp`` orders versions; the control passes a coarser one."""
        self.cmp = cmp
        self.index: dict[tuple[str, str], list] = {}
        for bucket, rows in buckets.items():
            eco = bucket.split("::", 1)[0]
            for name, advs in rows.items():
                self.index.setdefault((eco, normalize_name(eco, name)), []).extend(advs)

    def vulnerable(self, eco: str, pkgs: list[tuple[str, str]]) -> set:
        """``{(name, version, advisory id, fixed version)}``."""
        scheme = SCHEMES[eco]
        out = set()
        for name, version in pkgs:
            for adv in self.index.get((eco, normalize_name(eco, name)), ()):
                if satisfies(scheme, version, adv["VulnerableVersions"], self.cmp):
                    out.add((name, version, adv["VulnerabilityID"],
                             fixed_version(scheme, version, adv["PatchedVersions"], self.cmp)))
        return out
