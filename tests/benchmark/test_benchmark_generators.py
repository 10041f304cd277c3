"""The benchmark's generators give the same bytes for the same seed and
other bytes for another, the same amount of work for every seed, and
lockfiles that the program's parsers read as written."""

import hashlib
import json
import os

import pytest

from benchmark.traffic import advisories, lockfiles, trees

SEED = 2**31 + 12345  # the driver's seeds are large


def _digest(root):
    h = hashlib.sha256()
    for d, _dirs, names in sorted(os.walk(root)):
        for n in sorted(names):
            p = os.path.join(d, n)
            h.update(os.path.relpath(p, root).encode())
            with open(p, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def test_make_tree_is_a_function_of_its_seed(tmp_path):
    a = trees.make_tree(str(tmp_path / "a"), [SEED, 1], 1 << 20)
    b = trees.make_tree(str(tmp_path / "b"), [SEED, 1], 1 << 20)
    c = trees.make_tree(str(tmp_path / "c"), [SEED + 1, 1], 1 << 20)
    assert _digest(tmp_path / "a") == _digest(tmp_path / "b")
    assert _digest(tmp_path / "a") != _digest(tmp_path / "c")
    assert a == b and a["bytes"] >= 1 << 20 and a["files"] > 48


def test_tree_pool_sizes_do_not_depend_on_the_seed(tmp_path):
    traffic = {"pool": 3, "mib_min": 2, "mib_max": 4}
    p1 = trees.tree_pool(str(tmp_path / "s1"), traffic, 1)
    p2 = trees.tree_pool(str(tmp_path / "s2"), traffic, 2)
    want = trees.pool_sizes(traffic)
    for pool in (p1, p2):
        got = sorted(r["bytes"] for r in pool)
        assert all(w <= g <= w + (1 << 20) for w, g in zip(want, got)), (want, got)
    # made anew from the seed alone: the same pool again
    again = trees.tree_pool(str(tmp_path / "s1b"), traffic, 1)
    assert [dict(r, path=None) for r in again] == [dict(r, path=None) for r in p1]


def test_make_db_is_a_function_of_its_seed(tmp_path):
    b1, n1 = advisories.make_db(str(tmp_path / "db1"), [SEED, 7])
    b2, _ = advisories.advisories([SEED, 7])
    b3, _ = advisories.advisories([SEED + 1, 7])
    assert b1 == b2 and b1 != b3
    assert sum(len(a) for rows in b1.values() for a in rows.values()) == 106_000
    with open(tmp_path / "db1" / "manifest.json") as f:
        assert len(json.load(f)["buckets"]) == 10
    assert n1["npm"] == 15_000


def test_make_sbom_is_a_function_of_its_seed(tmp_path):
    _b, names = advisories.advisories(1)
    for i, s in enumerate((SEED, SEED, SEED + 1)):
        advisories.make_sbom(str(tmp_path / f"bom{i}.json"), s, names, 500)
    read = [(tmp_path / f"bom{i}.json").read_bytes() for i in range(3)]
    assert read[0] == read[1] != read[2]
    assert len(json.loads(read[0])["components"]) == 500


@pytest.mark.parametrize("mix", [{"npm": 0.5, "gomod": 0.3, "cargo": 0.1, "composer": 0.1},
                                 {"poetry": 0.7, "bundler": 0.3}])
def test_lockfile_pool_same_work_for_every_seed(tmp_path, mix):
    traffic = {"pool": 20, "pkgs_min": 5, "pkgs_max": 40, "zipf_s": 1.0, "warmup": ["npm"],
               "prerelease_share": 0.05, "mix": mix}
    _b, names = advisories.advisories(1)
    a = lockfiles.lockfile_pool(str(tmp_path / "a"), traffic, SEED, names)
    b = lockfiles.lockfile_pool(str(tmp_path / "b"), traffic, SEED, names)
    c = lockfiles.lockfile_pool(str(tmp_path / "c"), traffic, SEED + 1, names)
    strip = lambda pool: [(r["kind"], r["packages"]) for r in pool]  # noqa: E731
    assert strip(a) == strip(b) != strip(c)
    shape = lambda pool: sorted((r["kind"], len(r["packages"])) for r in pool)  # noqa: E731
    assert shape(a) == shape(c)
    counts = {k: sum(r["kind"] == k for r in a[:20]) for k in mix}
    assert a[20]["kind"] == "npm" and len(a[20]["packages"]) == 40  # the warm-up request
    assert counts == {k: round(20 * s) for k, s in mix.items()}


@pytest.mark.parametrize("kind", sorted(lockfiles.KINDS))
def test_lockfile_writer_matches_the_programs_parser(tmp_path, kind):
    from trivy_tpu.dependency import parsers

    parse = {"npm": parsers.parse_npm_lock, "gomod": parsers.parse_gomod,
             "cargo": parsers.parse_cargo_lock, "composer": parsers.parse_composer_lock,
             "poetry": parsers.parse_poetry_lock, "bundler": parsers.parse_gemfile_lock}[kind]
    import numpy as np

    eco = lockfiles.KINDS[kind][1]
    pkgs = lockfiles.draw_packages(np.random.default_rng(5), kind, 300, 60, 1.0,
                                   np.arange(300), 0.3)
    path = tmp_path / lockfiles.KINDS[kind][0]
    lockfiles.write_lockfile(kind, str(path), pkgs)
    got = sorted((p.name, p.version) for p in parse(path.read_bytes()) if p.version)
    assert got == sorted(pkgs)
    assert len({n for n, _v in pkgs}) == 60
    assert all(n.startswith(("github.com/acme", f"{eco}-pkg-", "acme")) for n, _v in pkgs)
