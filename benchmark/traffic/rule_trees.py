"""CI trees for a deployment with an org-wide custom ruleset: the
``fs_trees`` pool of ``trees.py`` (built-in secrets planted as there),
then, per repository and from the run's seed, one valid custom-rule
secret planted per ``custom_secret_every`` files of the tree, drawn
uniformly over the custom rules (at least one per repository), and
decoys: a ``decoy_share`` of the source files gets one line that names
``decoy_keywords`` (1-3) custom keywords (internal service names) with
no secret body. The ``fs_rule_trees`` traffic files parametrise it.
"""

from __future__ import annotations

import os
import random

from benchmark.traffic.trees import tree_pool

SOURCE_EXTS = (".py", ".go", ".js", ".c", ".java", ".rs", ".ts", ".rb", ".json", ".log")
BINARY_EXTS = (".so", ".o", ".wasm", ".pyc")
# decoy lines by the number of keywords they name
DECOYS = {
    1: ("import {0}", "    client = {0}.connect(host, port)"),
    2: ("// calls the {0} service, see {1}.proto", "    {0}.refresh({1}_cfg)"),
    3: ("# owner: {0} team ({1}, {2})",),
}


def _insert(data: bytes, line: str, rng: random.Random) -> bytes:
    at = data.find(b"\n", rng.randrange(len(data))) + 1 if data else 0
    return data[:at] + line.encode("latin-1") + b"\n" + data[at:]


def plant(repo: dict, custom: list, traffic: dict, seed: int, k: int) -> dict:
    """Plants custom secrets and decoys into one repository in place;
    returns it with ``planted`` and ``bytes`` brought up to date and the
    custom plants counted."""
    rng = random.Random(f"plant:{seed}:{k}")
    root = repo["path"]
    with_kw = [r for r in custom if r.names]
    every = float(traffic["custom_secret_every"])
    decoy_share = float(traffic["decoy_share"])
    lo, hi = traffic["decoy_keywords"]
    planted = set(repo.get("planted", []))
    added = custom_planted = 0
    files = [os.path.join(d, name) for d, _dirs, names in sorted(os.walk(root))
             for name in sorted(names)]
    text = [f for f in files if os.path.splitext(f)[1] not in BINARY_EXTS]
    # custom secrets land per ~``every`` files of the tree; one drawn on a
    # binary goes into the next text file in walk order
    secrets_in: dict[str, int] = {}
    due = 0
    for full in files:
        due += rng.random() < 1.0 / every
        if due and os.path.splitext(full)[1] not in BINARY_EXTS:
            secrets_in[full], due = due, 0
    if due or not secrets_in:
        at = text[-1] if due else text[rng.randrange(len(text))]
        secrets_in[at] = secrets_in.get(at, 0) + max(due, 1)
    sources = {f for f in text
               if os.path.splitext(f)[1] in SOURCE_EXTS
               and "third_party" not in os.path.relpath(f, root).split(os.sep)}
    for full in text:
        decoy = full in sources and with_kw and rng.random() < decoy_share
        if not decoy and full not in secrets_in:
            continue
        with open(full, "rb") as f:
            data = f.read()
        new = data
        if decoy:
            n = rng.randint(lo, hi)
            names_ = [rng.choice(rng.choice(with_kw).names) for _ in range(n)]
            new = _insert(new, rng.choice(DECOYS[n]).format(*names_), rng)
        for _ in range(secrets_in.get(full, 0)):
            rule = custom[rng.randrange(len(custom))]
            new = _insert(new, rule.make_secret(rng), rng)
            planted.add(os.path.relpath(full, root))
            custom_planted += 1
        with open(full, "wb") as f:
            f.write(new)
        added += len(new) - len(data)
    return dict(repo, planted=sorted(planted), bytes=repo["bytes"] + added,
                custom_planted=custom_planted)


def rules_pool(root: str, traffic: dict, seed: int, custom: list) -> list[dict]:
    """The seed's pool (``trees.tree_pool``) with the custom plantings."""
    pool = tree_pool(root, traffic, seed)
    return [plant(repo, custom, traffic, seed, k) for k, repo in enumerate(pool)]
