"""An organisation's `trivy-secret.yaml`: thousands of custom rules for
internal token formats, added to Trivy's built-in rules (Trivy's secret
docs, "Configuration": custom ``rules:`` are appended to the built-ins,
each with ``id``, ``category``, ``title``, ``severity``, ``regex``,
``keywords``, ``secret-group-name``, ``allow-rules`` and ``path``).

``generate`` makes the custom rules from a rules seed, in the shapes of
the built-in families (``SHAPES``), each with a maker of a valid secret
for planting and its keywords for decoys. ``merged_document`` is the
effective ruleset in the schema of ``secret_rules.json`` (built-ins first,
then the custom rules), which ``secrets.SecretReference`` reads;
``write_config`` writes the `trivy-secret.yaml` the program is given (JSON
is YAML, so no YAML library is needed). ``RulesReference`` is
``SecretReference`` with one shortcut that keeps its answers: a rule's
keyword test looks only for keywords whose first four bytes occur in the
file. With ``fold=False`` it is the control: the keyword test made
case-sensitive (Trivy lowers the content, A-Z only). Nothing here imports
the program.
"""

from __future__ import annotations

import bisect
import json
import os
import random

from benchmark.reference.secrets import LINE_WIDTH, SecretReference, _LOWER

HERE = os.path.dirname(os.path.abspath(__file__))
BUILTIN = os.path.join(HERE, "secret_rules.json")

# share of the custom rules in each shape (the built-in families)
SHAPES = (
    ("vendor", 0.70),      # vendor-prefixed tokens, keyword = the prefix
    ("assign", 0.20),      # <name>_key = "...", case-insensitive, keyword = name
    ("multi", 0.05),       # 2-8 literal prefixes, as aws-access-key-id
    ("pem", 0.03),         # multi-line armoured blocks
    ("bare", 0.02),        # anchored, no keywords: never prefiltered
)
CLASSES = (
    ("[0-9a-zA-Z]", "0123456789abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"),
    ("[0-9a-f]", "0123456789abcdef"),
    ("[A-Z0-9]", "ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789"),
    ("[0-9a-z]", "0123456789abcdefghijklmnopqrstuvwxyz"),
    ("[a-zA-Z0-9_\\-]", "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_-"),
)
_LOWERCASE = "abcdefghijklmnopqrstuvwxyz"
_UPPERCASE = _LOWERCASE.upper()
_DIGITS = "0123456789"
SEVERITIES = ("CRITICAL", "HIGH", "MEDIUM", "LOW")


class CustomRule:
    """One generated rule: its document (the ``secret_rules.json`` schema),
    a maker of a valid secret line, and its keywords."""

    def __init__(self, doc: dict, make_secret, names: list[str]):
        self.doc = doc
        self.make_secret = make_secret
        self.names = names  # identifiers a decoy mentions without a secret

    @property
    def id(self) -> str:
        return self.doc["id"]


def _word(rng: random.Random, lo: int, hi: int, alphabet: str = _LOWERCASE) -> str:
    return "".join(rng.choice(alphabet) for _ in range(rng.randint(lo, hi)))


def _body(rng: random.Random, chars: str, n: int) -> str:
    return "".join(rng.choice(chars) for _ in range(n))


def generate(n: int, rules_seed: int) -> list[CustomRule]:
    """``n`` custom rules from ``rules_seed``: the same seed gives the same
    rules, in the same order, with the same ids."""
    rng = random.Random(f"custom-rules:{rules_seed}")
    counts = [int(round(share * n)) for _, share in SHAPES]
    counts[0] += n - sum(counts)
    taken: set[str] = set()

    def fresh(make) -> str:
        while True:
            w = make()
            if w not in taken:
                taken.add(w)
                return w

    out: list[CustomRule] = []
    k = 0
    for (shape, _), count in zip(SHAPES, counts):
        for _ in range(count):
            k += 1
            rid = f"org-{shape}-{k:05d}"
            out.append(_make(shape, rid, rng, fresh))
    # about one rule in ten carries an allow rule or a path restriction
    for rule in out:
        roll = rng.random()
        if roll < 0.05:
            rule.doc["allow_rules"] = [{"id": f"{rule.id}-example", "path": None,
                                        "regex": "EXAMPLE|PLACEHOLDER"}]
        elif roll < 0.08:
            rule.doc["allow_rules"] = [{"id": f"{rule.id}-fixtures", "path": r"(?:^|/)fixtures?/",
                                        "regex": None}]
        elif roll < 0.10:
            rule.doc["path"] = r"\.(?:py|go|js|ts|java|rb|rs|c)$"
    return out


def _make(shape: str, rid: str, rng: random.Random, fresh) -> CustomRule:
    doc = {"id": rid, "category": "Internal", "title": f"Internal token {rid}",
           "severity": rng.choice(SEVERITIES), "regex": "", "keywords": [],
           "path": None, "secret_group_name": "secret", "allow_rules": [],
           "exclude_blocks": []}
    if shape == "vendor":
        # a distinct 4-8-byte prefix with a digit in it, as token prefixes
        # are (ghp_, glpat-, xoxb-): letters then a digit then letters
        prefix = fresh(lambda: _word(rng, 2, 4) + rng.choice(_DIGITS) + _word(rng, 1, 3))
        sep = rng.choice(("", "_", "-"))
        cls, chars = rng.choice(CLASSES)
        n = rng.randint(20, 48)
        doc["regex"] = (f"(?:^|[^0-9a-zA-Z])(?P<secret>{prefix}{sep}"
                        f"{cls}{{{n}}})")
        doc["keywords"] = [prefix]

        def make(r, prefix=prefix, sep=sep, chars=chars, n=n):
            return f'token = "{prefix}{sep}{_body(r, chars, n)}"'

        return CustomRule(doc, make, [prefix])
    if shape == "assign":
        # an internal service name, as it appears in code
        name = fresh(lambda: _word(rng, 6, 10))
        n = rng.randint(20, 40)
        doc["regex"] = (f"(?i){name}[_\\-]?(?:key|token|secret)[\\s:=\"']{{1,6}}"
                        f"(?P<secret>[0-9a-zA-Z]{{{n}}})")
        doc["keywords"] = [name]

        def make(r, name=name, n=n):
            nm = name.upper() if r.random() < 0.5 else name
            kind = r.choice(("KEY", "TOKEN", "SECRET"))
            if nm == name:
                kind = kind.lower()
            return f'{nm}_{kind} = "{_body(r, CLASSES[0][1], n)}"'

        return CustomRule(doc, make, [name])
    if shape == "multi":
        prefixes = [fresh(lambda: _word(rng, 3, 3, _UPPERCASE) + rng.choice(_DIGITS))
                    for _ in range(rng.randint(2, 8))]
        alt = "|".join(prefixes)
        doc["regex"] = f"(?:^|[^0-9a-zA-Z])(?P<secret>(?:{alt})[0-9A-Z]{{16}})"
        doc["keywords"] = list(prefixes)

        def make(r, prefixes=prefixes):
            return f"key_id: {r.choice(prefixes)}{_body(r, CLASSES[2][1], 16)}"

        return CustomRule(doc, make, list(prefixes))
    if shape == "pem":
        label = fresh(lambda: _word(rng, 4, 8, _UPPERCASE))
        head = f"-----BEGIN {label} PRIVATE KEY-----"
        doc["regex"] = (f"{head}(?P<secret>[\\s\\S]*?)"
                        f"-----END {label} PRIVATE KEY-----")
        doc["keywords"] = [f"BEGIN {label}"]

        def make(r, head=head, label=label):
            lines = [_body(r, CLASSES[0][1], 64) for _ in range(r.randint(2, 5))]
            return "\n".join([head] + lines + [f"-----END {label} PRIVATE KEY-----"])

        return CustomRule(doc, make, [f"BEGIN {label}"])
    # bare: a literal prefix and a hex body, no keywords
    prefix = fresh(lambda: _word(rng, 3, 5) + rng.choice(_DIGITS) + "_live_")
    n = rng.randint(24, 40)
    doc["regex"] = f"(?P<secret>{prefix}[0-9a-f]{{{n}}})"

    def make(r, prefix=prefix, n=n):
        return f"secret: {prefix}{_body(r, CLASSES[1][1], n)}"

    return CustomRule(doc, make, [])


def merged_document(custom: list[CustomRule], builtin_path: str = BUILTIN) -> dict:
    """The effective ruleset: the built-in rules, then the custom rules
    (Trivy appends custom rules to the built-ins), and the built-in global
    allow rules."""
    with open(builtin_path) as f:
        doc = json.load(f)
    ids = {r["id"] for r in doc["rules"]}
    extra = [r.doc for r in custom]
    clash = ids & {r["id"] for r in extra}
    if clash:
        raise ValueError(f"custom rule ids clash with built-ins: {sorted(clash)[:3]}")
    return {"source": "built-in rules and generated custom rules",
            "rules": doc["rules"] + extra,
            "global_allow_rules": doc["global_allow_rules"]}


def config_document(custom: list[CustomRule]) -> dict:
    """The `trivy-secret.yaml` for the custom rules, in Trivy's keys."""
    rules = []
    for r in custom:
        d = r.doc
        rule = {"id": d["id"], "category": d["category"], "title": d["title"],
                "severity": d["severity"], "regex": d["regex"],
                "keywords": d["keywords"], "secret-group-name": d["secret_group_name"]}
        if d["path"]:
            rule["path"] = d["path"]
        if d["allow_rules"]:
            rule["allow-rules"] = [{k: v for k, v in a.items() if v is not None}
                                   for a in d["allow_rules"]]
        rules.append(rule)
    return {"rules": rules}


def write_config(path: str, custom: list[CustomRule]) -> None:
    with open(path, "w") as f:
        json.dump(config_document(custom), f, indent=0)


class RulesReference(SecretReference):
    """``SecretReference`` over a merged document, with the keyword test
    narrowed first by the file's 4-byte grams (a keyword whose first four
    bytes do not occur cannot occur); ``fold=False`` drops the ASCII fold
    of the content in the keyword test (the control)."""

    def __init__(self, path: str, fold: bool = True):
        super().__init__(path)
        self.fold = fold

    def scan(self, rel: str, data: bytes) -> list[tuple]:
        text = self.content(rel, data)
        if text is None:
            return []
        raw = text.encode("latin-1")
        lower = (raw.translate(_LOWER) if self.fold else raw).decode("latin-1")
        grams = _grams(lower)
        hits = []
        for rule in self.rules:
            if rule["path"] is not None and not rule["path"].search(rel):
                continue
            if any(p.search(rel) for p in rule["allow_paths"]):
                continue
            if rule["keywords"] and not any(
                    (len(k) < 4 or _code(k) in grams) and k in lower for k in rule["keywords"]):
                continue
            spans = []
            for m in rule["re"].finditer(text):
                g = rule["group"]
                s, e = m.span(g) if g and g in rule["re"].groupindex else m.span()
                if s >= 0 and s != e:
                    spans.append((s, e))
            if not spans:
                continue
            blocks = [m.span() for b in rule["blocks"] for m in b.finditer(text)]
            allow = rule["allow_res"] + self.global_allow_res
            for s, e in spans:
                if any(bs <= s and e <= be for bs, be in blocks):
                    continue
                if any(a.search(text[s:e]) for a in allow):
                    continue
                if (rule["id"], s, e) not in hits:
                    hits.append((rule["id"], s, e))
        return _censor(text, hits)


def _code(s: str) -> int:
    """A text's first four bytes as one little-endian number."""
    return int.from_bytes(s[:4].encode("latin-1", "replace"), "little")


def _grams(lower: str) -> set[int]:
    """The distinct 4-byte grams of a text, as ``_code`` numbers them."""
    import numpy as np

    b = np.frombuffer(lower.encode("latin-1"), dtype=np.uint8).astype(np.uint32)
    if len(b) < 4:
        return set()
    codes = b[:-3] | (b[1:-2] << 8) | (b[2:-1] << 16) | (b[3:] << 24)
    return set(np.unique(codes).tolist())


def _censor(text: str, hits: list) -> list[tuple]:
    """Findings of ``hits`` as ``SecretReference.scan`` renders them."""
    if not hits:
        return []
    buf = bytearray(text.encode("latin-1"))
    for _rid, s, e in hits:
        for i in range(s, min(e, len(buf))):
            if buf[i] != 10:
                buf[i] = 42
    censored = buf.decode("latin-1").split("\n")
    starts = [0]
    for ln in text.split("\n")[:-1]:
        starts.append(starts[-1] + len(ln) + 1)
    out = []
    for rid, s, e in hits:
        li = bisect.bisect_right(starts, s) - 1
        lj = bisect.bisect_right(starts, max(s, e - 1)) - 1
        line = censored[li]
        if len(line) > LINE_WIDTH:
            begin = max(0, min(s - starts[li] - 20, len(line) - LINE_WIDTH))
            line = line[begin:begin + LINE_WIDTH]
        out.append((rid, li + 1, lj + 1, line))
    return sorted(out)
