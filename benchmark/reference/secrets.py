"""Plain reference for `trivy fs --scanners secret`: Trivy's secret scan
semantics (pkg/fanal/secret/scanner.go, pkg/fanal/analyzer/secret), written
straight from that description with Python's `re`, over the benchmark's own
copy of the 113 built-in rules (`secret_rules.json`). It imports nothing of
the program.

Per file: the analyzer's eligibility (size, skipped names, directories and
extensions, binary sniff, global allow paths), then per rule: path match,
per-rule allow paths, keyword test on the ASCII-lowered content, every
regex match (the ``secret`` group where the rule has one), exclude blocks,
allow regexes on the secret text. All secret spans of a file are censored
with ``*`` together; a finding is (rule, first line, last line, the
censored first line, cut to a 100-character window starting 20 characters
before the secret when the line is longer).
"""

from __future__ import annotations

import bisect
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))

SKIP_FILES = {"go.mod", "go.sum", "package-lock.json", "yarn.lock",
              "pnpm-lock.yaml", "Pipfile.lock", "Gemfile.lock"}
SKIP_DIRS = {".git", "node_modules"}
SKIP_EXTS = {".jpg", ".png", ".gif", ".doc", ".pdf", ".bin", ".svg", ".socket",
             ".deb", ".rpm", ".zip", ".gz", ".gzip", ".tar"}
ALLOWED_BINARIES = {".pyc"}
LINE_WIDTH = 100
_LOWER = bytes(c + 32 if 65 <= c <= 90 else c for c in range(256))


def _is_binary(head: bytes) -> bool:
    return any(b < 7 or 13 < b < 27 or 27 < b < 32 or b == 127 for b in head[:300])


def _printable_runs(data: bytes) -> bytes:
    """strings(1): runs of at least 4 printable bytes (or tabs), one per line."""
    return b"".join(m.group(0) + b"\n" for m in re.finditer(rb"[\t\x20-\x7e]{4,}", data))


class SecretReference:
    def __init__(self, path: str = os.path.join(HERE, "secret_rules.json")):
        with open(path) as f:
            doc = json.load(f)
        self.global_allow_paths = [re.compile(a["path"]) for a in doc["global_allow_rules"] if a.get("path")]
        self.global_allow_res = [re.compile(a["regex"]) for a in doc["global_allow_rules"] if a.get("regex")]
        self.rules = []
        for r in doc["rules"]:
            self.rules.append({
                "id": r["id"],
                "re": re.compile(r["regex"]),
                "group": r["secret_group_name"],
                "keywords": [k.translate(str.maketrans("ABCDEFGHIJKLMNOPQRSTUVWXYZ", "abcdefghijklmnopqrstuvwxyz"))
                             for k in r["keywords"]],
                "path": re.compile(r["path"]) if r["path"] else None,
                "allow_paths": [re.compile(a["path"]) for a in r["allow_rules"] if a.get("path")],
                "allow_res": [re.compile(a["regex"]) for a in r["allow_rules"] if a.get("regex")],
                "blocks": [re.compile(b) for b in r["exclude_blocks"]],
            })

    def content(self, rel: str, data: bytes) -> str | None:
        """The text the scan sees for this file, or None where the file is
        not scanned for secrets."""
        if len(data) < 10:
            return None
        parts = rel.split("/")
        if any(p in SKIP_DIRS for p in parts[:-1]) or parts[-1] in SKIP_FILES:
            return None
        ext = os.path.splitext(parts[-1])[1]
        if ext in SKIP_EXTS or any(p.search(rel) for p in self.global_allow_paths):
            return None
        if _is_binary(data[:300]):
            if ext not in ALLOWED_BINARIES:
                return None
            data = _printable_runs(data)
        else:
            data = data.replace(b"\r", b"")
        return data.decode("latin-1")

    def scan(self, rel: str, data: bytes) -> list[tuple]:
        """``[(rule_id, start_line, end_line, match), ...]`` for one file."""
        text = self.content(rel, data)
        if text is None:
            return []
        lower = text.encode("latin-1").translate(_LOWER).decode("latin-1")
        hits = []
        for rule in self.rules:
            if rule["path"] is not None and not rule["path"].search(rel):
                continue
            if any(p.search(rel) for p in rule["allow_paths"]):
                continue
            if rule["keywords"] and not any(k in lower for k in rule["keywords"]):
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
