"""Plain reference for `trivy fs --scanners license --license-full`, over
the benchmark's own copy of the license corpus (`license_corpus.json`). It
imports nothing of the program, and works on words and word 5-grams as
tuples of strings, where the program hashes them.

Which files: canonical license files (LICENSE, COPYING, NOTICE and their
variants, up to 512 KiB) whole, and the first 4 KiB of source files.
Text is the file's bytes read as UTF-8 (bad bytes replaced), then as
Latin-1 bytes (characters beyond it become ``?``). Words are runs of bytes
other than whitespace, control bytes and `` "'(),.;:!?``, lowercased.

Scoring, for every license of the corpus:

- full-text lane: the 5-grams of the license's full text, each weighted by
  one over the number of licenses whose texts hold it; the confidence is
  the weight of those the file holds over the weight of all;
- phrase lane: each fingerprint phrase of five words or more counts as its
  distinct 5-grams, a shorter one as its whole text found in the file's
  lowercased, space-collapsed text; the confidence is the share found;
- a license is found at the larger of the two, when that is at least the
  corpus's threshold (0.9). A license found whole (0.999 or more) hides the
  licenses it subsumes. Of the rest, only those whose confidence rounds
  (three decimals) to the best are reported, best first by matched
  full-text weight, then by name, and one per family (licenses whose
  weighted full-text 5-grams overlap by 80% or more either way).
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

LICENSE_STEMS = {"license", "licence", "copying", "copyright", "notice",
                 "unlicense", "licenses", "licences"}
LICENSE_EXTS = {"", ".txt", ".md", ".rst", ".html"}
HEADER_EXTS = {".c", ".h", ".cc", ".cpp", ".hpp", ".go", ".py", ".js", ".ts",
               ".java", ".rb", ".rs", ".sh", ".swift", ".kt", ".scala", ".cs",
               ".m", ".mm"}
MAX_LICENSE_BYTES = 512 << 10
HEADER_BYTES = 4 << 10
NGRAM = 5


def _word_table() -> bytes:
    out = bytearray(256)
    for b in range(256):
        ch = chr(b)
        if ch in " \"'(),.;:!?" or ch.isspace() or b < 32:
            out[b] = 32
        else:
            out[b] = ord(ch.lower()[0])
    return bytes(out)


_TABLE = _word_table()


def words(text: str) -> list[str]:
    raw = text.encode("latin-1", "replace").translate(_TABLE)
    return raw.decode("latin-1").split(" ") if raw else []


def grams(ws: list[str]) -> set:
    ws = [w for w in ws if w]
    return {tuple(ws[i:i + NGRAM]) for i in range(len(ws) - NGRAM + 1)}


def license_kind(rel: str, size: int) -> str | None:
    """"license-file", "header" or None, as the analyzers choose files."""
    base = os.path.basename(rel).lower()
    stem, ext = os.path.splitext(base)
    if size <= MAX_LICENSE_BYTES:
        if ext in LICENSE_EXTS and stem in LICENSE_STEMS:
            return "license-file"
        if ext not in HEADER_EXTS and any(
            base.startswith(s + "-") or base.startswith(s + ".")
            for s in ("license", "licence", "copying")
        ):
            return "license-file"
    if size > 0 and os.path.splitext(rel)[1].lower() in HEADER_EXTS:
        return "header"
    return None


class LicenseReference:
    def __init__(self, path: str = os.path.join(HERE, "license_corpus.json"),
                 accumulate=None):
        """``accumulate`` sums a list of weights; the default is exact
        float64 addition. The control passes a lower-precision one."""
        with open(path) as f:
            doc = json.load(f)
        self.threshold = float(doc["min_confidence"])
        self.subsumes = doc["subsumes"]
        self.accumulate = accumulate or (lambda ws: float(sum(ws)))
        self.licenses = sorted(doc["fingerprints"])
        texts = doc["full_texts"]
        full = {lic: grams(words(texts[lic])) for lic in self.licenses if lic in texts}
        df: dict = {}
        for gs in full.values():
            for g in gs:
                df[g] = df.get(g, 0) + 1
        self.full = {lic: {g: 1.0 / df[g] for g in gs} for lic, gs in full.items()}
        self.full_total = {lic: self.accumulate(list(w.values())) for lic, w in self.full.items()}
        self.phrase_grams, self.short = {}, {}
        for lic in self.licenses:
            pg, short = set(), []
            for ph in doc["fingerprints"][lic]:
                pw = [w for w in ph.translate(str.maketrans(" \"'(),.;:!?", " " * 11)).split(" ") if w]
                if len(pw) < NGRAM:
                    short.append(ph)
                else:
                    pg |= grams(words(ph))
            self.phrase_grams[lic], self.short[lic] = pg, short
        # families: weighted full-text overlap of 80% or more either way
        fam = {lic: {lic} for lic in self.full}
        names = sorted(self.full)
        for i, a in enumerate(names):
            for b in names[i + 1:]:
                shared = self.full[a].keys() & self.full[b].keys()
                ov_a = sum(self.full[a][g] for g in shared) / self.full_total[a] if self.full_total[a] else 0
                ov_b = sum(self.full[b][g] for g in shared) / self.full_total[b] if self.full_total[b] else 0
                if ov_a >= 0.8 or ov_b >= 0.8:
                    merged = fam[a] | fam[b]
                    for x in merged:
                        fam[x] = merged
        self.family = {lic: min(f) for lic, f in fam.items()}

    def classify(self, text: str) -> list[tuple[str, float]]:
        """``[(name, confidence), ...]`` as the report gives them."""
        tg = grams(words(text))
        norm = " ".join(text.lower().split())
        found = []
        for lic in self.licenses:
            matched_w = 0.0
            full_conf = 0.0
            if lic in self.full and self.full_total[lic] > 0:
                w = self.full[lic]
                matched_w = self.accumulate([w[g] for g in sorted(w.keys() & tg)])
                full_conf = matched_w / self.full_total[lic]
            n_units = len(self.phrase_grams[lic]) + len(self.short[lic])
            phrase_conf = 0.0
            if n_units:
                got = len(self.phrase_grams[lic] & tg) + sum(ph in norm for ph in self.short[lic])
                phrase_conf = got / n_units
            conf = max(full_conf, phrase_conf)
            if conf >= self.threshold:
                found.append((conf, matched_w, lic))
        whole = {name for conf, _w, name in found if conf >= 0.999}
        hidden = {s for name in whole for s in self.subsumes.get(name, [])}
        found = sorted((f for f in found if f[2] not in hidden),
                       key=lambda x: (-round(x[0], 3), -x[1], x[2]))
        out, families = [], set()
        for conf, _w, name in found:
            if round(conf, 3) < round(found[0][0], 3):
                break
            f = self.family.get(name, name)
            if f in families:
                continue
            families.add(f)
            out.append((name, round(conf, 3)))
        return out

    def scan(self, rel: str, data: bytes) -> tuple[str, list] | None:
        """``(kind, findings)`` for one file, or None where no analyzer
        takes it."""
        kind = license_kind(rel, len(data))
        if kind is None:
            return None
        raw = data if kind == "license-file" else data[:HEADER_BYTES]
        return kind, self.classify(raw.decode("utf-8", "replace"))
