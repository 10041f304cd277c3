"""Compile secret rules into the tables of the device rule pass.

The TPU secret path is a two-stage design (the reference itself stages a
keyword prefilter before the regex, ref: pkg/fanal/secret/scanner.go:174-186,
377-463): the device evaluates every rule against every chunk and returns a
per-(chunk, rule) *hit bit*; the host then runs the exact regex engine
(`SecretScanner`) on just the flagged (file, rule) pairs. Device hits may
contain false positives (they only cost a cheap host confirmation) but must
never contain false negatives — that invariant is what makes the final
findings byte-identical to the CPU backend.

Each rule compiles into one of three lanes:

- **anchored lane**: the regex lowers to one or more *variants*, each an
  anchor literal (>= 3 bytes at a fixed offset from the match start) plus
  character-class window checks at fixed offsets. Constructs that won't
  lower (lookarounds, backrefs, optional/variable mid-pattern runs, anchors)
  are *truncated*: dropping a required suffix condition only weakens the
  predicate, which can only add false positives — soundness is preserved.
- **keyword lane**: rules that don't lower use their keywords (lowercased
  substring search, exactly the reference's `MatchKeywords` semantics) on
  device.
- **host lane**: rules with neither an anchored program nor keywords of at
  least ``MIN_ANCHOR`` bytes are evaluated host-side on every file (the
  reference also regex-scans every file for keyword-less rules).

The rule axis is data, not code: :func:`build_tables` turns a compiled
ruleset into flat, power-of-two-padded tables that are arguments of one
compiled program (`trivy_tpu.ops.rules`), so the program does not depend
on the rule count and a ruleset of the same padded sizes reuses it.

- **literals**: every keyword and anchor is an *entry* keyed by its
  ASCII-lowered text (A-Z only, as ``rules.ascii_lower``). Its key is one
  4-byte window of that text (a 3-byte one for a 3-byte literal, or where
  every 4-byte window is taken), chosen so that no two entries share a
  key; the keys sit in a two-choice bucket
  table of ``BUCKET_SLOTS`` slots, and an entry that finds no free key or
  slot goes to the overflow list, which every candidate also probes — no
  literal is ever dropped. A hashed bitmap of the keys is the dense first
  test at every position; the byte table (lowered text for the keyword
  test, raw text for the anchor) verifies a candidate exactly, so a hash
  hit alone never reports.
- **variants**: each anchored variant's window checks become ``(class,
  count, delta)`` rows of at most ``CHECK_SPAN`` bytes, evaluated against
  the class-membership table; the word-boundary test is one more row over
  the non-alnum class.
- **widths**: the byte and check-row tables are as wide as ``NARROW_PCT``
  percent of the ruleset's literals and variants need; each wider entry
  and variant also has a full-width row in the spill tables, which the
  pass reads only for it.
"""

from __future__ import annotations

import re
import re._constants as sre_c
import re._parser as sre_parse
from dataclasses import dataclass, field

import numpy as np

from trivy_tpu.secret.rules import Rule

# Minimum literal length (anchors and device keywords): shorter literals are
# too common to be useful hash keys and would flood the host-confirm stage
MIN_ANCHOR = 3
# Cap on variants per rule (branch fan-out) before falling back to keywords.
MAX_VARIANTS = 48
# Cap on expanding fixed repeats into per-byte classes.
MAX_EXPAND = 64

_ALL_BYTES = frozenset(range(256))
_NL = ord("\n")
# Content is scanned as latin-1 text (1:1 byte<->char), so \d/\w/\s must use
# Python's *unicode* semantics restricted to the first 256 codepoints — the
# ASCII-only sets would silently drop matches like \xa0 for \s (a device
# false negative, breaking the no-FN contract for custom rules).
_DIGITS = frozenset(b for b in range(256) if re.match(r"\d", chr(b)))
_WORD = frozenset(b for b in range(256) if re.match(r"\w", chr(b)))
_SPACES = frozenset(b for b in range(256) if re.match(r"\s", chr(b)))
_ALNUM = frozenset(range(48, 58)) | frozenset(range(65, 91)) | frozenset(range(97, 123))


class _Truncate(Exception):
    """Lowering stopped at an un-lowerable construct; tokens accumulated so
    far (mutated in place) remain valid as a weaker predicate."""


@dataclass(frozen=True)
class Token:
    """A run of ``count`` mandatory chars drawn from ``chars``."""

    chars: frozenset
    count: int


@dataclass
class Check:
    """Window check: positions [anchor+delta, anchor+delta+count) all in class."""

    chars: frozenset
    count: int
    delta: int  # offset from the anchor's first byte (may be negative)
    class_id: int = -1


@dataclass
class Variant:
    anchor: bytes
    checks: list[Check] = field(default_factory=list)
    pre_len: int = 0  # fixed bytes between match start and anchor start
    boundary: bool = False  # require non-alnum (or pos 0) before match start

    @property
    def window(self) -> tuple[int, int]:
        """[lo, hi) byte range the program inspects, relative to the anchor."""
        lo = min(
            [0]
            + [c.delta for c in self.checks]
            + ([-self.pre_len - 1] if self.boundary else [])
        )
        hi = max([len(self.anchor)] + [c.delta + c.count for c in self.checks])
        return lo, hi


@dataclass
class CompiledRules:
    """Device tables for one effective ruleset.

    ``rule_ids`` indexes the rule axis of the device pass; a hit for rule
    ``i`` means "run exact rule ``rule_ids[i]`` on this file host-side".
    ``host_rule_ids`` must be evaluated host-side on every file.
    """

    rule_ids: list[str]
    classes: np.ndarray  # [n_classes, 256] bool
    variants: list[tuple[int, Variant]]  # (rule_index, variant)
    keywords: list[tuple[int, bytes]]  # (rule_index, lowercased keyword)
    host_rule_ids: list[str]
    margin: int  # max bytes a program inspects beyond/behind a position
    span: int = 8  # required chunk overlap (max device-window extent)
    anchored_rule_ids: list[str] = field(default_factory=list)
    # keyword-prefilter table: (rule_index, ascii-lowered keyword) for EVERY
    # device rule that declares keywords — the keyword lane's own entries
    # plus anchored-lane rules that also declare keywords. The rule pass
    # (ops/rules.py) reports each row's keyword candidates; candidates gate
    # host confirms at file level (the reference's MatchKeywords is a
    # whole-file test, scanner.go:174-186).
    prefilter_keywords: list[tuple[int, bytes]] = field(default_factory=list)

    @property
    def num_rules(self) -> int:
        return len(self.rule_ids)

    @property
    def guarded(self) -> np.ndarray:
        """[R] bool: rules whose keywords are in the prefilter table — a
        prefilter miss across a whole file means the rule cannot match it
        (keywords are a whole-file predicate in the exact engine)."""
        g = np.zeros(self.num_rules, dtype=bool)
        for ridx, _ in self.prefilter_keywords:
            g[ridx] = True
        return g

    def prefilter_fingerprint(self) -> bytes:
        """Digest of the prefilter table: any keyword add/remove/edit (or a
        rule-index renumbering) flips it. Mixed into the dedup-cache key so
        cached hit/candidate vectors can never survive a ruleset keyword
        edit."""
        import hashlib

        h = hashlib.blake2b(digest_size=16)
        for ridx, kw in sorted(self.prefilter_keywords):
            h.update(ridx.to_bytes(4, "little"))
            h.update(len(kw).to_bytes(4, "little"))
            h.update(kw)
        return h.digest()


def _category_chars(cat) -> frozenset:
    if cat == sre_c.CATEGORY_DIGIT:
        return _DIGITS
    if cat == sre_c.CATEGORY_NOT_DIGIT:
        return _ALL_BYTES - _DIGITS
    if cat == sre_c.CATEGORY_WORD:
        return _WORD
    if cat == sre_c.CATEGORY_NOT_WORD:
        return _ALL_BYTES - _WORD
    if cat == sre_c.CATEGORY_SPACE:
        return _SPACES
    if cat == sre_c.CATEGORY_NOT_SPACE:
        return _ALL_BYTES - _SPACES
    raise _Truncate


def _in_chars(items) -> frozenset:
    negate = False
    chars: set[int] = set()
    for op, av in items:
        if op == sre_c.NEGATE:
            negate = True
        elif op == sre_c.LITERAL:
            if av < 256:
                chars.add(av)
        elif op == sre_c.RANGE:
            lo, hi = av
            chars.update(range(lo, min(hi, 255) + 1))
        elif op == sre_c.CATEGORY:
            chars.update(_category_chars(av))
        else:
            raise _Truncate
    return frozenset(_ALL_BYTES - chars) if negate else frozenset(chars)


def _single_chars(op, av, dotall: bool = False) -> frozenset:
    """Character set of a single-position node."""
    if op == sre_c.LITERAL:
        if av >= 256:
            raise _Truncate
        return frozenset({av})
    if op == sre_c.NOT_LITERAL:
        return _ALL_BYTES - {av}
    if op == sre_c.IN:
        return _in_chars(av)
    if op == sre_c.ANY:
        return _ALL_BYTES if dotall else _ALL_BYTES - {_NL}
    raise _Truncate


def _is_word_prefix_branch(op, av) -> frozenset | None:
    """Detect the leading ``(?:^|[^...])`` word-boundary idiom
    (ref: builtin-rules.go:81 startWord) and return its boundary class."""
    if op != sre_c.BRANCH:
        return None
    _, alts = av
    if len(alts) != 2:
        return None
    for a, b in ((list(alts[0]), list(alts[1])), (list(alts[1]), list(alts[0]))):
        if len(a) == 1 and a[0][0] == sre_c.AT and len(b) == 1:
            try:
                return _single_chars(*b[0])
            except _Truncate:
                return None
    return None


def _walk(nodes, streams: list[list[Token]], dotall: bool = False) -> None:
    """Lower an AST node sequence onto every open token stream, mutating
    ``streams`` in place so partial progress survives :class:`_Truncate`.
    """
    for op, av in nodes:
        if op in (sre_c.LITERAL, sre_c.NOT_LITERAL, sre_c.IN, sre_c.ANY):
            tok = Token(_single_chars(op, av, dotall), 1)
            for s in streams:
                s.append(tok)
        elif op in (sre_c.MAX_REPEAT, sre_c.MIN_REPEAT):
            lo, hi, sub = av
            sub = list(sub)
            if len(sub) == 1 and sub[0][0] in (
                sre_c.LITERAL,
                sre_c.NOT_LITERAL,
                sre_c.IN,
                sre_c.ANY,
            ):
                chars = _single_chars(*sub[0], dotall)
                if lo > 0:
                    for s in streams:
                        s.append(Token(chars, lo))
                if hi != lo:
                    # variable run: offsets beyond it are unknown
                    raise _Truncate
            else:
                if lo == 0:
                    raise _Truncate
                if lo * max(1, len(sub)) > MAX_EXPAND:
                    # check the first mandatory copy, then stop
                    _walk(sub, streams, dotall)
                    raise _Truncate
                for _ in range(lo):
                    _walk(sub, streams, dotall)
                if hi != lo:
                    raise _Truncate
        elif op == sre_c.SUBPATTERN:
            _g, add_f, _del_f, sub = av
            if add_f & re.IGNORECASE:
                raise _Truncate
            _walk(list(sub), streams, dotall or bool(add_f & re.DOTALL))
        elif op == sre_c.BRANCH:
            _, alts = av
            if len(streams) * len(alts) > MAX_VARIANTS:
                raise _Truncate
            forked: list[list[Token]] = []
            truncated = False
            for alt in alts:
                alt_streams = [list(s) for s in streams]
                try:
                    _walk(list(alt), alt_streams, dotall)
                except _Truncate:
                    truncated = True
                forked.extend(alt_streams)
            streams[:] = forked
            if truncated:
                raise _Truncate
        else:
            # AT, ASSERT, ASSERT_NOT, GROUPREF, ...: cannot lower
            raise _Truncate


def _compile_variant(tokens: list[Token], boundary: bool) -> Variant | None:
    # expand fixed tokens into per-byte classes (long runs keep run form)
    seq: list[frozenset] = []
    tail_runs: list[Token] = []  # runs too long to expand, kept as checks
    for t in tokens:
        if t.count > MAX_EXPAND:
            tail_runs.append(t)
            break  # positions after it are known, but keep it simple
        seq.extend([t.chars] * t.count)

    # anchor = longest run of singleton classes
    best: tuple[int, int] | None = None
    i = 0
    while i < len(seq):
        if len(seq[i]) == 1:
            j = i
            while j < len(seq) and len(seq[j]) == 1:
                j += 1
            if best is None or (j - i) > best[1]:
                best = (i, j - i)
            i = j
        else:
            i += 1
    if best is None or best[1] < MIN_ANCHOR:
        return None
    a_start, a_len = best
    anchor = bytes(next(iter(seq[k])) for k in range(a_start, a_start + a_len))
    v = Variant(anchor=anchor, pre_len=a_start, boundary=boundary)

    checks: list[Check] = []
    k = 0
    while k < len(seq):
        if a_start <= k < a_start + a_len:
            k += 1
            continue
        chars = seq[k]
        j = k
        while j < len(seq) and not (a_start <= j < a_start + a_len) and seq[j] == chars:
            j += 1
        if chars != _ALL_BYTES:
            checks.append(Check(chars=chars, count=j - k, delta=k - a_start))
        k = j
    for t in tail_runs:
        if t.chars != _ALL_BYTES:
            checks.append(Check(chars=t.chars, count=t.count, delta=len(seq) - a_start))
    v.checks = checks
    return v


def compile_rule(rule: Rule) -> list[Variant] | None:
    """Lower one rule to anchored variants, or None for keyword/host lane."""
    try:
        tree = sre_parse.parse(rule.regex)
    except Exception:
        return None
    if tree.state.flags & re.IGNORECASE:
        return None
    nodes = list(tree)
    boundary = False
    if nodes:
        bc = _is_word_prefix_branch(*nodes[0])
        if bc is not None:
            # only the standard non-alnum boundary is modeled; any other
            # boundary class is skipped (sound: weaker predicate)
            boundary = bc == (_ALL_BYTES - _ALNUM)
            nodes = nodes[1:]
    streams: list[list[Token]] = [[]]
    try:
        _walk(nodes, streams, dotall=bool(tree.state.flags & re.DOTALL))
    except _Truncate:
        pass
    variants = []
    for s in streams:
        v = _compile_variant(s, boundary)
        if v is None:
            return None  # every variant must be detectable, else no-FN breaks
        variants.append(v)
    return variants or None


def compile_rules(rules: list[Rule]) -> CompiledRules:
    """Compile an effective ruleset to device tables."""
    rule_ids: list[str] = []
    variants: list[tuple[int, Variant]] = []
    keywords: list[tuple[int, bytes]] = []
    host_rule_ids: list[str] = []
    anchored_rule_ids: list[str] = []
    class_index: dict[frozenset, int] = {}

    prefilter_keywords: list[tuple[int, bytes]] = []

    def kw_bytes(rule: Rule) -> list[bytes]:
        # a keyword with chars >255 can never be a substring of latin-1
        # scan content, so dropping it keeps the device keyword test
        # EXACTLY equal to the host's match_keywords, not merely sound
        out = []
        for kw in rule.lower_keywords:
            try:
                out.append(kw.encode("latin-1"))
            except UnicodeEncodeError:
                continue
        return out

    for rule in rules:
        prog = compile_rule(rule)
        kb = kw_bytes(rule)
        if any(len(k) < MIN_ANCHOR for k in kb):
            # a keyword too short to key cannot gate the rule on device: the
            # rule is unguarded (anchored) or scanned on every file (host)
            kb = []
        if prog is not None:
            ridx = len(rule_ids)
            rule_ids.append(rule.id)
            anchored_rule_ids.append(rule.id)
            for v in prog:
                for c in v.checks:
                    if c.chars not in class_index:
                        class_index[c.chars] = len(class_index)
                    c.class_id = class_index[c.chars]
                variants.append((ridx, v))
            # anchored rules that also declare keywords join the prefilter
            # table: their confirms gate on a whole-file keyword candidate
            if kb:
                prefilter_keywords.extend((ridx, k) for k in kb)
        elif rule.lower_keywords:
            if not kb:
                # no representable keyword: nothing for the device to find
                host_rule_ids.append(rule.id)
                continue
            ridx = len(rule_ids)
            rule_ids.append(rule.id)
            for k in kb:
                keywords.append((ridx, k))
            prefilter_keywords.extend((ridx, k) for k in kb)
        else:
            host_rule_ids.append(rule.id)

    classes = np.zeros((max(1, len(class_index)), 256), dtype=bool)
    for chars, idx in class_index.items():
        classes[idx, list(chars)] = True

    # margin: array padding for shifted reads; span: required chunk overlap
    # so every device window lies fully inside at least one chunk's real data
    margin = 8
    span = 8
    for _, v in variants:
        lo, hi = v.window
        margin = max(margin, hi, -lo)
        span = max(span, hi - lo)
    for _, kw in keywords:
        margin = max(margin, len(kw))
        span = max(span, len(kw))
    for _, kw in prefilter_keywords:
        # anchored-lane keywords are tested in the same rows as their
        # anchors — the overlap must cover them too
        margin = max(margin, len(kw))
        span = max(span, len(kw))

    return CompiledRules(
        rule_ids=rule_ids,
        classes=classes,
        variants=variants,
        keywords=keywords,
        host_rule_ids=host_rule_ids,
        margin=margin,
        span=span,
        anchored_rule_ids=anchored_rule_ids,
        prefilter_keywords=prefilter_keywords,
    )


# -- device tables ----------------------------------------------------------

# bits of the dense key bitmap (the first test at every position): fixed,
# so every ruleset shares its shape; ~9k literals fill 0.2% of it
BITMAP_BITS = 22
# slots per bucket of the key table (two-choice: a key lives in one of two)
BUCKET_SLOTS = 4
# bytes per window-check row (longer checks split into several rows)
CHECK_SPAN = 32
# percent of the entries (literal words) and of the variants (check rows)
# that the verify steps' narrow lane holds; wider ones take the spill lane
NARROW_PCT = 95
_GOLD = 0x9E3779B1
_H1 = 0x85EBCA6B
_H2 = 0xC2B2AE35
_SALT3 = 0x5BD1E995


def _pow2(n: int, lo: int = 1) -> int:
    n = max(int(n), lo)
    return 1 << (n - 1).bit_length()


def _narrow(sizes: list[int]) -> int:
    """The smallest power of two, at least 4, that holds ``NARROW_PCT``
    percent of ``sizes``."""
    k = -(-NARROW_PCT * len(sizes) // 100)
    return _pow2(sorted(sizes)[k - 1] if sizes else 1, 4)


def _mul_hi(key, mult: int, bits: int):
    """Top ``bits`` bits of the 32-bit product (numpy, uint32 wraparound) —
    the device computes the same expression on uint32 lanes."""
    k = np.asarray(key, dtype=np.uint64)
    return ((k * np.uint64(mult)) & np.uint64(0xFFFFFFFF)) >> np.uint64(32 - bits)


def bitmap_hash(key):
    return _mul_hi(key, _GOLD, BITMAP_BITS)


def bucket_hash(key, klen, which: int, bucket_bits: int):
    k = np.asarray(key, dtype=np.uint64)
    k = np.where(np.asarray(klen) == 3, k ^ np.uint64(_SALT3), k)
    return _mul_hi(k, _H1 if which == 1 else _H2, bucket_bits)


def _word_le(b: bytes) -> int:
    return int.from_bytes(b[:4].ljust(4, b"\x00"), "little")


def _rarity(w: bytes) -> int:
    """Key-window preference (lower first): windows with bytes other than
    lowercase letters occur less often in source text, so they keep the
    dense first test selective."""
    return -sum(1 for c in w if not 97 <= c <= 122)


@dataclass
class _Entry:
    low: bytes  # ASCII-lowered literal: the key text and the keyword test
    raw: bytes | None = None  # anchor text (case-sensitive test), if any
    kw_rules: list[int] = field(default_factory=list)
    variants: list[int] = field(default_factory=list)  # compiled.variants idx
    off: int = 0
    klen: int = 4
    key: int = 0


@dataclass
class RuleTables:
    """The rule pass's operands (numpy, padded to power-of-two rungs) and
    the host's maps back from result bits to rule indices.

    Result bit ``k < kw_bits`` is keyword entry ``k``: rules
    ``kw_rules[kw_start[k]:kw_start[k + 1]]`` have a keyword present;
    bit ``kw_bits + v`` is anchored variant ``v`` of rule ``var_rule[v]``.
    """

    arrays: dict[str, np.ndarray]
    kw_bits: int
    var_bits: int
    kw_start: np.ndarray  # [kw_bits + 1] CSR into kw_rules
    kw_rules: np.ndarray
    var_rule: np.ndarray  # [var_bits] rule index, -1 for padding
    entries: int
    overflow: int  # literals the overflow list holds
    margin: int  # plane padding the pass needs on each side of a row
    kw_lane: np.ndarray  # [R] bool: rules whose keyword presence is the hit
    # [out_words - 1] u32: the result bits the spill lane decides (keyword
    # entries and variants wider than the narrow lane, and the variants of
    # such anchors)
    spill_bits: np.ndarray

    @property
    def out_words(self) -> int:
        """u32 words per result row: the bit axis, then the overflow count."""
        return (self.kw_bits + self.var_bits) // 32 + 1

    def pairs(self, out: np.ndarray) -> tuple[np.ndarray, ...]:
        """Unpack pass results ``[n, out_words]`` into sparse pairs:
        ``(hit_rows, hit_rules, cand_rows, cand_rules)``. Candidates are
        the rules with a keyword in the row; hits are the anchored
        variants that passed plus the keyword-lane candidates."""
        words = np.ascontiguousarray(out[:, :-1], dtype=np.uint32)
        r, w = np.nonzero(words)
        if len(r):
            bits = np.unpackbits(
                words[r, w].view(np.uint8).reshape(-1, 4), axis=1,
                bitorder="little",
            )
            br, bb = np.nonzero(bits)
            rows = r[br]
            idx = w[br].astype(np.int64) * 32 + bb
        else:
            rows = idx = np.zeros(0, dtype=np.int64)
        kw = idx < self.kw_bits
        k_rows, k_bits = rows[kw], idx[kw]
        lo, hi = self.kw_start[k_bits], self.kw_start[k_bits + 1]
        n = hi - lo
        cand_rows = np.repeat(k_rows, n)
        total = int(n.sum())
        offs = np.arange(total) - np.repeat(n.cumsum() - n, n)
        cand_rules = self.kw_rules[np.repeat(lo, n) + offs]
        # a rule's keywords may share a row: one pair each
        if len(cand_rows):
            pair = np.unique(np.stack([cand_rows, cand_rules], 1), axis=0)
            cand_rows, cand_rules = pair[:, 0], pair[:, 1]
        v_rows = rows[~kw]
        v_rules = self.var_rule[idx[~kw] - self.kw_bits]
        lane = self.kw_lane[cand_rules]
        hit = np.unique(np.stack([
            np.concatenate([v_rows, cand_rows[lane]]),
            np.concatenate([v_rules, cand_rules[lane]]),
        ], 1), axis=0) if len(v_rows) or lane.any() else np.zeros((0, 2), np.int64)
        return hit[:, 0], hit[:, 1], cand_rows, cand_rules

    def spill_hits(self, out: np.ndarray) -> int:
        """Set (row, bit) pairs of ``out [n, out_words]`` that the spill
        lane decided."""
        return int(np.bitwise_count(np.asarray(out)[:, :-1] & self.spill_bits).sum())

    def nbytes(self) -> int:
        return int(sum(a.nbytes for a in self.arrays.values()))

    def operands(self) -> tuple:
        return tuple(self.arrays[k] for k in TABLE_ORDER)


TABLE_ORDER = (
    "bitmap", "slot_key", "slot_tag", "ovf_key", "ovf_klen", "ovf_ent",
    "ent_low", "ent_raw", "ent_mask", "ent_off", "ent_kbit", "ent_anchor",
    "ent_vstart", "ent_vcount", "var_rows", "classes", "omax",
    "ent_spill", "spill_low", "spill_raw", "spill_mask", "var_spill", "spill_var_rows",
)


def build_tables(compiled: CompiledRules, bucket_slots: int = BUCKET_SLOTS,
                 buckets: int = 0, widths: tuple[int, int] | None = None) -> RuleTables:
    """The device tables of ``compiled``. ``buckets`` overrides the key
    table's bucket count (a power of two; tests force collisions and
    overflow with a tiny table). ``widths`` overrides the narrow lane's
    literal words and check rows; tests pass the full widths, which leave
    the spill lane empty."""
    from trivy_tpu.secret.rules import ascii_lower

    def lower(b: bytes) -> bytes:
        return ascii_lower(b.decode("latin-1")).encode("latin-1")

    entries: list[_Entry] = []
    by_low: dict[bytes, int] = {}

    def entry_for(low: bytes) -> _Entry:
        if low not in by_low:
            by_low[low] = len(entries)
            entries.append(_Entry(low=low))
        return entries[by_low[low]]

    for ridx, kw in compiled.prefilter_keywords:
        e = entry_for(kw)
        if ridx not in e.kw_rules:
            e.kw_rules.append(ridx)
    for vi, (_ridx, v) in enumerate(compiled.variants):
        low = lower(v.anchor)
        e = entry_for(low)
        if e.raw is None or e.raw == v.anchor:
            e.raw = v.anchor
            e.variants.append(vi)
        else:
            # same lowered text, other case: an entry of its own, whose key
            # is taken, so it lands in the overflow list
            entries.append(_Entry(low=low, raw=v.anchor, variants=[vi]))

    # keys: a unique window per entry, shorter literals first (fewer windows)
    used: set[tuple[int, int]] = set()
    overflow: list[int] = []
    keyed: list[int] = []
    for i in sorted(range(len(entries)), key=lambda i: (len(entries[i].low), i)):
        e = entries[i]
        if len(e.low) == 3:
            e.off, e.klen, e.key = 0, 3, _word_le(e.low)
            if (e.key, 3) in used:
                overflow.append(i)
            else:
                used.add((e.key, 3))
                keyed.append(i)
            continue
        # a free 4-byte window, else a free 3-byte one (less selective),
        # else the overflow list
        windows = [(o, 4) for o in sorted(range(len(e.low) - 3),
                                          key=lambda o: (_rarity(e.low[o:o + 4]), o))]
        windows += [(o, 3) for o in range(len(e.low) - 2)]
        for o, klen in windows:
            k = _word_le(e.low[o:o + klen])
            if (k, klen) not in used:
                e.off, e.klen, e.key = o, klen, k
                used.add((k, klen))
                keyed.append(i)
                break
        else:
            e.off, e.klen, e.key = 0, 4, _word_le(e.low[:4])
            overflow.append(i)

    nb = buckets or _pow2(2 * len(keyed) // bucket_slots + 1, 16)
    bucket_bits = nb.bit_length() - 1
    slot_key = np.zeros(nb * bucket_slots, dtype=np.uint32)
    slot_tag = np.full(nb * bucket_slots, -1, dtype=np.int32)
    fill = np.zeros(nb, dtype=np.int32)
    for i in keyed:
        e = entries[i]
        b1 = int(bucket_hash(e.key, e.klen, 1, bucket_bits))
        b2 = int(bucket_hash(e.key, e.klen, 2, bucket_bits))
        b = b1 if fill[b1] <= fill[b2] else b2
        if fill[b] >= bucket_slots:
            overflow.append(i)
            continue
        slot = b * bucket_slots + fill[b]
        fill[b] += 1
        slot_key[slot] = e.key
        slot_tag[slot] = i * 2 + (1 if e.klen == 3 else 0)

    n_ovf = _pow2(len(overflow))
    ovf_key = np.zeros(n_ovf, dtype=np.uint32)
    ovf_klen = np.zeros(n_ovf, dtype=np.int32)
    ovf_ent = np.full(n_ovf, -1, dtype=np.int32)
    for j, i in enumerate(overflow):
        ovf_key[j], ovf_klen[j], ovf_ent[j] = entries[i].key, entries[i].klen, i

    # dense bitmap over every key (3-byte keys with each possible 4th byte)
    bitmap = np.zeros((1 << BITMAP_BITS) // 32, dtype=np.uint32)
    keys4 = []
    for e in entries:
        if e.klen == 4:
            keys4.append(e.key)
        else:
            keys4.extend(e.key | (b << 24) for b in range(256))
    if keys4:
        h = bitmap_hash(np.asarray(keys4, dtype=np.uint64)).astype(np.int64)
        np.bitwise_or.at(bitmap, h >> 5, (np.uint32(1) << (h & 31).astype(np.uint32)))

    # entry byte tables: every entry's first lw words (the narrow lane),
    # and the entries wider than that whole on the spill lane
    n_ent = _pow2(len(entries))
    n_words = [-(-len(e.low) // 4) for e in entries]
    lw_full = _pow2(max(n_words + [1]), 4)
    lw = _narrow(n_words) if widths is None else min(widths[0], lw_full)
    wide = [i for i, n in enumerate(n_words) if n > lw]
    ent_low = np.zeros((n_ent, lw), dtype=np.uint32)
    ent_raw = np.zeros((n_ent, lw), dtype=np.uint32)
    ent_mask = np.zeros((n_ent, lw), dtype=np.uint32)
    ent_off = np.zeros(n_ent, dtype=np.int32)
    ent_kbit = np.full(n_ent, -1, dtype=np.int32)
    ent_anchor = np.zeros(n_ent, dtype=np.int32)
    ent_vstart = np.zeros(n_ent, dtype=np.int32)
    ent_vcount = np.zeros(n_ent, dtype=np.int32)
    ent_spill = np.full(n_ent, -1, dtype=np.int32)
    spill_low = np.zeros((_pow2(len(wide)), lw_full), dtype=np.uint32)
    spill_raw = np.zeros_like(spill_low)
    spill_mask = np.zeros_like(spill_low)

    def words(b: bytes, n: int) -> np.ndarray:
        return np.frombuffer(b[:n * 4].ljust(n * 4, b"\x00"), dtype="<u4").astype(np.uint32)

    kw_entries = [i for i, e in enumerate(entries) if e.kw_rules]
    kbit = {i: k for k, i in enumerate(kw_entries)}
    var_order: list[int] = []  # bit v -> compiled.variants index
    for i, e in enumerate(entries):
        ent_low[i] = words(e.low, lw)
        ent_mask[i] = words(b"\xff" * len(e.low), lw)
        ent_off[i] = e.off
        if e.raw is not None:
            ent_raw[i] = words(e.raw, lw)
            ent_anchor[i] = 1
            ent_vstart[i] = len(var_order)
            ent_vcount[i] = len(e.variants)
            var_order.extend(e.variants)
        ent_kbit[i] = kbit.get(i, -1)
    for j, i in enumerate(wide):
        e = entries[i]
        ent_spill[i] = j
        spill_low[j] = words(e.low, lw_full)
        spill_mask[j] = words(b"\xff" * len(e.low), lw_full)
        if e.raw is not None:
            spill_raw[j] = words(e.raw, lw_full)

    # variant window-check rows: (class, count <= CHECK_SPAN, delta)
    n_cls = compiled.classes.shape[0]
    nonalnum = n_cls  # extra class row: the word-boundary test
    classes = np.zeros((_pow2(n_cls + 1), 256), dtype=np.uint8)
    classes[:n_cls] = compiled.classes
    classes[nonalnum] = 1
    for lo, hi in ((48, 57), (65, 90), (97, 122)):
        classes[nonalnum, lo:hi + 1] = 0
    rows_of: list[list[tuple[int, int, int]]] = []
    for vi in var_order:
        v = compiled.variants[vi][1]
        rows = []
        for ch in v.checks:
            for d in range(0, ch.count, CHECK_SPAN):
                rows.append((ch.class_id, min(CHECK_SPAN, ch.count - d), ch.delta + d))
        if v.boundary:
            rows.append((nonalnum, 1, -v.pre_len - 1))
        rows_of.append(rows)
    # every variant's first width rows (the narrow lane), and the variants
    # wider than that whole on the spill lane
    n_var = _pow2(len(var_order), 32)
    n_rows = [len(r) for r in rows_of]
    width_full = _pow2(max(n_rows + [1]), 4)
    width = _narrow(n_rows) if widths is None else min(widths[1], width_full)
    wide_v = [v for v, n in enumerate(n_rows) if n > width]
    var_rows = np.zeros((n_var, width, 3), dtype=np.int32)
    for v, rows in enumerate(rows_of):
        for w, row in enumerate(rows[:width]):
            var_rows[v, w] = row
    var_spill = np.full(n_var, -1, dtype=np.int32)
    spill_var_rows = np.zeros((_pow2(len(wide_v)), width_full, 3), dtype=np.int32)
    for j, v in enumerate(wide_v):
        var_spill[v] = j
        for w, row in enumerate(rows_of[v]):
            spill_var_rows[j, w] = row
    var_rule = np.full(n_var, -1, dtype=np.int64)
    for v, vi in enumerate(var_order):
        var_rule[v] = compiled.variants[vi][0]

    n_kw = _pow2(len(kw_entries), 32)
    kw_start = np.zeros(n_kw + 1, dtype=np.int64)
    kw_rules: list[int] = []
    for k, i in enumerate(kw_entries):
        kw_rules.extend(entries[i].kw_rules)
        kw_start[k + 1] = len(kw_rules)
    kw_start[len(kw_entries) + 1:] = len(kw_rules)

    spill_bit = np.zeros(n_kw + n_var, dtype=bool)
    spill_bit[n_kw + np.asarray(wide_v, dtype=np.int64)] = True
    for i in wide:
        if i in kbit:
            spill_bit[kbit[i]] = True
        v0 = n_kw + ent_vstart[i]
        spill_bit[v0:v0 + ent_vcount[i]] = True

    kw_lane = np.zeros(compiled.num_rules, dtype=bool)
    kw_lane[[ridx for ridx, _ in compiled.keywords]] = True
    omax = max([e.off for e in entries] + [0])
    reach = max(compiled.margin, lw_full * 4) + CHECK_SPAN + 4
    arrays = {
        "bitmap": bitmap, "slot_key": slot_key.reshape(nb, bucket_slots),
        "slot_tag": slot_tag.reshape(nb, bucket_slots),
        "ovf_key": ovf_key, "ovf_klen": ovf_klen, "ovf_ent": ovf_ent,
        "ent_low": ent_low, "ent_raw": ent_raw, "ent_mask": ent_mask,
        "ent_off": ent_off, "ent_kbit": ent_kbit, "ent_anchor": ent_anchor,
        "ent_vstart": ent_vstart, "ent_vcount": ent_vcount,
        "var_rows": var_rows, "classes": classes.reshape(-1),
        "omax": np.asarray(omax, dtype=np.int32),
        "ent_spill": ent_spill, "spill_low": spill_low, "spill_raw": spill_raw,
        "spill_mask": spill_mask, "var_spill": var_spill, "spill_var_rows": spill_var_rows,
    }
    return RuleTables(
        arrays=arrays, kw_bits=n_kw, var_bits=n_var, kw_start=kw_start,
        kw_rules=np.asarray(kw_rules, dtype=np.int64), var_rule=var_rule,
        entries=len(entries), overflow=len(overflow),
        margin=-(-reach // 128) * 128, kw_lane=kw_lane,
        spill_bits=np.packbits(spill_bit, bitorder="little").view("<u4").astype(np.uint32),
    )
