"""Device-resident license n-gram scoring (PAPER.md §7: "license
classification ... vectorized as sharded vmap'd lookups" with corpus
shards on the mesh 'model' axis).

The classifier's two gram lanes (full-text distinctiveness weights +
pooled fingerprint-phrase grams, see ``licensing/classify.py``) compile
into one table per corpus shard (:class:`CorpusTable32`): a sorted uint32
key column and a dense per-key *credit matrix* ``[Ku, 2*Ls]`` holding
each key's full-lane weight and phrase-lane credit for every license in
the shard's slab. :class:`DeviceBytesScorer` ships zero-padded uint8 text
rows; the device lowers, tokenizes and hashes them, gates them against
two shingle blooms, intersects each row's sorted unique grams with the
key column (binary search) and reduces the hit rows of the credit matrix
— a pure gather + weighted-sum (embedding-lookup shape, no scatter
anywhere), returning per-(text, license) full-lane matched weight and
phrase-lane gram hit counts.

Sharding: rows shard over the mesh 'data' axis, the corpus table over
'model' (each model shard owns a contiguous slab of the license axis and
only that slab's gram keys), via :func:`trivy_tpu.parallel.mesh.
sharded_bytes_score_fn`. The table is uploaded once per (corpus, mesh)
and stays HBM-resident across scans: per scan only the text rows cross
the link.

Soundness of the 32-bit key image: corpus and text keys reduce from the
same int64 hashes, so every true int64 match survives, and credit tables
count fold multiplicity — collisions can only *add* matched weight or
phrase credit (never remove it). Device-gated candidate sets are
therefore supersets of the host scorer's and thresholding on device
scores never drops a passing license; the reported confidence itself can
exceed the host oracle's only on a collision (~T*Ku/2^32 expected per
text), and never undershoots it.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field

import numpy as np

# int32 gram-row padding sentinel (fold32 / pack_gram_rows); pads sort last
PAD_KEY = np.int32(np.iinfo(np.int32).max)

# raw-bytes path sentinel (uint32 hash domain): real keys clamp to
# 0xFFFFFFFE on BOTH sides (corpus build and device kernel), so the
# all-ones value is free for row padding / invalid-gram slots
SENT32 = np.uint32(0xFFFFFFFF)
_MASK64 = np.uint64(0xFFFFFFFF)

# byte-shingle bloom geometry for the raw-bytes candidate gate: LUT-
# lowered byte windows hash into 2^22-slot bitmasks. Two lanes:
# - main lane: 8-byte windows of the corpus texts, counted per 512-byte
#   block — license text is contiguous, so a dense block flags the row
#   even when a short header hides inside a large source file;
# - anchor lane: 4-byte windows of the short fingerprint phrases, whose
#   whitespace-ROBUST windows (fully inside a word, or word bytes + the
#   first separator byte) survive arbitrary whitespace-run edits — the
#   recall guarantee for the host substring lane (`ph in normalize(t)`
#   is whitespace-collapsing, so the gate must be too).
SHINGLE_BITS = 22
SHINGLE_BLOCK = 512  # main-lane density block (divides every row width)
_SHINGLE_MIX = np.uint32(2654435761)  # Knuth multiplicative hash
_SHINGLE_P2 = np.uint32(40503)


def fold32(keys: np.ndarray) -> np.ndarray:
    """Fold int64 gram/word hashes to int32 (xor-fold of the halves),
    reserving PAD_KEY for padding. Applied identically to corpus and text
    keys, so int64 equality always survives the fold."""
    k = np.asarray(keys, dtype=np.int64)
    folded = (k ^ (k >> np.int64(32))).astype(np.int32)
    folded[folded == PAD_KEY] = PAD_KEY - np.int32(1)
    return folded


def fold_u32(keys64: np.ndarray) -> np.ndarray:
    """Raw-bytes-path key fold: low 32 bits of the int64 hash domain.
    The classifier's word hash and gram fold are pure ring arithmetic mod
    2^64 (see ops/gram_gate.py), so truncation is a ring homomorphism —
    a uint32 device kernel computes EXACTLY this image from raw bytes.
    Values clamp to 0xFFFFFFFE so SENT32 stays reserved for padding;
    clamp collisions, like fold collisions, only ever ADD credit."""
    k = np.asarray(keys64, dtype=np.int64).astype(np.uint64) & _MASK64
    return np.minimum(k.astype(np.uint32), np.uint32(0xFFFFFFFE))


def lut_low32(lut: np.ndarray) -> np.ndarray:
    """The classifier's byte->lowered-value LUT folded to uint32 (the
    image the device kernel gathers; separators stay 0)."""
    return (
        np.asarray(lut, dtype=np.int64).astype(np.uint64) & _MASK64
    ).astype(np.uint32)


def _pack_words(sv: np.ndarray, n: int, width: int) -> np.ndarray:
    """little-endian byte packing of ``width``-byte windows at positions
    0..n-1 of a space-substituted LUT image, as uint32 word(s) folded with
    the shingle mix constants — shared by the host bloom build and
    (structurally) the device gate kernel."""
    with np.errstate(over="ignore"):
        if width == 4:
            w = (
                sv[:n]
                + (sv[1 : n + 1] << np.uint32(8))
                + (sv[2 : n + 2] << np.uint32(16))
                + (sv[3 : n + 3] << np.uint32(24))
            )
            return (w * _SHINGLE_MIX) >> np.uint32(32 - SHINGLE_BITS)
        wlo = (
            sv[:n]
            + (sv[1 : n + 1] << np.uint32(8))
            + (sv[2 : n + 2] << np.uint32(16))
            + (sv[3 : n + 3] << np.uint32(24))
        )
        whi = (
            sv[4 : n + 4]
            + (sv[5 : n + 5] << np.uint32(8))
            + (sv[6 : n + 6] << np.uint32(16))
            + (sv[7 : n + 7] << np.uint32(24))
        )
        return (wlo * _SHINGLE_MIX + whi * _SHINGLE_P2) >> np.uint32(
            32 - SHINGLE_BITS
        )


def shingle_hashes(
    data: np.ndarray, lut32: np.ndarray, width: int
) -> tuple[np.ndarray, np.ndarray]:
    """Host mirror of the device gate's shingle pipeline over one uint8
    buffer -> ``(hashes [n] uint32, valid [n] bool)`` with one window per
    byte position (trailing windows pad with spaces). Separators (LUT
    value 0) shingle as ASCII space; a window is valid when it STARTS on
    a word byte (pad/whitespace runs contribute nothing)."""
    v = lut32[data.astype(np.int64)]
    sv = np.concatenate(
        [
            np.where(v == 0, np.uint32(32), v),
            np.full(width, 32, dtype=np.uint32),
        ]
    )
    h = _pack_words(sv, len(v), width)
    return h, v != 0


def _robust_windows(text: str, lut: np.ndarray, width: int) -> list[bytes]:
    """Shingle windows of ``text`` that survive arbitrary whitespace-run
    edits: fully inside one word, or word bytes followed by exactly one
    trailing separator byte (every separator LUTs to the same space, and
    only the FIRST byte of a run lands inside such a window)."""
    lut32 = lut_low32(lut)
    words: list[bytes] = []
    cur = bytearray()
    for byte in text.encode("latin-1", "replace"):
        if lut32[byte] == 0:
            if cur:
                words.append(bytes(cur))
                cur = bytearray()
        else:
            cur.append(byte)
    if cur:
        words.append(bytes(cur))
    out: list[bytes] = []
    for w in words:
        for i in range(max(0, len(w) - width + 1)):
            out.append(w[i : i + width])
        if len(w) >= width - 1:
            out.append(w[len(w) - (width - 1) :] + b" ")
    return out


@dataclass
class ShingleGate:
    """Gate-side corpus artifacts: the two bloom bitmasks plus the
    soundness threshold for the anchor lane."""

    bloom8: np.ndarray  # [2^SHINGLE_BITS] uint8, main-lane 8-byte windows
    bloom4: np.ndarray  # [2^SHINGLE_BITS] uint8, anchor-lane 4-byte windows
    # minimum robust-window hit count any short phrase occurrence is
    # GUARANTEED to produce, however the scanned file spaces or wraps it:
    # ahits >= anchor_min is a sound superset of the host substring lane
    anchor_min: int


def build_shingle_gate(
    corpus_texts: list[str], anchor_texts: list[str], lut: np.ndarray
) -> ShingleGate:
    """Build the two-lane shingle gate from the normalized corpus texts
    (main lane; raw variants welcome too) and the short fingerprint
    phrases (anchor lane). The main lane is recall-tuned, not sound: its
    per-block threshold lives host-side as a knob, chosen low enough
    that even whitespace-mangled license text trips on intra-word
    windows. The anchor lane IS sound for the substring check, with the
    threshold computed here from the phrases themselves."""
    lut32 = lut_low32(lut)
    bloom8 = np.zeros(1 << SHINGLE_BITS, dtype=np.uint8)
    bloom4 = np.zeros(1 << SHINGLE_BITS, dtype=np.uint8)
    for t in corpus_texts:
        b = np.frombuffer(
            (t + " ").encode("latin-1", "replace"), dtype=np.uint8
        )
        if not len(b):
            continue
        h, valid = shingle_hashes(b, lut32, 8)
        bloom8[h[valid]] = 1
    for t in anchor_texts:
        b = np.frombuffer(
            (t + " ").encode("latin-1", "replace"), dtype=np.uint8
        )
        if not len(b):
            continue
        h, valid = shingle_hashes(b, lut32, 4)
        bloom4[h[valid]] = 1
    anchor_min = 1
    if anchor_texts:
        counts = []
        for t in anchor_texts:
            rws = _robust_windows(t, lut, 4)
            n = 0
            for rw in rws:
                h, valid = shingle_hashes(
                    np.frombuffer(rw, dtype=np.uint8), lut32, 4
                )
                if valid[0] and bloom4[h[0]]:
                    n += 1
            counts.append(n)
        anchor_min = max(1, min(counts))
    return ShingleGate(bloom8=bloom8, bloom4=bloom4, anchor_min=anchor_min)


@dataclass
class CorpusTable32:
    """Host-side corpus fingerprint table, pre-split into model shards.

    Arrays carry a leading shard axis ``m`` so the same buffers serve the
    single-device path (m=1) and the sharded path (axis sharded over
    'model'). The credit matrix is license-local per shard; concatenating
    per-shard score blocks along the license axis restores global order.
    Keys live in the uint32 low-32 hash domain the device computes
    natively from arena bytes (ops/gram_gate.py's ring-homomorphism
    trick); the table also carries the classifier constants the kernels
    need (LUT + mix constants) and the shingle-bloom gate bitmask."""

    keys: np.ndarray  # [m, Ku] uint32, sorted per shard, SENT32 padded
    credit: np.ndarray  # [m, Ku, 2*Ls] f32: [:Ls] full weight, [Ls:] phrase
    gate: ShingleGate  # two-lane shingle blooms + anchor soundness floor
    lut: np.ndarray  # [256] int64 classifier byte LUT
    p1: int  # classifier word-hash / gram-fold constants (int64 domain)
    p2: int
    hash_p: int
    ngram: int
    n_shards: int
    lic_per_shard: int
    n_licenses: int
    wtot: np.ndarray = field(default=None)
    n_units: np.ndarray = field(default=None)
    n_short: np.ndarray = field(default=None)

    @property
    def padded_licenses(self) -> int:
        return self.n_shards * self.lic_per_shard


def build_corpus_table32(
    licenses: list[str],
    full_keys: dict[str, np.ndarray],
    full_weights: dict[str, np.ndarray],
    phrase_keys: dict[str, np.ndarray],
    phrase_short: dict[str, list[str]],
    corpus_texts: list[str],
    anchor_texts: list[str],
    lut: np.ndarray,
    p1: int,
    p2: int,
    hash_p: int,
    ngram: int = 5,
    model_shards: int = 1,
) -> CorpusTable32:
    """Compile the classifier's scoring tables for the raw-bytes kernel.

    Inputs are the host scorer's own structures (int64 gram keys +
    distinctiveness weights per license), so device scores agree with the
    host oracle by construction, modulo the sound 32-bit image. Keys fold
    with :func:`fold_u32` (the image the device reproduces from raw
    bytes); a phrase key is credited with the COUNT of distinct int64
    grams mapping to it, so an intra-license collision overcounts (sound:
    the gate and the phrase confidence may only ever exceed the host
    oracle, never undershoot it).

    Dedup note: the device dedups text grams in the FOLDED domain while
    the host dedups in int64 first, so two distinct int64
    grams of one text colliding in their low 32 bits score once on
    device and twice on host (~T^2/2^33 per text); the classifier's EPS
    confirm band absorbs it like every other device/host rounding gap.
    """
    m = max(1, int(model_shards))
    L = len(licenses)
    Ls = -(-L // m)
    shard_pairs: list[dict[int, dict[int, list[float]]]] = [
        {} for _ in range(m)
    ]
    for li, lic in enumerate(licenses):
        shard, local = divmod(li, Ls)
        tbl = shard_pairs[shard]
        fk = full_keys.get(lic)
        if fk is not None and len(fk):
            w = full_weights[lic]
            for k, kw in zip(fold_u32(fk).tolist(), w.tolist()):
                ent = tbl.setdefault(k, {}).setdefault(local, [0.0, 0.0])
                ent[0] += kw
        pk = phrase_keys.get(lic)
        if pk is not None and len(pk):
            for k in fold_u32(np.unique(pk)).tolist():
                ent = tbl.setdefault(k, {}).setdefault(local, [0.0, 0.0])
                ent[1] += 1.0
    Ku = max(1, max(len(t) for t in shard_pairs))
    keys = np.full((m, Ku), SENT32, dtype=np.uint32)
    credit = np.zeros((m, Ku, 2 * Ls), dtype=np.float32)
    for s, tbl in enumerate(shard_pairs):
        for ki, k in enumerate(sorted(tbl)):
            keys[s, ki] = k
            for local, (w, p) in tbl[k].items():
                credit[s, ki, local] = w
                credit[s, ki, Ls + local] = p
    wtot = np.zeros(L, dtype=np.float64)
    n_units = np.zeros(L, dtype=np.int64)
    n_short = np.zeros(L, dtype=np.int64)
    for li, lic in enumerate(licenses):
        w = full_weights.get(lic)
        wtot[li] = float(w.sum()) if w is not None and len(w) else 0.0
        pk = phrase_keys.get(lic)
        shorts = phrase_short.get(lic, [])
        n_short[li] = len(shorts)
        n_units[li] = (len(pk) if pk is not None else 0) + len(shorts)
    return CorpusTable32(
        keys=keys, credit=credit,
        gate=build_shingle_gate(corpus_texts, anchor_texts, lut),
        lut=np.asarray(lut, dtype=np.int64),
        p1=int(p1), p2=int(p2), hash_p=int(hash_p), ngram=int(ngram),
        n_shards=m, lic_per_shard=Ls, n_licenses=L,
        wtot=wtot, n_units=n_units, n_short=n_short,
    )


def pack_gram_rows(
    keys32: np.ndarray,
    text_ids: np.ndarray,
    n_texts: int,
    max_row: int = 8192,
    min_row: int = 256,
):
    """Pack per-text sorted-unique int32 gram keys into padded row
    matrices, bucketed by row length (every dispatch shape compiles
    once — the same bucket-ladder discipline as ``TpuSecretScanner``).

    Returns ``(groups, overflow)`` where each group is ``(rows [n, T],
    text_indices [n])`` for one T bucket and ``overflow`` lists texts
    whose unique gram count exceeds ``max_row`` (they take the host
    path — a >64 KB license text is rare enough that splitting rows is
    not worth the extra kernel variant).
    """
    if len(keys32) == 0:
        return [], []
    # one flat int64 sort instead of a two-key lexsort: text id in the
    # high bits, the key's order-preserving uint32 image in the low bits
    # (biasing by 2^31 maps int32 order onto unsigned order)
    combined = (text_ids.astype(np.int64) << np.int64(32)) | (
        keys32.astype(np.int64) + np.int64(1 << 31)
    )
    combined.sort()
    keep = np.empty(len(combined), dtype=bool)
    keep[0] = True
    np.not_equal(combined[1:], combined[:-1], out=keep[1:])
    combined = combined[keep]
    t = combined >> np.int64(32)
    k = ((combined & np.int64(0xFFFFFFFF)) - np.int64(1 << 31)).astype(
        np.int32
    )
    counts = np.bincount(t, minlength=n_texts)
    offsets = np.zeros(n_texts + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    overflow = np.nonzero(counts > max_row)[0].tolist()
    # bucket texts by padded row length (power-of-two ladder)
    buckets: dict[int, list[int]] = {}
    for ti in np.nonzero((counts > 0) & (counts <= max_row))[0].tolist():
        b = min_row
        while b < counts[ti]:
            b *= 2
        buckets.setdefault(b, []).append(ti)
    groups = []
    for T in sorted(buckets):
        tis = buckets[T]
        rows = np.full((len(tis), T), PAD_KEY, dtype=np.int32)
        for ri, ti in enumerate(tis):
            rows[ri, : counts[ti]] = k[offsets[ti] : offsets[ti + 1]]
        groups.append((rows, np.asarray(tis, dtype=np.int64)))
    return groups, overflow


# -- raw-bytes device scoring (ISSUE 17 tentpole): tokenize + hash + score
# -- from uint8 rows entirely on device; the host ships bytes, not grams ----

# width bucket ladder for packed text rows; every (bucket, corpus) pair
# compiles exactly once. 49152 covers the longest common full license
# text (GPL-3.0 ~35 KB); longer texts take the host oracle (the same
# wide-window confirm rung the secret scanner uses)
BYTES_WIDTHS = (1024, 2048, 4096, 8192, 16384, 32768, 49152)
# per-dispatch element budget: row count per bucket derives as
# BYTES_ROW_ELEMS // width so every dispatch moves similar work
BYTES_ROW_ELEMS = 1 << 20


def _u32_const(v: int) -> np.uint32:
    return np.uint32(np.int64(v).astype(np.uint64) & _MASK64)


def pack_text_rows(
    encoded: list[bytes], max_width: int = 0, widths=BYTES_WIDTHS
):
    """Pack latin-1 text buffers into zero-padded uint8 row matrices,
    bucketed by width -> ``(groups, wide)``: ``groups`` maps width ->
    ``(rows [n, W] uint8, text_indices [n])``; ``wide`` lists texts at or
    above the width cap (host-oracle rung). A text always packs strictly
    below its bucket width, so at least one trailing zero separator
    terminates its last word exactly like the host tokenizer's EOF."""
    cap = int(max_width) or widths[-1]
    ladder = [w for w in widths if w <= cap]
    if not ladder:
        ladder = [widths[0]]
    buckets: dict[int, list[int]] = {}
    wide: list[int] = []
    for ti, e in enumerate(encoded):
        n = len(e)
        if n == 0:
            continue
        if n >= ladder[-1]:
            wide.append(ti)
            continue
        for w in ladder:
            if n < w:
                buckets.setdefault(w, []).append(ti)
                break
    groups: dict[int, tuple[np.ndarray, np.ndarray]] = {}
    for w in sorted(buckets):
        tis = buckets[w]
        rows = np.zeros((len(tis), w), dtype=np.uint8)
        for ri, ti in enumerate(tis):
            e = encoded[ti]
            rows[ri, : len(e)] = np.frombuffer(e, dtype=np.uint8)
        groups[w] = (rows, np.asarray(tis, dtype=np.int64))
    return groups, wide


def build_bytes_gate_fn(row_len: int, lut: np.ndarray):
    """Jitted two-lane shingle gate: ``(rows [B, C] uint8, bloom8,
    bloom4) -> (block_hits [B, C/512] int32, anchor_hits [B] int32,
    word_bytes [B] int32)``. Pure elementwise packing + two bloom
    gathers per byte + blocked sums (the ops/match.py formulation that
    runs at memory bandwidth) — no scans, no sorts, no binary searches.
    Thresholding happens host-side, so gate knobs never recompile."""
    import jax
    import jax.numpy as jnp

    C = int(row_len)
    if C % SHINGLE_BLOCK:
        raise ValueError(f"row width {C} not a multiple of {SHINGLE_BLOCK}")
    lut32 = lut_low32(lut)

    def gate(rows, bloom8, bloom4):
        B = rows.shape[0]
        v = jnp.asarray(lut32)[rows.astype(jnp.int32)]  # [B, C] uint32
        sv = jnp.where(v == 0, jnp.uint32(32), v)

        def sh(d):
            return jnp.pad(
                sv[:, d:], ((0, 0), (0, d)), constant_values=np.uint32(32)
            )

        valid = v != 0
        # main lane: 8-byte windows -> per-512-block hit counts
        wlo = (
            sv
            + sh(1) * jnp.uint32(1 << 8)
            + sh(2) * jnp.uint32(1 << 16)
            + sh(3) * jnp.uint32(1 << 24)
        )
        whi = (
            sh(4)
            + sh(5) * jnp.uint32(1 << 8)
            + sh(6) * jnp.uint32(1 << 16)
            + sh(7) * jnp.uint32(1 << 24)
        )
        h8 = (wlo * _SHINGLE_MIX + whi * _SHINGLE_P2) >> jnp.uint32(
            32 - SHINGLE_BITS
        )
        hit8 = ((bloom8.reshape(-1)[h8] != 0) & valid).astype(jnp.int32)
        blk = jnp.sum(
            hit8.reshape(B, C // SHINGLE_BLOCK, SHINGLE_BLOCK), axis=2
        )
        # anchor lane: 4-byte windows, whole-row count
        w4 = wlo  # identical packing
        h4 = (w4 * _SHINGLE_MIX) >> jnp.uint32(32 - SHINGLE_BITS)
        ahits = jnp.sum(
            (bloom4.reshape(-1)[h4] != 0) & valid, axis=1, dtype=jnp.int32
        )
        nb = jnp.sum(valid, axis=1, dtype=jnp.int32)
        return blk, ahits, nb

    return gate


def build_bytes_score_fn(
    row_len: int,
    gram_cap: int,
    lic_per_shard: int,
    lut: np.ndarray,
    p1: int,
    p2: int,
    hash_p: int,
    ngram: int = 5,
):
    """The ``score_from_bytes`` kernel body: ``(rows [B, C] uint8, keys
    [.., Ku] uint32, credit [.., Ku, 2*Ls]) -> (full_w [B, Ls], phrase
    [B, Ls], n_uniq [B] int32)``.

    Extends ops/gram_gate.py's on-device rolling-hash machinery (LUT
    lowering, zero-run word segmentation, prefix-sum word moments,
    chained next-start gram folds — all in the exact uint32 low-32 image
    of the host's int64 hashes) into full scoring: per-position gram keys
    sort per row, which compacts valid keys left AND dedups them (first-
    occurrence mask — the host's np.unique), the first ``gram_cap``
    columns binary-search the shard's corpus keys, and matched credit
    rows accumulate in G-chunked gathers (scan keeps the [B, chunk, 2Ls]
    transient bounded). ``n_uniq`` counts unique valid keys over the FULL
    row so the host can detect gram_cap overflow and reroute that row to
    the exact oracle instead of silently under-scoring it."""
    import jax
    import jax.numpy as jnp

    C, Ls = int(row_len), int(lic_per_shard)
    G = max(256, int(gram_cap))
    CH = 256  # credit-gather chunk (G is always a multiple: widths/4)
    G = -(-G // CH) * CH
    lut32 = lut_low32(lut)
    P1, P2, HP = _u32_const(p1), _u32_const(p2), _u32_const(hash_p)
    SENT = jnp.uint32(0xFFFFFFFF)

    def score(rows, keys, credit):
        keys = keys.reshape(-1)
        Ku = keys.shape[0]
        credit_ = credit.reshape(Ku, -1)
        B = rows.shape[0]
        vals = jnp.asarray(lut32)[rows.astype(jnp.int32)]  # [B, C] uint32
        nz = vals != 0
        idx = jnp.arange(C, dtype=jnp.int32)
        posw = idx.astype(jnp.uint32)
        prev_nz = jnp.pad(nz[:, :-1], ((0, 0), (1, 0)))
        starts = nz & ~prev_nz
        sep_idx = jnp.where(~nz, idx, C)
        nsep = jax.lax.cummin(sep_idx, axis=1, reverse=True)
        pref0 = jnp.pad(
            jnp.cumsum(vals, axis=1, dtype=jnp.uint32), ((0, 0), (1, 0))
        )
        pref1 = jnp.pad(
            jnp.cumsum(vals * posw[None, :], axis=1, dtype=jnp.uint32),
            ((0, 0), (1, 0)),
        )
        s0 = jnp.take_along_axis(pref0, nsep, axis=1) - pref0[:, :C]
        s1 = jnp.take_along_axis(pref1, nsep, axis=1) - pref1[:, :C]
        s1 = s1 - posw[None, :] * s0
        H = s0 * P1 + s1 * P2  # exact low-32 word hash at start positions
        start_idx = jnp.where(starts, idx, C)
        ns = jnp.concatenate(
            [
                jax.lax.cummin(start_idx, axis=1, reverse=True)[:, 1:],
                jnp.full((B, 1), C, dtype=jnp.int32),
            ],
            axis=1,
        )
        ns_pad = jnp.concatenate(
            [ns, jnp.full((B, 1), C, dtype=jnp.int32)], axis=1
        )
        H_pad = jnp.concatenate(
            [H, jnp.zeros((B, 1), dtype=jnp.uint32)], axis=1
        )
        key = H
        p = jnp.broadcast_to(idx[None, :], (B, C))
        for _ in range(ngram - 1):
            p = jnp.take_along_axis(ns_pad, p, axis=1)
            key = key * HP + jnp.take_along_axis(H_pad, p, axis=1)
        vgram = starts & (p < C)  # all ngram word starts inside the row
        kk = jnp.where(
            vgram, jnp.minimum(key, jnp.uint32(0xFFFFFFFE)), SENT
        )
        ks = jnp.sort(kk, axis=1)  # valid keys left, dedup for free
        fresh = jnp.concatenate(
            [jnp.ones((B, 1), dtype=bool), ks[:, 1:] != ks[:, :-1]], axis=1
        )
        n_uniq = jnp.sum(fresh & (ks != SENT), axis=1, dtype=jnp.int32)
        Geff = min(G, C)
        kg = ks[:, :Geff]
        mg = fresh[:, :Geff] & (kg != SENT)
        pos = jnp.minimum(
            jnp.searchsorted(keys, kg.ravel()).reshape(B, Geff), Ku - 1
        )
        hit = (jnp.take(keys, pos) == kg) & mg

        # chunked credit gather: [B, CH, 2*Ls] transient per step instead
        # of one [B, G, 2*Ls] monster; a masked sum, not a matmul: TPU
        # lowers f32 matmuls to bf16 multiplies by default (~2^-8 relative
        # error — far outside the classifier's EPS band), while a
        # where+sum reduces in exact f32 on every backend)
        nch = Geff // CH
        pos_c = pos[:, : nch * CH].reshape(B, nch, CH).transpose(1, 0, 2)
        hit_c = hit[:, : nch * CH].reshape(B, nch, CH).transpose(1, 0, 2)

        def body(acc, chunk):
            pc, hc = chunk
            v = jnp.take(credit_, pc, axis=0)  # [B, CH, 2*Ls]
            return acc + jnp.sum(
                jnp.where(hc[:, :, None], v, 0.0), axis=1
            ), None

        s, _ = jax.lax.scan(
            body,
            jnp.zeros((B, credit_.shape[1]), dtype=jnp.float32),
            (pos_c, hit_c),
        )
        return s[:, :Ls], s[:, Ls:], n_uniq

    return score


class DeviceBytesScorer:
    """Raw-bytes scorer: the corpus table, shingle bloom and anchor set
    are committed to device memory once; per scan only zero-padded uint8
    text rows cross the link (the arena-slab traffic the link budget
    already pays) — no host tokenization, no gram rows. Kernels compile
    lazily per width bucket. With a mesh, rows shard over 'data' and the
    corpus over 'model' (:func:`trivy_tpu.parallel.mesh.corpus_sharding`);
    instances are cached per mesh by :func:`get_bytes_scorer`."""

    def __init__(self, table: CorpusTable32, mesh=None):
        import jax

        self.table = table
        self.mesh = mesh
        self._gate_fns: dict[int, object] = {}
        self._score_fns: dict[int, object] = {}
        self._take_fns: dict = {}
        blooms = (table.gate.bloom8, table.gate.bloom4)
        if mesh is None:
            self.corpus_device = (
                jax.device_put(table.keys), jax.device_put(table.credit),
            )
            self.bloom_device = tuple(jax.device_put(b) for b in blooms)
            self.data_parallelism = 1
        else:
            from trivy_tpu.parallel.mesh import corpus_sharding

            if int(mesh.shape["model"]) != table.n_shards:
                raise ValueError(
                    f"corpus built for {table.n_shards} model shards but "
                    f"mesh has model={int(mesh.shape['model'])}"
                )
            from jax.sharding import NamedSharding, PartitionSpec

            self.corpus_device = tuple(
                jax.device_put(a, corpus_sharding(mesh, a.ndim))
                for a in (table.keys, table.credit)
            )
            rep = NamedSharding(mesh, PartitionSpec())
            self.bloom_device = tuple(
                jax.device_put(b, rep) for b in blooms
            )
            self.data_parallelism = int(mesh.shape["data"])
        # HBM ledger: corpus table + shingle blooms are the raw-bytes
        # lane's once-per-process resident footprint
        from trivy_tpu.obs import recorder as flight

        flight.note_resident(
            "corpus",
            sum(int(a.nbytes)
                for a in (table.keys, table.credit, *blooms)),
        )
        self.dispatch_count = 0
        self.upload_bytes = 0  # telemetry: row bytes that crossed the link

    def rows_per_dispatch(self, width: int) -> int:
        """Row-count rung for one width bucket: a fixed function of the
        width (one compiled shape per kernel per bucket), rounded up to
        the mesh data parallelism."""
        dp = max(1, self.data_parallelism)
        b = max(8, BYTES_ROW_ELEMS // int(width))
        return -(-b // dp) * dp

    def put_rows(self, rows: np.ndarray):
        """Upload one padded row batch (the only per-scan link traffic)."""
        import jax

        self.upload_bytes += rows.nbytes
        if self.mesh is None:
            return jax.device_put(rows)
        from trivy_tpu.parallel.mesh import batch_sharding

        return jax.device_put(rows, batch_sharding(self.mesh))

    def gate_bytes(self, rows_dev, width: int):
        """Async shingle gate on a resident batch -> (block_hits,
        anchor_hits, word_bytes) device arrays."""
        import jax

        fn = self._gate_fns.get(width)
        if fn is None:
            gate = build_bytes_gate_fn(width, self.table.lut)
            if self.mesh is None:
                from trivy_tpu.obs import recorder as flight

                fn = flight.instrument_jit("ops.bytes_gate", gate)
            else:
                from trivy_tpu.parallel.mesh import sharded_bytes_gate_fn

                fn = sharded_bytes_gate_fn(gate, self.mesh)
            self._gate_fns[width] = fn
        self.dispatch_count += 1
        return fn(rows_dev, *self.bloom_device)

    def score_from_bytes(self, rows_dev, width: int):
        """Async full scoring on a resident batch -> (full_w [B, m*Ls],
        phrase [B, m*Ls], n_uniq [B]) device arrays. The tentpole entry:
        tokenization, hashing, dedup, corpus binary search and credit
        accumulation all happen on device."""
        import jax

        t = self.table
        fn = self._score_fns.get(width)
        if fn is None:
            score = build_bytes_score_fn(
                width, width // 4, t.lic_per_shard, t.lut,
                t.p1, t.p2, t.hash_p, t.ngram,
            )
            if self.mesh is None:
                from trivy_tpu.obs import recorder as flight

                fn = flight.instrument_jit("ops.bytes_score", score)
            else:
                from trivy_tpu.parallel.mesh import sharded_bytes_score_fn

                fn = sharded_bytes_score_fn(score, self.mesh)
            self._score_fns[width] = fn
        self.dispatch_count += 1
        return fn(rows_dev, *self.corpus_device)

    def gram_cap(self, width: int) -> int:
        """Unique-gram capacity of the score kernel at one width (rows
        whose n_uniq exceeds it reroute to the host oracle)."""
        return max(256, width // 4)

    def take_rows(self, rows_dev, idx: np.ndarray, out_rows: int):
        """Device-side row selection for the score stage: the gate batch
        stays resident and flagged rows are gathered by index — no second
        upload. Single-device flavor only (the mesh path re-packs host
        rows: arbitrary row gathers cross shard boundaries)."""
        import jax
        import jax.numpy as jnp

        if self.mesh is not None:
            raise ValueError("take_rows: host re-pack under a mesh")
        shape = (rows_dev.shape, int(out_rows))
        fn = self._take_fns.get(shape)
        if fn is None:
            from trivy_tpu.obs import recorder as flight

            fn = flight.instrument_jit(
                "ops.take_rows", lambda arr, i: jnp.take(arr, i, axis=0)
            )
            self._take_fns[shape] = fn
        full = np.zeros(out_rows, dtype=np.int32)
        full[: len(idx)] = idx
        return fn(rows_dev, full)


_SCORER_CACHE: dict = {}
_SCORER_LOCK = threading.Lock()


def get_bytes_scorer(build_table, mesh=None) -> DeviceBytesScorer:
    """Process-wide raw-bytes scorer cache: corpus + bloom upload once
    per (corpus, mesh) and stay HBM-resident across scans, and across
    classifier instances. ``build_table`` is a one-arg callable (model
    shard count) invoked only on a cache miss; the key is the mesh
    identity. Locked: analyzer finalizes may race from worker threads and
    the table must upload exactly once."""
    if mesh is None:
        key = ("bytes", None)
    else:
        key = (
            "bytes", tuple(mesh.devices.flat), mesh.axis_names,
            mesh.shape["model"],
        )
    with _SCORER_LOCK:
        scorer = _SCORER_CACHE.get(key)
        if scorer is None:
            model = 1 if mesh is None else int(mesh.shape["model"])
            scorer = DeviceBytesScorer(build_table(model), mesh=mesh)
            _SCORER_CACHE[key] = scorer
    return scorer
