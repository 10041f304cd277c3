"""Plain jnp secret matcher, unrolled per rule: the oracle of the
table-driven rule pass (``ops/rules.py``).

One data-parallel pass over a batch of fixed-size byte chunks, written
rule by rule as static jnp code, so its program grows with the ruleset —
which is why the scanner runs ``ops/rules.py`` instead. Tests hold the
rule pass to this matcher bit for bit. Three building blocks:

- **Anchor/keyword literals**: the first 4 bytes of every literal compare as
  one packed uint32 word (built once with shifts/ors); remaining bytes are
  shifted byte-equality ANDs. No hashing, no multiplies.
- **Character classes**: compiled to interval lists at build time; class
  membership is a handful of range compares. No table gathers.
- **Window checks** ("n consecutive bytes all in class"): sparse-table
  doubling — ``D[k][p] = all-in-class over [p, p+2^k)`` built by
  ``D[k+1][p] = D[k][p] & D[k][p+2^k]``; an arbitrary-length window is the
  AND of two overlapping power-of-two windows. O(log n) passes, no cumsum.

Device contract (see trivy_tpu.secret.device_compile): per-(chunk, rule) hit
booleans with possible false positives and NO false negatives; the host
confirm stage re-runs the exact engine on flagged (file, rule) pairs only.
The returned function is jittable and shards over a device mesh along the
batch axis (see trivy_tpu.parallel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from trivy_tpu.obs import recorder as flight
from trivy_tpu.secret.device_compile import CompiledRules

_ALNUM_INTERVALS = [(48, 57), (65, 90), (97, 122)]


def _intervals(chars: frozenset) -> list[tuple[int, int]]:
    """Sorted byte set -> minimal closed intervals."""
    out: list[tuple[int, int]] = []
    for b in sorted(chars):
        if out and b == out[-1][1] + 1:
            out[-1] = (out[-1][0], b)
        else:
            out.append((b, b))
    return out


def _word32(lit: bytes) -> int:
    """First 4 bytes little-endian packed."""
    w = 0
    for i in range(4):
        w |= lit[i] << (8 * i)
    return w


def build_match_fn(compiled: CompiledRules, chunk_len: int):
    """Build the jitted matcher: ``chunks [B, chunk_len] uint8 -> [B, R] bool``.

    A True at ``[b, r]`` means rule ``compiled.rule_ids[r]`` *may* match
    within chunk ``b`` (for anchored rules the full device window was
    verified; for keyword rules a keyword substring is present).
    """
    C = chunk_len
    M = max(8, compiled.margin + 4)
    num_rules = compiled.num_rules

    # class interval tables (compile-time)
    n_classes = compiled.classes.shape[0]
    class_intervals = []
    for cid in range(n_classes):
        chars = frozenset(np.nonzero(compiled.classes[cid])[0].tolist())
        # complement form when it is cheaper (e.g. [^x] classes)
        inv = _intervals(frozenset(range(256)) - chars)
        pos = _intervals(chars)
        if len(inv) < len(pos):
            class_intervals.append(("neg", inv))
        else:
            class_intervals.append(("pos", pos))

    # which doubling levels each class needs: {(cid, k)}
    need_levels: dict[int, int] = {}
    for _, v in compiled.variants:
        for ch in v.checks:
            if ch.count >= 2:
                k = (ch.count).bit_length() - 1
                need_levels[ch.class_id] = max(need_levels.get(ch.class_id, 0), k)

    def fn(chunks: jax.Array) -> jax.Array:
        x = jnp.pad(chunks, ((0, 0), (M, M)))  # [B, L] uint8, zeros
        B = chunks.shape[0]

        def shift(arr: jax.Array, d: int) -> jax.Array:
            """arr[:, M+d : M+d+C] — value at chunk position p+d."""
            return jax.lax.slice_in_dim(arr, M + d, M + d + C, axis=1)

        # packed 4-byte words for literal compares (little-endian)
        xw = x.astype(jnp.uint32)
        word = (
            xw
            + jnp.pad(xw[:, 1:], ((0, 0), (0, 1))) * jnp.uint32(1 << 8)
            + jnp.pad(xw[:, 2:], ((0, 0), (0, 2))) * jnp.uint32(1 << 16)
            + jnp.pad(xw[:, 3:], ((0, 0), (0, 3))) * jnp.uint32(1 << 24)
        )
        if compiled.keywords:
            is_upper = (x >= 65) & (x <= 90)
            xl = jnp.where(is_upper, x + 32, x)
            xlw = xl.astype(jnp.uint32)
            word_l = (
                xlw
                + jnp.pad(xlw[:, 1:], ((0, 0), (0, 1))) * jnp.uint32(1 << 8)
                + jnp.pad(xlw[:, 2:], ((0, 0), (0, 2))) * jnp.uint32(1 << 16)
                + jnp.pad(xlw[:, 3:], ((0, 0), (0, 3))) * jnp.uint32(1 << 24)
            )

        def literal_hit(lit: bytes, data: jax.Array, wdata: jax.Array) -> jax.Array:
            """[B, C] bool: literal starts at position p."""
            if len(lit) >= 4:
                ok = shift(wdata, 0) == jnp.uint32(_word32(lit))
                for j in range(4, len(lit)):
                    ok &= shift(data, j) == lit[j]
            else:
                ok = shift(data, 0) == lit[0]
                for j in range(1, len(lit)):
                    ok &= shift(data, j) == lit[j]
            return ok

        def in_class(cid: int, data: jax.Array) -> jax.Array:
            kind, ivs = class_intervals[cid]
            m = jnp.zeros(data.shape, dtype=bool)
            for lo, hi in ivs:
                if lo == hi:
                    m |= data == lo
                else:
                    m |= (data >= lo) & (data <= hi)
            return ~m if kind == "neg" else m

        # doubling tables: dtab[cid][k][B, L] = all-in-class over [p, p+2^k)
        dtab: dict[int, list[jax.Array]] = {}
        for cid in sorted(need_levels):
            base = in_class(cid, x)
            levels = [base]
            for k in range(need_levels[cid]):
                w = 1 << k
                prev = levels[-1]
                nxt = prev & jnp.pad(prev[:, w:], ((0, 0), (0, w)))
                levels.append(nxt)
            dtab[cid] = levels
        cls0: dict[int, jax.Array] = {}  # single-byte class membership

        def class_base(cid: int) -> jax.Array:
            if cid in dtab:
                return dtab[cid][0]
            if cid not in cls0:
                cls0[cid] = in_class(cid, x)
            return cls0[cid]

        def window_ok(cid: int, n: int, delta: int) -> jax.Array:
            """[B, C] bool at anchor positions p: bytes [p+delta, p+delta+n)
            all in class cid."""
            if n == 1:
                return shift(class_base(cid), delta)
            k = n.bit_length() - 1
            lv = dtab[cid][k]
            w = 1 << k
            hit = shift(lv, delta)
            if n != w:
                hit &= shift(lv, delta + n - w)
            return hit

        # non-alnum membership for word-boundary checks (padding zeros are
        # non-alnum, so chunk-start / file-start positions pass — FP-only)
        na = jnp.ones(x.shape, dtype=bool)
        for lo, hi in _ALNUM_INTERVALS:
            na &= ~((x >= lo) & (x <= hi))

        per_rule: list[list[jax.Array]] = [[] for _ in range(num_rules)]

        for ridx, v in compiled.variants:
            ok = literal_hit(v.anchor, x, word)
            for ch in v.checks:
                ok &= window_ok(ch.class_id, ch.count, ch.delta)
            if v.boundary:
                ok &= shift(na, -v.pre_len - 1)
            per_rule[ridx].append(ok.any(axis=1))

        if compiled.keywords:
            for ridx, kw in compiled.keywords:
                ok = literal_hit(kw, xl, word_l)
                per_rule[ridx].append(ok.any(axis=1))

        cols = [
            functools.reduce(jnp.logical_or, hits)
            if hits
            else jnp.zeros((B,), dtype=bool)
            for hits in per_rule
        ]
        return jnp.stack(cols, axis=1) if cols else jnp.zeros((B, 0), dtype=bool)

    return flight.instrument_jit("ops.match", fn)
