"""The table-driven secret rule pass: every rule against every row in one
compiled program whose shape does not depend on the rule count.

Replaces the reference's per-file, per-rule Go-regexp loop (ref:
pkg/fanal/secret/scanner.go:377-463, the north-star hot loop) with one
data-parallel pass over a batch of fixed-size byte rows. The ruleset is
data: the tables of ``secret.device_compile.build_tables`` are arguments,
padded to power-of-two rungs, so a ruleset of the same padded sizes reuses
the compiled program and 5,000 rules cost no more program than 100.

Per batch ``rows [B, C] uint8``:

1. **dense key test.** Rows are zero-padded by ``margin`` bytes on each
   side (the padding is real zero bytes: class membership and the
   word-boundary test read it, byte-for-byte as ``ops/match.py``'s
   matcher). At every position the ASCII-lowered (A-Z only) 4-byte word is
   hashed into a fixed 2^22-bit bitmap of the entries' keys; a set bit
   makes the position a *candidate*.
2. **candidate pages.** Candidates are compacted by a prefix count and a
   binary search into pages of ``PAGE`` positions (a ``while_loop`` over
   as many pages as the batch has, so no candidate is ever dropped).
   Each candidate probes the two buckets of its 4-byte key and of its
   3-byte key, and the overflow list, for entries whose key it carries.
3. **exact literal test.** Each probed entry is verified byte-for-byte
   at its start (key position minus the key's offset in the literal):
   the lowered text for the keyword test, the raw text for an anchor.
   A verified keyword sets its keyword bit for the row.
4. **anchored variants.** Verified anchors expand into (anchor, variant)
   items, compacted into pages of ``ITEMS`` the same way; each item's
   ``(class, count, delta)`` rows gather their bytes and test them
   against the class-membership table. A passing variant sets its bit.

Steps 3 and 4 run on two lanes. The narrow lane tests every entry on its
first ``lw`` words and every variant on its first ``width`` rows, where
``build_tables`` derives both widths from the ruleset's own histogram
(the smallest power of two that holds ``NARROW_PCT``, 95%, of its
literals and of its variants), not from its widest literal or variant.
An entry or variant wider than that has a row of its own in the
full-width spill tables (``ent_spill``, ``var_spill``): the (candidate,
slot) pairs and items of such entries that pass the narrow test, a
necessary condition, are compacted into pages of ``SPILL`` the same way
and tested again at full width, which decides them. A ruleset with
nothing wider has an empty spill lane, whose loops run zero times.

Work is per candidate position, not per variant per position. The result
is one ``[B, out_words]`` uint32 array: the keyword and variant bits, then
per row the number of literals verified through the overflow list.

Device contract: per-(row, rule) hits with possible false positives and
NO false negatives (keyword bits are exact substring presence); the host
confirm re-runs the exact engine on flagged (file, rule) pairs only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from trivy_tpu.secret.device_compile import (
    _GOLD,
    _H1,
    _H2,
    _SALT3,
    BITMAP_BITS,
    CHECK_SPAN,
    TABLE_ORDER,
)

# candidate positions per page of the outer loop
PAGE = 4096
# (anchor, variant) items per page of the inner loop
ITEMS = 1024
# (candidate, slot) pairs or items per page of a spill lane's loop
SPILL = 128


def _mul_hi(k, mult: int, bits: int):
    return (k * jnp.uint32(mult)) >> (32 - bits)


def _words(x):
    """Little-endian 4-byte words: ``w[:, p]`` packs bytes p..p+3."""
    v = x.astype(jnp.uint32)
    n = x.shape[1] - 3
    return (v[:, :n] | (v[:, 1:n + 1] << 8) | (v[:, 2:n + 2] << 16)
            | (v[:, 3:n + 3] << 24))


def build_rules_fn(margin: int, kw_bits: int):
    """The pass for tables of this padding and keyword-bit rung (both
    powers of two or multiples of 128, so rulesets share programs):
    ``fn(rows, *RuleTables.operands()) -> [B, out_words] uint32``."""

    def fn(rows, *tables):
        return rules_pass(rows, *tables, margin=margin, kw_bits=kw_bits)

    return fn


def rules_pass(rows, *tables, margin: int, kw_bits: int):
    """``rows [B, C] uint8`` and the tables (``TABLE_ORDER``) ->
    ``[B, out_words] uint32`` (see the module docstring)."""
    t = dict(zip(TABLE_ORDER, tables))
    B, C = rows.shape
    M = margin
    L = C + 2 * M
    nbits = kw_bits + t["var_rows"].shape[0]
    nb, S = t["slot_key"].shape
    bucket_bits = nb.bit_length() - 1
    lanes = jnp.arange(t["ent_low"].shape[1], dtype=jnp.int32) * 4
    lanes_w = jnp.arange(t["spill_low"].shape[1], dtype=jnp.int32) * 4

    x = jnp.pad(rows, ((0, 0), (M, M + 3)))  # [B, L + 3] real zero bytes
    up = (x >= 65) & (x <= 90)
    xl = jnp.where(up, x + 32, x)
    rw = _words(x)  # [B, L]
    lw_ = _words(xl)
    rw_flat = rw.reshape(-1)
    lw_flat = lw_.reshape(-1)
    xb_flat = x[:, :L].reshape(-1)

    # 1. dense key test over key positions [0, C + omax) of the row
    D = C + M - 4
    keys = lw_[:, M:M + D]
    h = _mul_hi(keys, _GOLD, BITMAP_BITS)
    bit = (t["bitmap"][(h >> 5).astype(jnp.int32)] >> (h & 31)) & 1
    q = jnp.arange(D, dtype=jnp.int32)
    cand = (bit == 1) & (q < C + t["omax"])[None, :]
    cs = jnp.cumsum(cand.reshape(-1).astype(jnp.int32))
    total = cs[-1]

    slot_key, slot_tag = t["slot_key"], t["slot_tag"]
    ovf_key, ovf_klen, ovf_ent = t["ovf_key"], t["ovf_klen"], t["ovf_ent"]
    n_slots = 2 + ovf_ent.shape[0]
    span = jnp.arange(CHECK_SPAN, dtype=jnp.int32)

    def bucket(k, klen, mult):
        k = jnp.where(klen == 3, k ^ jnp.uint32(_SALT3), k)
        return _mul_hi(k, mult, bucket_bits).astype(jnp.int32)

    def literal(r, s, ent_low, ent_raw, ent_mask, lanes):
        """(lowered, raw) matches of the literals ``ent_*`` (rows of
        words, ``lanes`` wide) starting at ``s`` of rows ``r``."""
        pos = jnp.clip(M + s[..., None] + lanes, 0, L - 1)
        widx = r[..., None] * L + pos
        low_ok = jnp.all(((lw_flat[widx] ^ ent_low) & ent_mask) == 0, axis=-1)
        raw_ok = jnp.all(((rw_flat[widx] ^ ent_raw) & ent_mask) == 0, axis=-1)
        return low_ok, raw_ok

    def variant(vr, r, s):
        """Whether the window-check rows ``vr [n, width, 3]`` of variants
        anchored at ``s`` of rows ``r`` all pass."""
        cls, cnt, dlt = vr[..., 0], vr[..., 1], vr[..., 2]
        p = jnp.clip(M + s[:, None, None] + dlt[:, :, None] + span, 0, L - 1)
        byte = xb_flat[r[:, None, None] * L + p].astype(jnp.int32)
        member = t["classes"][cls[:, :, None] * 256 + byte] == 1
        return jnp.all(member | (span >= cnt[:, :, None]), axis=(1, 2))

    def compacted(flags, body, init):
        """Fold ``carry = body(j, ok, carry)`` over pages of ``SPILL`` of
        the indices ``j`` where ``flags`` is set (``ok``: ``j`` is one)."""
        fcs = jnp.cumsum(flags.astype(jnp.int32))

        def step(st):
            k, carry = st
            i = k * SPILL + jnp.arange(SPILL, dtype=jnp.int32)
            ok = i < fcs[-1]
            j = jnp.where(ok, jnp.searchsorted(fcs, i, side="right"), 0)
            return k + 1, body(j, ok, carry)

        return jax.lax.while_loop(lambda st: st[0] * SPILL < fcs[-1], step,
                                  (jnp.int32(0), init))[1]

    def page(state):
        pg, bits, ovf = state
        j = pg * PAGE + jnp.arange(PAGE, dtype=jnp.int32)
        valid = j < total
        flat = jnp.where(valid, jnp.searchsorted(cs, j, side="right"), 0)
        row = flat // D
        qq = flat % D
        key4 = lw_flat[row * L + M + qq]
        key3 = key4 & jnp.uint32(0xFFFFFF)

        # 2. probe: two buckets per key length, S slots each, + overflow
        bks = jnp.stack([bucket(key4, 4, _H1), bucket(key4, 4, _H2),
                         bucket(key3, 3, _H1), bucket(key3, 3, _H2)], axis=1)
        sl = (bks[:, :, None] * S + jnp.arange(S, dtype=jnp.int32)).reshape(PAGE, -1)
        sk, st = slot_key.reshape(-1)[sl], slot_tag.reshape(-1)[sl]
        three = (st & 1) == 1
        hit4 = (st >= 0) & ~three & (sk == key4[:, None])
        hit3 = (st >= 0) & three & (sk == key3[:, None])
        e4 = jnp.max(jnp.where(hit4, st >> 1, -1), axis=1)
        e3 = jnp.max(jnp.where(hit3, st >> 1, -1), axis=1)
        ho = (((ovf_klen == 4)[None, :] & (ovf_key[None, :] == key4[:, None]))
              | ((ovf_klen == 3)[None, :] & (ovf_key[None, :] == key3[:, None])))
        eo = jnp.where(ho, ovf_ent[None, :], -1)
        ents = jnp.concatenate([e4[:, None], e3[:, None], eo], axis=1)
        ents = jnp.where(valid[:, None], ents, -1)  # [PAGE, n_slots]

        # 3. exact literal test at the literal's start: the narrow lane,
        # then the spill lane for the wider entries that passed it
        e = jnp.maximum(ents, 0)
        s = qq[:, None] - t["ent_off"][e]
        live = (ents >= 0) & (s >= 0) & (s < C)
        low_ok, raw_ok = literal(row[:, None], s, t["ent_low"][e], t["ent_raw"][e],
                                 t["ent_mask"][e], lanes)
        wide = t["ent_spill"][e].reshape(-1)
        r_flat = jnp.broadcast_to(row[:, None], s.shape).reshape(-1)
        s_flat = s.reshape(-1)

        def spill_literals(jj, ok, oks):
            w = jnp.maximum(wide[jj], 0)
            lo, ra = literal(r_flat[jj], s_flat[jj], t["spill_low"][w], t["spill_raw"][w],
                             t["spill_mask"][w], lanes_w)
            dst = jnp.where(ok, jj, PAGE * n_slots)
            return oks[0].at[dst].set(lo, mode="drop"), oks[1].at[dst].set(ra, mode="drop")

        low_ok, raw_ok = compacted(
            live.reshape(-1) & (wide >= 0) & (low_ok | raw_ok).reshape(-1),
            spill_literals, (low_ok.reshape(-1), raw_ok.reshape(-1)))
        low_ok, raw_ok = low_ok.reshape(s.shape), raw_ok.reshape(s.shape)
        kbit = t["ent_kbit"][e]
        kw_ok = live & low_ok & (kbit >= 0)
        an_ok = live & raw_ok & (t["ent_anchor"][e] == 1)
        rowb = row[:, None] * nbits
        bits = bits.at[jnp.where(kw_ok, rowb + kbit, B * nbits).reshape(-1)].set(
            True, mode="drop")
        from_ovf = jnp.arange(n_slots)[None, :] >= 2
        ovf = ovf.at[row].add(jnp.sum((kw_ok | an_ok) & from_ovf, axis=1).astype(jnp.uint32)
                              * valid.astype(jnp.uint32))

        # 4. anchored variants of the verified anchors: the narrow lane,
        # then the spill lane for the wider variants that passed it
        counts = jnp.where(an_ok, t["ent_vcount"][e], 0).reshape(-1)
        ics = jnp.cumsum(counts)
        n_items = ics[-1]
        vstart = t["ent_vstart"][e].reshape(-1)

        def items(st2):
            k, bits = st2
            i = k * ITEMS + jnp.arange(ITEMS, dtype=jnp.int32)
            ok_i = i < n_items
            pair = jnp.where(ok_i, jnp.searchsorted(ics, i, side="right"), 0)
            within = i - (ics[pair] - counts[pair])
            var = jnp.where(ok_i, vstart[pair] + within, 0)
            r_i = r_flat[pair]
            s_i = s_flat[pair]
            ok = variant(t["var_rows"][var], r_i, s_i) & ok_i
            vw = t["var_spill"][var]
            idx = jnp.where(ok & (vw < 0), r_i * nbits + kw_bits + var, B * nbits)
            bits = bits.at[idx].set(True, mode="drop")

            def spill_variants(jj, ok_j, bits):
                ok_w = variant(t["spill_var_rows"][jnp.maximum(vw[jj], 0)], r_i[jj], s_i[jj])
                idx = jnp.where(ok_w & ok_j, r_i[jj] * nbits + kw_bits + var[jj], B * nbits)
                return bits.at[idx].set(True, mode="drop")

            return k + 1, compacted(ok & (vw >= 0), spill_variants, bits)

        _, bits = jax.lax.while_loop(lambda st2: st2[0] * ITEMS < n_items, items,
                                     (jnp.int32(0), bits))
        return pg + 1, bits, ovf

    bits0 = jnp.zeros((B * nbits,), dtype=bool)
    ovf0 = jnp.zeros((B,), dtype=jnp.uint32)
    _, bits, ovf = jax.lax.while_loop(lambda st: st[0] * PAGE < total, page,
                                      (jnp.int32(0), bits0, ovf0))
    packed = bits.reshape(B, nbits // 32, 32).astype(jnp.uint32)
    packed = jnp.sum(packed << jnp.arange(32, dtype=jnp.uint32), axis=2, dtype=jnp.uint32)
    return jnp.concatenate([packed, ovf[:, None]], axis=1)
