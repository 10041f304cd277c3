"""Device-side slab decompressor: expand a compressed wire buffer into
the raw ``[B, C]`` uint8 batch the prefilter/match stages consume.

Host half (format, gate, encoder, reference decoder):
``trivy_tpu/secret/compress.py``. The codec was *chosen for this
kernel*: every mode decodes with fixed-shape dense array ops — no
data-dependent control flow, no back-references — so one jit per
(rows_bucket, wire_rung) pair covers every batch, and the whole thing
vmaps over rows.

Per row ``i`` the kernel sees ``(buf, offs[i], clen[i], mode[i])`` and
produces ``out[i, :C]``:

- **RAW** — masked gather of ``clen`` bytes from ``buf[offs:]``.
- **PACK7** — pure positional bit math: output byte ``j`` lives at bit
  offset ``7j`` of the row's stream; read the straddling big-endian
  16-bit window and shift. (Byte lanes are masked to the row's extent
  first, so the +1 spill read is always a harmless zero.)
- **TOKEN** — table decode: per-token expansion lengths
  (``tab_len[tok]``), exclusive cumsum for output positions, then
  ``MAX_EXPANSION`` masked scatter rounds writing ``tab_bytes[tok, k]``
  at ``pos + k``. Invalid lanes scatter into a spill slot past ``C``.

Rows with ``clen == 0`` (bucket padding) decode to zero rows — exactly
what the raw path ships for padding, so downstream stages see identical
planes. XLA (not Pallas) on purpose: the hot ops are gather/cumsum/
scatter, which Mosaic lowers poorly.

The kernel computes all three modes for every row and selects one, so
it is not cheap: measured on a v5e behind PCIe, it took ~37% of the
device time of a secret+license fs scan for a ~13% wire saving. It pays
only behind a link much slower than PCIe, which is why
``--secret-compress auto`` leaves it off on the ``host`` and ``pcie``
link classes (``secret/tpu_scanner.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from trivy_tpu.obs import recorder as flight
from trivy_tpu.secret.compress import (
    MAX_EXPANSION,
    MODE_PACK7,
    MODE_TOKEN,
)

__all__ = ["build_decompress_fn"]


def build_decompress_fn(chunk_len: int, tab_bytes: np.ndarray,
                        tab_len: np.ndarray):
    """Jitted ``(buf [W] u8, offs [B] i32, clen [B] i32, mode [B] u8)
    -> [B, C] u8``. ``tab_bytes``/``tab_len`` are the static TOKEN
    expansion tables from the host codec (closed over as constants)."""
    C = chunk_len
    tb = jnp.asarray(tab_bytes)   # [256, MAX_EXPANSION] u8
    tl = jnp.asarray(tab_len)     # [256] i32
    j = jnp.arange(C, dtype=jnp.int32)

    def _row(buf, off, clen, mode):
        # the row's stream, masked to its extent (lane j >= clen reads 0)
        in_row = j < clen
        cb = jnp.where(
            in_row,
            buf[jnp.clip(off + j, 0, buf.shape[0] - 1)],
            jnp.uint8(0),
        )

        # RAW: the stream IS the row (short streams zero-fill)
        raw = cb

        # PACK7: output byte j = bits [7j, 7j+7) of the stream, big-endian
        t0 = 7 * j
        p = t0 >> 3
        o = t0 & 7
        cb16 = cb.astype(jnp.int32)
        nxt = jnp.where(p + 1 < C, cb16[jnp.clip(p + 1, 0, C - 1)], 0)
        word = cb16[jnp.clip(p, 0, C - 1)] * 256 + nxt
        pack7 = ((word >> (16 - 7 - o)) & 0x7F).astype(jnp.uint8)

        # TOKEN: lengths -> exclusive cumsum -> masked scatter rounds
        lens = jnp.where(in_row, tl[cb], 0)
        pos = jnp.cumsum(lens) - lens
        out = jnp.zeros(C + MAX_EXPANSION, dtype=jnp.uint8)
        spill = C + MAX_EXPANSION - 1
        for k in range(MAX_EXPANSION):
            valid = lens > k
            idx = jnp.where(valid, jnp.clip(pos + k, 0, spill), spill)
            out = out.at[idx].set(jnp.where(valid, tb[cb, k], out[idx]))
        token = out[:C]

        return jnp.where(
            mode == MODE_TOKEN,
            token,
            jnp.where(mode == MODE_PACK7, pack7, raw),
        )

    def decompress(buf, offs, clen, mode):
        return jax.vmap(_row, in_axes=(None, 0, 0, 0))(
            buf, offs, clen, mode
        )

    return flight.instrument_jit("ops.decompress", decompress)
