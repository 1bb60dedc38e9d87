"""Shared building blocks of the Pallas decode kernels.

Every kernel of the codec compiles for a TPU and runs in the Pallas
interpreter on other platforms; :func:`use_interpreter` is the single place
that decides which.  The helpers below are written once for both: they use
only constructs the TPU compiler (Mosaic) lowers, and the interpreter gives
them their plain ``jnp`` meaning.

Lane layout.  A block of decoder lanes is an ``(S, 128)`` array: lane ``l``
sits at ``[l // 128, l % 128]``, so per-lane state fills whole vector
registers.  Each lane owns a private row of ``ROW_UNITS`` uint32 units
covering its 128-bit subsequence plus overhang (128 + max_len + 31 < 192
bits -> 6 units), passed unit-major as ``(ROW_UNITS, S, 128)``; bit
positions are local to that row.

What Mosaic does not lower, and what replaces it here:

* 1-D dynamic gathers -> :func:`peek` selects among the six units, and
  :func:`lut_lookup` reads the decode table row by row with a lane gather
  inside one vector register (``take_along_axis`` over 128 lanes).
* scatters -> each iteration of :func:`decode_lanes` writes one row of a
  ``(MAX_SYMS, S, 128)`` symbol buffer; :func:`stage_segments` moves each
  lane's run of symbols into the staging tile with a lane rotation and a
  masked row write, driven by scalars in SMEM.
* ``cumsum`` -> :func:`lane_scan` / :func:`sublane_scan`, log-step
  Hillis-Steele scans built from rotations (exact in int32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ROW_UNITS = 6           # 192 bits >= 128 (body) + 12 (max codeword) + 31 (align)
MAX_SYMS = 128          # worst case: 128 one-bit codewords per subsequence
SUBLANES = 8            # rows of one int32 vector register
CHUNK = SUBLANES * LANES  # entries per DMA chunk of an (rows, 128) side list


def use_interpreter() -> bool:
    """Pallas kernels compile on a TPU and run in the interpreter elsewhere."""
    return jax.default_backend() != "tpu"


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def lane_block(n: int) -> int:
    """Lanes provisioned for ``n`` decoder lanes (whole 128-lane rows)."""
    return round_up(max(n, 1), LANES)


def pack_lut(dec_sym, dec_len) -> jnp.ndarray:
    """Decode LUT as int32 ``(rows, 128)``: symbol | length << 16."""
    size = dec_sym.shape[0]
    packed = (jnp.asarray(dec_sym).astype(jnp.int32)
              | (jnp.asarray(dec_len).astype(jnp.int32) << 16))
    pad = lane_block(size) - size
    if pad:
        packed = jnp.concatenate([packed, jnp.zeros((pad,), jnp.int32)])
    return packed.reshape(-1, LANES)


def lane_rows(units, ids) -> jnp.ndarray:
    """Unit rows of subsequences ``ids``: ``out[k, ...] = units[4*ids + k]``.

    Returns uint32 ``(ROW_UNITS, *ids.shape)``; reads past the stream are
    zero (the encoder's tail padding semantics).
    """
    units = jnp.asarray(units)
    k = jnp.arange(ROW_UNITS, dtype=jnp.int32).reshape(
        (ROW_UNITS,) + (1,) * ids.ndim)
    idx = ids[None] * 4 + k
    n = units.shape[0]
    return jnp.where(idx < n, units[jnp.clip(idx, 0, n - 1)], jnp.uint32(0))


def lut_lookup(lut_ref, idx):
    """``lut[idx]`` for an ``(S, 128)`` index array, one table row at a time.

    A TPU lane gather reads within one vector register, so each table row
    (128 entries, broadcast over the sublanes) answers the lanes whose index
    falls in it.
    """
    shape = idx.shape
    if shape[0] == 1:       # Mosaic's lane gather refuses a one-row operand
        idx = jnp.concatenate([idx, idx])
    row = idx >> 7
    col = idx & (LANES - 1)

    def body(j, acc):
        tab = jnp.broadcast_to(lut_ref[pl.ds(j, 1), :], idx.shape)
        return jnp.where(row == j, jnp.take_along_axis(tab, col, axis=1),
                         acc)

    out = jax.lax.fori_loop(0, lut_ref.shape[0], body,
                            jnp.zeros(idx.shape, jnp.int32))
    return out[:shape[0]]


def peek(rows, pos, max_len: int):
    """Next ``max_len`` bits at row-local bit ``pos`` of every lane.

    ``rows`` is a sequence of ``ROW_UNITS`` uint32 ``(S, 128)`` arrays.
    Returns int32 LUT indices.
    """
    u = pos >> 5
    sh = (pos & 31).astype(jnp.uint32)
    w0, w1 = rows[0], rows[1]
    for k in range(1, ROW_UNITS):
        nxt = rows[k + 1] if k + 1 < ROW_UNITS else jnp.zeros_like(rows[0])
        w0 = jnp.where(u == k, rows[k], w0)
        w1 = jnp.where(u == k, nxt, w1)
    hi = w0 << sh
    lo = jnp.where(sh == 0, jnp.uint32(0), w1 >> (jnp.uint32(32) - sh))
    return ((hi | lo) >> jnp.uint32(32 - max_len)).astype(jnp.int32)


def decode_lanes(rows, start, end, lut_ref, *, max_len: int, lut_size: int,
                 lut_base=None, y_ref=None):
    """Masked decode of every lane's window ``[start, end)``.

    The loop runs while any lane is active -- the TPU analogue of the
    paper's ``__all_sync`` early exit.  ``lut_base`` offsets each lane's
    index into a merged multi-codebook table.  With ``y_ref`` (an int32
    ``(MAX_SYMS, S, 128)`` buffer) iteration ``k`` stores every active
    lane's ``k``-th symbol in ``y_ref[k]``; inactive lanes keep the old
    value, so a zeroed buffer ends as the padded per-lane symbol table.
    Returns ``(landing, counts)``.
    """
    pos0 = jnp.maximum(jnp.minimum(start, end), 0)

    def cond(state):
        _, pos, _ = state
        return jnp.any(pos < end)

    def body(state):
        k, pos, count = state
        active = pos < end
        win = peek(rows, pos, max_len)
        if lut_base is not None:
            win = win + lut_base
        # Clamp: a corrupt stream or merged-table offset must not read
        # outside the table.
        entry = lut_lookup(lut_ref, jnp.clip(win, 0, lut_size - 1))
        length = entry >> 16
        if y_ref is not None:
            slot = jnp.minimum(k, MAX_SYMS - 1)
            y_ref[slot] = jnp.where(active, entry & 0xFFFF, y_ref[slot])
        count = count + active.astype(jnp.int32)
        pos = jnp.where(active, pos + jnp.maximum(length, 1), pos)
        return k + 1, pos, count

    _, pos, count = jax.lax.while_loop(
        cond, body, (jnp.int32(0), pos0, jnp.zeros(pos0.shape, jnp.int32)))
    return pos, count


def transpose_symbols(y_ref, yt_ref):
    """``yt[s, j, k] = y[k, s, j]``: each lane's symbols as one row."""
    for s in range(y_ref.shape[1]):
        yt_ref[s] = y_ref[:, s, :].T


def stage_segments(yt_ref, meta_ref, stage_ref, *, n_lanes: int,
                   n_valid: int, cols: int, tile: int):
    """Write every lane's decoded run into the staging tile.

    ``meta_ref`` (SMEM, ``(1, 1, >= 2 * n_lanes)``) holds each lane's
    tile-local output offset in ``[0, n_lanes)`` and its symbol count in
    ``[n_lanes, 2 * n_lanes)``.  The tile is ``tile`` symbols laid out as
    rows of ``cols``: position ``p = r * cols + c`` lives at
    ``stage_ref[c // 128, r, c % 128]``.  A run is cut into pieces that stay
    inside one such 128-lane row; each piece is a rotation of the lane's
    symbol row and one masked row write.  Positions outside
    ``[0, tile)`` are dropped (neighbouring tiles own them).  Only the
    first ``n_valid`` lanes can carry a run.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def lane_body(l, carry):
        off = meta_ref[0, 0, l]
        n = meta_ref[0, 0, n_lanes + l]
        lo = jnp.maximum(off, 0)
        hi = jnp.minimum(off + n, tile)

        @pl.when(lo < hi)
        def _():
            row = yt_ref[l >> 7, pl.ds(l & (LANES - 1), 1), :]

            def piece(p):
                r = jax.lax.div(p, cols)
                c = p - r * cols
                ch = c >> 7
                c0 = c & (LANES - 1)
                k0 = p - off
                length = jnp.minimum(jnp.minimum(hi - p, cols - c),
                                     LANES - c0)
                rolled = pltpu.roll(row, (c0 - k0 + LANES) & (LANES - 1), 1)
                cur = stage_ref[ch, pl.ds(r, 1), :]
                keep = (lane >= c0) & (lane < c0 + length)
                stage_ref[ch, pl.ds(r, 1), :] = jnp.where(keep, rolled, cur)
                return p + length

            jax.lax.while_loop(lambda p: p < hi, piece, lo)

        return carry

    jax.lax.fori_loop(0, min(n_valid, n_lanes), lane_body, 0)


def place_outliers(pos_hbm, val_hbm, bufs, sem, lo, hi, base, stage_ref, *,
                   cols: int, bias: int):
    """Overwrite outlier positions of the staging tile from the side list.

    Entries ``[lo, hi)`` of the sorted side list (``pos_hbm`` / ``val_hbm``,
    int32 ``(rows, 128)`` in HBM) fall inside this tile, whose first global
    position is ``base``.  They arrive in SMEM ``CHUNK`` entries at a time;
    each is written as ``val + bias`` into its staging cell (same layout as
    :func:`stage_segments`).
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    pos_buf, val_buf = bufs

    def chunk(ci, carry):
        first = (lo >> 10) + ci
        row0 = pl.multiple_of(first * SUBLANES, SUBLANES)
        for src, dst in ((pos_hbm, pos_buf), (val_hbm, val_buf)):
            cp = pltpu.make_async_copy(src.at[pl.ds(row0, SUBLANES), :], dst,
                                       sem)
            cp.start()
            cp.wait()

        def one(i, c):
            j = i - first * CHUNK
            p = pos_buf[j >> 7, j & (LANES - 1)] - base
            v = val_buf[j >> 7, j & (LANES - 1)]
            r = jax.lax.div(p, cols)
            cc = p - r * cols
            cur = stage_ref[cc >> 7, pl.ds(r, 1), :]
            stage_ref[cc >> 7, pl.ds(r, 1), :] = jnp.where(
                lane == (cc & (LANES - 1)), v + bias, cur)
            return c

        jax.lax.fori_loop(jnp.maximum(lo, first * CHUNK),
                          jnp.minimum(hi, (first + 1) * CHUNK), one, 0)
        return carry

    n_chunks = jnp.where(hi > lo, ((hi - 1) >> 10) - (lo >> 10) + 1, 0)
    jax.lax.fori_loop(0, n_chunks, chunk, 0)


def side_list(opos, oval):
    """The outlier side list as two int32 ``(rows, 128)`` arrays whose row
    count covers every aligned ``CHUNK`` a tile can ask for."""
    m = opos.shape[0]
    rows = (m // CHUNK + 1) * SUBLANES
    pad = rows * LANES - m
    pos = jnp.concatenate([jnp.asarray(opos, jnp.int32),
                           jnp.full((pad,), -1, jnp.int32)])
    val = jnp.concatenate([jnp.asarray(oval, jnp.int32),
                           jnp.zeros((pad,), jnp.int32)])
    return pos.reshape(rows, LANES), val.reshape(rows, LANES)


def outlier_ranges(opos, tile_bases) -> jnp.ndarray:
    """Index range ``[r[t], r[t+1])`` of the side list inside each tile.

    ``opos`` is ascending with ``-1`` padding at the end; ``tile_bases``
    holds every tile's first global position plus the end position.
    """
    key = jnp.where(jnp.asarray(opos) >= 0, jnp.asarray(opos, jnp.int32),
                    jnp.iinfo(jnp.int32).max)
    return jnp.searchsorted(key, tile_bases, side="left").astype(jnp.int32)


def _roll_scan(x, axis: int):
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    s = 1
    while s < x.shape[axis]:
        x = x + jnp.where(idx >= s, pltpu.roll(x, s, axis), 0)
        s *= 2
    return x


def lane_scan(x):
    """Inclusive prefix sum along the last (lane) axis."""
    return _roll_scan(x, x.ndim - 1)


def sublane_scan(x):
    """Inclusive prefix sum along the second-to-last (row) axis."""
    return _roll_scan(x, x.ndim - 2)


def flat_scan(x):
    """Inclusive prefix sum of an ``(R, 128)`` block in row-major order."""
    p = lane_scan(x)
    tot = jnp.broadcast_to(p[:, LANES - 1:], p.shape)
    return p + sublane_scan(tot) - tot
