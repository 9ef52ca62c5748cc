"""The superstep's gather: a per-vertex table read once an edge row.

On the TPU a gather costs per ROW fetched, and a row of one element is the
dear form of it — the flat scalar gather, 7.13 ns a row where a row gather
out of a table in fast memory costs 1.8 (docs/KERNELS.md). So a table is
viewed with ``P`` vertices a row: a pair reads row ``id // P`` and keeps
slot ``id % P``. The same elements, selected.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128        # a [rows, <= 128] 32-bit buffer is lane-padded to 128
ROW_BYTES = 512

# A gather table this small the compiler keeps in the chip's fast memory,
# where a row gather costs 1.8 ns a row; out of it 9.9 (docs/KERNELS.md).
TABLE_BYTES = 64 << 20

# A one-column table is never gathered element by element: its row holds
# a full row of lanes, so the table is dense (docs/KERNELS.md holds the
# chip's readings for 2, 8, 32 and 128 vertices a row).
ONE_COLUMN_PACK = LANES

# The picked rows of a one-column table are an [ids, P] temporary, a row
# of lanes a gathered id where the flat gather's is one element (512 B of
# a float, 1024 of an int64): past this many bytes of them — an eighth of
# a v5e's 16 GB — a table is read a tile of ids at a time, one tile's
# rows live at once (docs/KERNELS.md).
PACKED_ROWS_BYTES = 2 << 30


def gather_pack(n: int, C: int) -> int:
    """Vertices a row of the gather table ``[n, C]``.

    ``C >= 2``: the smallest power of two that brings the lane-padded
    table under ``TABLE_BYTES``, no more than fit a row's 128 lanes; 1 is
    the plain table. ``C == 1``, where the plain table is the flat gather:
    ``ONE_COLUMN_PACK``, halved until it divides ``n``."""
    if C == 1:
        P = ONE_COLUMN_PACK
        while P > 1 and n % P:
            P //= 2
        return P
    P = 1
    while ((n // P) * ROW_BYTES > TABLE_BYTES
           and 2 * P * C <= LANES and n % (2 * P) == 0):
        P *= 2
    return P


def row_and_slot(ids: jnp.ndarray, P: int):
    """Where id ``i`` lies in the ``[n // P, P * C]`` view: row ``i // P``,
    slot ``i % P``. Functions of the ids alone — a caller inside a loop
    forms them outside it."""
    return ids // P, ids % P


def rows_tile(ids: int, itemsize: int) -> int:
    """Ids a tile of ``packed_elements``: all of them while their picked
    rows (lane-padded, ``LANES`` elements of ``itemsize`` bytes an id) stay
    within ``PACKED_ROWS_BYTES``, else the most whose rows do."""
    return min(ids, PACKED_ROWS_BYTES // (LANES * itemsize))


def _pick(view: jnp.ndarray, row: jnp.ndarray,
          slot: jnp.ndarray) -> jnp.ndarray:
    """One row of ``view [n // P, P]`` an id and the slot's element of it:
    one pass along each row's lanes (summed as bit patterns, so the element
    comes back as it is, whatever it holds)."""
    rows = view[row, :]
    hit = jnp.arange(view.shape[1], dtype=slot.dtype)[None, :] == slot[:, None]
    dt = rows.dtype
    if dt == jnp.bool_:
        return jnp.any(hit & rows, axis=1)
    bits = jax.lax.bitcast_convert_type(
        rows, jnp.dtype(f"uint{8 * dt.itemsize}"))
    picked = jnp.sum(jnp.where(hit, bits, 0), axis=1, dtype=bits.dtype)
    return jax.lax.bitcast_convert_type(picked, dt)


def packed_elements(table: jnp.ndarray, row: jnp.ndarray, slot: jnp.ndarray,
                    P: int) -> jnp.ndarray:
    """``table[ids]`` for a flat ``table [n]`` and ``row, slot =
    row_and_slot(ids, P)``: a row gather out of the ``[n // P, P]`` view
    and the lane pick, ``rows_tile`` ids at a time (equal tiles one after
    the other and one remainder slice), so that the picked rows never
    outgrow ``PACKED_ROWS_BYTES`` however many ids a dispatch reads."""
    view = table.reshape(-1, P)
    ids = row.shape[0]
    tile = rows_tile(ids, table.dtype.itemsize)
    if tile == ids:
        return _pick(view, row, slot)
    whole = ids // tile * tile
    out = jax.lax.map(lambda at: _pick(view, *at),
                      (row[:whole].reshape(-1, tile),
                       slot[:whole].reshape(-1, tile))).reshape(whole)
    if whole == ids:
        return out
    return jnp.concatenate([out, _pick(view, row[whole:], slot[whole:])])


def packed_rows(table: jnp.ndarray, row: jnp.ndarray, slot: jnp.ndarray,
                P: int) -> jnp.ndarray:
    """``table[ids]`` for ``table [n, C]`` and ``row, slot =
    row_and_slot(ids, P)``: a row gather out of the ``[n // P, P * C]``
    view, then each pair's slot of the row it read."""
    n, C = table.shape
    if C == 1 and P > 1:
        return packed_elements(table.reshape(n), row, slot, P)[:, None]
    rows = table.reshape(n // P, P * C)[row, :]
    g = rows[:, :C]
    for s in range(1, P):
        g = jnp.where((slot == s)[:, None], rows[:, s * C:(s + 1) * C], g)
    return g
