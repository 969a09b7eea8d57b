"""HBM layouts the gather kernels DMA from, and their decoders.

A TPU DMA moves whole tiles of a tiled array, so a kernel that fetches one
database row at a time needs each row to be its own tile: the tables the
kernels read are ``[n, 1, W]`` arrays with ``W`` a multiple of 128 32-bit
words. XLA stores such an array densely (``n·W·4`` bytes), and the per-row
copy ``table.at[idx]`` is then one aligned ``(1, W)`` tile.

* **Vector rows.** f32 tables keep their values, zero-padded to
  ``W = ⌈d/128⌉·128``. int8 tables are bit-cast four values to an int32
  word (little-endian: byte ``k`` of word ``j`` is element ``4j + k``) and
  zero-padded to ``W = ⌈d/512⌉·128`` words; the kernel unpacks the bytes
  with shifts.
* **Packed label rows.** The ``[n, E, 2]`` uint32 rank words of
  ``repro.search.device_graph.pack_labels`` become ``[n, 1, W]`` int32
  with ``W = ⌈2E/128⌉·128``: word 0 of every edge in lanes ``[0, E)``,
  word 1 in lanes ``[E, 2E)``, zeros after (the kernel rotates word 1
  down to lane 0). At E = 96 that is 1,024 B per node, 10.67 B per edge
  (8 B of payload); at E = 48 it is 512 B, again 10.67 B per edge.
  Below E = 32 the row is larger than the 16 B per edge of the int32
  ``[n, E, 4]`` layout, whose rows a TPU pads as well.
  :func:`label_row_bytes` gives it for any E.

Every helper takes numpy or jnp arrays (the host export builds the rows
once; jitted callers convert a logical table in-graph) and passes an
array that is already in row layout through unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LANE = 128


def _xp(a):
    return np if isinstance(a, np.ndarray) else jnp


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def is_rows(table) -> bool:
    """True for a table already in the ``[n, 1, W]`` row layout."""
    return table.ndim == 3


def table_rows(table):
    """``[n, d]`` f32 / int8 table -> ``[n, 1, W]`` f32 / int32-word rows."""
    if is_rows(table):
        return table
    xp = _xp(table)
    n, d = table.shape
    if table.dtype == np.int8:
        dp = _round_up(d, 4 * LANE)
        t = xp.pad(table, ((0, 0), (0, dp - d)))
        if xp is np:
            words = np.ascontiguousarray(t).view("<i4")
        else:
            words = jax.lax.bitcast_convert_type(
                t.reshape(n, dp // 4, 4), jnp.int32)
        return words.reshape(n, 1, dp // 4)
    t = table.astype(np.float32)
    dp = _round_up(d, LANE)
    return xp.pad(t, ((0, 0), (0, dp - d))).reshape(n, 1, dp)


def gather_vectors(table, idx, d: int):
    """Rows ``idx`` of a logical or row-layout table as f32 ``[..., d]``
    (int8 values as stored; dequantizing is the caller's)."""
    if not is_rows(table):
        return table[idx].astype(jnp.float32)
    rows = table[idx][..., 0, :]
    if rows.dtype == jnp.int32:
        b = jax.lax.bitcast_convert_type(rows, jnp.int8)   # [..., W, 4]
        rows = b.reshape(b.shape[:-2] + (b.shape[-2] * 4,))
    return rows[..., :d].astype(jnp.float32)


def query_planes(q, table):
    """Queries laid out to meet the rows of ``table`` in the kernel:
    ``[B, 1, W]`` for f32 rows, ``[B, 4, W]`` byte planes for int8 words
    (plane ``k`` holds ``q[4j + k]`` at lane ``j``)."""
    rows = table_rows(table)
    B, d = q.shape
    W = rows.shape[-1]
    q = q.astype(jnp.float32)
    if rows.dtype == jnp.int32:
        qp = jnp.pad(q, ((0, 0), (0, 4 * W - d))).reshape(B, W, 4)
        return jnp.transpose(qp, (0, 2, 1))
    return jnp.pad(q, ((0, 0), (0, W - d))).reshape(B, 1, W)


def is_int32_rects(labels) -> bool:
    """True for the unpacked int32 ``[..., E, 4]`` rank rectangles, the
    parity-oracle layout; False for the packed words ``[..., E, 2]`` and
    their label rows. The one test every caller dispatches on."""
    return labels.shape[-1] == 4


def is_label_rows(labels) -> bool:
    """True for packed labels already in the ``[n, 1, W]`` row layout
    (the word layout ``[n, E, 2]`` and the int32 ``[n, E, 4]`` rectangles
    have a trailing 2 or 4)."""
    return labels.shape[-1] % LANE == 0


def label_rows(plabels):
    """``[n, E, 2]`` uint32 packed words -> ``[n, 1, ⌈2E/128⌉·128]`` int32
    rows, word 0 of the edges first, then word 1."""
    if is_label_rows(plabels):
        return plabels
    xp = _xp(plabels)
    n, E, _ = plabels.shape
    if xp is np:
        w = np.ascontiguousarray(plabels, dtype=np.uint32).view(np.int32)
    else:
        w = jax.lax.bitcast_convert_type(plabels, jnp.int32)
    w = xp.transpose(w, (0, 2, 1)).reshape(n, 2 * E)    # [w0 | w1]
    W = _round_up(2 * E, LANE)
    return xp.pad(w, ((0, 0), (0, W - 2 * E))).reshape(n, 1, W)


def label_row_bytes(E: int) -> int:
    """Bytes of one node's packed label row for degree ``E`` (what the
    kernel DMAs per expanded node, and what each node holds in HBM)."""
    return _round_up(2 * E, LANE) * 4


def label_words(plabels, idx, E: int):
    """Packed words of nodes ``idx`` as uint32 ``[..., E, 2]`` from either
    the word layout or the row layout."""
    rows = plabels[idx]
    if not is_label_rows(plabels):
        return rows
    r = rows[..., 0, :]
    w = jnp.stack([r[..., :E], r[..., E:2 * E]], axis=-1)
    return jax.lax.bitcast_convert_type(w, jnp.uint32)
