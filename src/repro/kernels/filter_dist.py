"""Pallas TPU kernels: fused edge-label validity test + squared distance.

Three variants share the label-test semantics (paper Alg. 2 lines 8-9,
turned from a per-edge branch into a predication mask so invalid
neighbors come back +inf and are annihilated by the subsequent top-k):

``filter_dist_pallas`` — the original *pre-gathered* form. The caller hands
the kernel a dense ``[B, E, D]`` candidate tensor that XLA gathered into HBM
beforehand. Kept as the simple baseline (the unfused search loop and the
unfused delta scan).

``filter_dist_gather_pallas`` — the *gather-fused* path. The kernel
receives the full vector table (``memory_space=ANY``, never blocked; in
HBM, or pinned in VMEM up to :data:`VMEM_TABLE_BYTES`) in the row layout
of :mod:`repro.kernels.layout` plus
scalar-prefetched candidate row ids (``PrefetchScalarGridSpec``), and DMAs
the rows of a tile into a double-buffered VMEM scratch — tile ``r+1``'s
row fetches are issued before tile ``r``'s compute, so the gather overlaps
the MXU matvec. A row is fetched only if its candidate can still pass:
the wrappers hand the kernel a DMA row id of ``-1`` for padding, idle
rows, visited candidates and (where the labels are known before the call)
candidates failing the label test (:func:`fetch_rows`), and the kernel
skips those DMAs; their lanes are masked to ``+inf`` as before, so every
result is what fetching them would give. A tile with no row to fetch is
dead (a scalar-prefetched ``-1`` per tile): no DMA, no compute, ``+inf``
out. The dense ``[B, E, D]``
intermediate never exists. Squared distance uses cached per-row norms
(``‖c‖² − 2·q·c + ‖q‖²``), the visited test shifts the candidate's word of
the bit-packed ``[B, ceil(n/32)]`` bitmap, and int8 rows arrive as int32
words whose bytes the kernel unpacks with shifts (dequantized by the
per-candidate scale after the MXU).

``filter_dist_gather_packed_pallas`` — the *packed-metadata superkernel*
(the serving hot path). One grid step per (query, expanded node): the tile
is that node's ``E`` neighbors, and the node's packed label row (two
16-bit ranks per word, ``[n, 1, ⌈2E/128⌉·128]`` — see
``repro.kernels.layout``) is DMA'd
alongside the vector rows, driven by a second scalar-prefetch operand
carrying the expanded-node ids; a ``-1`` there is a dead tile (no live
expanded node, or no candidate left to fetch), which issues no DMA, skips
the compute and comes back ``+inf``. The dominance test unpacks the ranks
with a mask-and-shift and compares in-register; no ``[B, M·E, 4]`` label gather
exists in the surrounding program (asserted structurally by
``benchmarks/bench_batched.py``).

Layout rules the TPU lowering imposes, and how the kernels meet them:
every per-candidate operand is a ``[R, 1, te]`` array blocked ``(1, 1,
te)`` (candidates on lanes, one tile per grid step, ``R = B·tiles``); the
per-query rank states ride in SMEM as a scalar-prefetch operand; tables
are DMA'd one ``(1, W)`` tile per row. VMEM at d = 768 f32, TE = 128: two
``(TE, 1, 768)`` row buffers (≤ 6 MiB with sublane padding) plus a few KiB
of metadata tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import label_rows, query_planes, table_rows

TE = 128  # candidate-tile width
# Tables up to this size are pinned in VMEM (a quarter of a TPU v5e's
# 128 MiB): the 32 MiB d = 128 shard's per-row DMAs run 1.5x faster per
# call from there than from HBM, and left to XLA the placement moves with
# unrelated changes to the search loop (it once gave VMEM to the label rows).
VMEM_TABLE_BYTES = 32 << 20
_NT = (((1,), (1,)), ((), ()))   # dot_general: contract both last dims


def _dot(a, b):
    """``a @ b.T`` in full f32 precision. The MXU's default f32 matmul on
    TPU rounds its inputs to bf16, which reorders near-tied neighbours
    against the exact f32 ground truth and makes the brute-valid scan
    inexact."""
    return jax.lax.dot_general(a, b, _NT, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _shr(x, s):
    return jax.lax.shift_right_logical(x, jnp.asarray(s, x.dtype))


def _cross(q_ref, rows):
    """``[1, te]`` inner products of the query with ``rows [te, W]`` (f32
    values, or int32 words of four int8 values each, one MXU pass per byte
    plane against the matching plane of ``q_ref``)."""
    if rows.dtype != jnp.int32:
        return _dot(q_ref[0], rows)
    cross = None
    for k in range(4):
        plane = jax.lax.shift_right_arithmetic(
            jax.lax.shift_left(rows, jnp.int32(24 - 8 * k)), jnp.int32(24)
        ).astype(jnp.float32)
        part = _dot(q_ref[0, k:k + 1, :], plane)
        cross = part if cross is None else cross + part
    return cross


def _rank_ok(lx, hx, ly, hy, a, c):
    return (lx <= a) & (a <= hx) & (ly <= c) & (c <= hy)


def _filter_dist_kernel(st_ref, q_ref, cand_ref, lab_ref, ids_ref, out_ref,
                        *, tiles):
    b = pl.program_id(0) // tiles
    q = q_ref[0]                                      # [1, Dp]
    cand = cand_ref[0].astype(jnp.float32)            # [te, Dp]
    lab = lab_ref[0]                                  # [4, te]
    ids = ids_ref[0]                                  # [1, te]
    cross = _dot(q, cand)
    cs = _dot(jnp.ones_like(q), cand * cand)
    dist = cs - 2.0 * cross + jnp.sum(q * q)
    ok = _rank_ok(lab[0:1], lab[1:2], lab[2:3], lab[3:4],
                  st_ref[b, 0], st_ref[b, 1]) & (ids >= 0)
    out_ref[0] = jnp.where(ok, dist, jnp.inf)


def _pad_tiles(c: int, te: int):
    te = min(te, c)
    tiles = -(-c // te)
    return te, tiles, tiles * te - c


@functools.partial(jax.jit, static_argnames=("interpret", "te"))
def filter_dist_pallas(
    q: jnp.ndarray,          # [B, D]
    cand: jnp.ndarray,       # [B, E, D]
    labels: jnp.ndarray,     # [B, E, 4] int32
    state: jnp.ndarray,      # [B, 2] int32
    cand_ids: jnp.ndarray,   # [B, E] int32, -1 padding
    *,
    interpret: bool = False,
    te: int = TE,
) -> jnp.ndarray:
    b, e, d = cand.shape
    te, tiles, pe = _pad_tiles(e, te)
    dp = -(-d // 128) * 128
    cand = jnp.pad(cand.astype(jnp.float32), ((0, 0), (0, pe), (0, dp - d)))
    labels = jnp.pad(labels, ((0, 0), (0, pe), (0, 0)))
    cand_ids = jnp.pad(cand_ids, ((0, 0), (0, pe)), constant_values=-1)
    R = b * tiles
    qp = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, dp - d)))
    lab = labels.reshape(b, tiles, te, 4).transpose(0, 1, 3, 2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((1, 1, dp), lambda r, st: (r // tiles, 0, 0)),
            pl.BlockSpec((1, te, dp), lambda r, st: (r, 0, 0)),
            pl.BlockSpec((1, 4, te), lambda r, st: (r, 0, 0)),
            pl.BlockSpec((1, 1, te), lambda r, st: (r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, te), lambda r, st: (r, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_filter_dist_kernel, tiles=tiles),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, 1, te), jnp.float32),
        interpret=interpret,
    )(state.astype(jnp.int32), qp.reshape(b, 1, dp),
      cand.reshape(R, te, dp), lab.reshape(R, 4, te),
      cand_ids.reshape(R, 1, te))
    return out.reshape(b, tiles * te)[:, :e]


def _gather_kernel(*refs, te: int, tiles: int, packed: bool):
    """One grid step = one tile of ``te`` candidates of query
    ``r // tiles``. Double-buffered per-row HBM→VMEM fetch: warm tile 0
    up, issue tile ``r+1``'s fetches before tile ``r``'s compute, await
    tile ``r``. The packed kernel also fetches the tile's label row.

    A row is fetched only where its DMA row id is ``>= 0``; the wrappers
    give ``-1`` to every candidate the ``ok`` mask below throws away
    (:func:`fetch_rows`), so a skipped row's stale VMEM only reaches a lane
    that is forced to ``+inf`` (each lane of the MXU product reads its own
    row alone). A tile whose scalar-prefetched ``tile_ref`` entry is ``-1``
    (no row to fetch; for the packed kernel that entry is the expanded
    node) is dead: no label or row DMA, no compute, ``+inf`` out. Starts
    and waits test the same ids, so every semaphore waited on was
    started."""
    if packed:
        (st_ref, tile_ref, table_ref, plab_ref, sids_ref, nsids_ref, q_ref,
         ids_ref, norm_ref, word_ref, scale_ref, out_ref,
         vec_scr, lab_scr, sem, lab_sem) = refs
    else:
        (st_ref, tile_ref, table_ref, sids_ref, nsids_ref, q_ref, lab_ref,
         ids_ref, norm_ref, word_ref, scale_ref, out_ref, vec_scr,
         sem) = refs
    r = pl.program_id(0)
    slot = jax.lax.rem(r, 2)

    def rows(p, s, op):
        """``op(copy)`` on the DMA of each fetched row of tile ``p``."""
        def go(i, carry):
            # the row ids of tile r and of tile r+1 arrive as SMEM blocks
            src = jnp.where(p == r, sids_ref[0, 0, i], nsids_ref[0, 0, i])

            @pl.when(src >= 0)
            def _():
                op(pltpu.make_async_copy(table_ref.at[jnp.maximum(src, 0)],
                                         vec_scr.at[s, i], sem.at[s, i]))
            return carry
        jax.lax.fori_loop(0, te, go, 0)

    def label_copy(p, s):
        return pltpu.make_async_copy(
            plab_ref.at[tile_ref[p]], lab_scr.at[s], lab_sem.at[s])

    def start(p, s):
        @pl.when(tile_ref[p] >= 0)
        def _():
            rows(p, s, lambda copy: copy.start())
            if packed:
                label_copy(p, s).start()

    @pl.when(r == 0)
    def _warmup():          # the first tile has no predecessor to prefetch it
        start(0, 0)

    @pl.when(r + 1 < pl.num_programs(0))
    def _prefetch():        # issue tile r+1's fetches before tile r's compute
        start(r + 1, 1 - slot)

    @pl.when(tile_ref[r] >= 0)
    def _compute():
        rows(r, slot, lambda copy: copy.wait())
        b = r // tiles
        a, c = st_ref[2 * b], st_ref[2 * b + 1]
        if packed:
            label_copy(r, slot).wait()
            lab = lab_scr[slot]                       # [1, W] int32
            # word 1 sits at lanes [te, 2·te): rotate it down to lane 0
            w1 = pltpu.roll(lab, lab.shape[-1] - te, 1)[:, :te]
            w0 = lab[:, :te]
            label_ok = _rank_ok(w0 & 0xFFFF, _shr(w0, 16),
                                w1 & 0xFFFF, _shr(w1, 16), a, c)
        else:
            lab = lab_ref[0]                          # [4, te] int32
            label_ok = _rank_ok(lab[0:1], lab[1:2], lab[2:3], lab[3:4], a, c)
        vecs = vec_scr[slot]                          # [te, 1, W]
        vecs = vecs.reshape(vecs.shape[0], vecs.shape[2])
        q = q_ref[0]                                  # [P, W]
        cross = _cross(q_ref, vecs) * scale_ref[0]    # dequant after the MXU
        dist = norm_ref[0] - 2.0 * cross + jnp.sum(q * q)
        ids = ids_ref[0]                              # [1, te]
        seen = (_shr(word_ref[0], jnp.maximum(ids, 0) & 31) & 1) == 1
        ok = label_ok & (ids >= 0) & ~seen
        out_ref[0] = jnp.where(ok, dist, jnp.inf)

    @pl.when(tile_ref[r] < 0)
    def _dead():
        out_ref[0] = jnp.full(out_ref.shape[1:], jnp.inf, jnp.float32)


def _gather_call(kernel_refs_prefix, *, rows, qp, tiled, R, te, tiles,
                 packed, extra_in_specs, extra_scratch, interpret):
    """Shared ``pallas_call`` plumbing of the two gather kernels."""
    n, _, W = rows.shape
    P = qp.shape[1]
    nsp = len(kernel_refs_prefix)
    tile = lambda r, *_: (r, 0, 0)                    # noqa: E731
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]     # table (HBM or VMEM)
    in_specs += extra_in_specs
    # DMA source rows of this tile and of the next one (SMEM, per step)
    in_specs += [
        pl.BlockSpec((1, 1, te), tile, memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, te),
                     lambda r, *_: (jnp.minimum(r + 1, R - 1), 0, 0),
                     memory_space=pltpu.SMEM),
    ]
    in_specs += [pl.BlockSpec((1, P, W), lambda r, *_: (r // tiles, 0, 0))]
    if not packed:
        in_specs += [pl.BlockSpec((1, 4, te), tile)]              # labels
    in_specs += [pl.BlockSpec((1, 1, te), tile)] * 4   # ids, norms, words, scales
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=nsp,
        grid=(R,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, te), tile),
        scratch_shapes=[pltpu.VMEM((2, te, 1, W), rows.dtype)]
        + extra_scratch + [pltpu.SemaphoreType.DMA((2, te))]
        + ([pltpu.SemaphoreType.DMA((2,))] if packed else []),
    )
    table, *rest = tiled
    if not interpret and n * W * rows.dtype.itemsize <= VMEM_TABLE_BYTES:
        table = pltpu.with_memory_space_constraint(
            table, memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_gather_kernel, te=te, tiles=tiles, packed=packed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, 1, te), jnp.float32),
        interpret=interpret,
    )(*kernel_refs_prefix, table, *rest)


def _tile_meta(x, R, te):
    return x.reshape(R, 1, te)


def fetch_rows(cand_ids, words, n, passes=None):
    """The table row each candidate's DMA reads, ``-1`` where the kernel
    would throw the row away: padding and idle rows (``cand_ids < 0``),
    candidates whose bit is set in their gathered visited ``words``, and
    those failing ``passes`` (a label test known before the call)."""
    words = jax.lax.bitcast_convert_type(words, jnp.uint32)
    bit = (jnp.maximum(cand_ids, 0) & 31).astype(jnp.uint32)
    ok = (cand_ids >= 0) & ((_shr(words, bit) & 1) == 0)
    if passes is not None:
        ok &= passes
    return jnp.where(ok, jnp.minimum(cand_ids, n - 1), -1)


def label_passes(labels, state):
    """``[B, C]`` dominance test of int32 rectangles ``[B, C, 4]``."""
    return _rank_ok(labels[..., 0], labels[..., 1], labels[..., 2],
                    labels[..., 3], state[:, 0:1], state[:, 1:2])


def packed_fetch_rows(cur_ids, cand_ids, words, n):
    """``(cur, fetch)`` of the packed kernel: :func:`fetch_rows` with the
    candidates of a ``-1`` expanded node skipped too, and the expanded
    nodes with ``-1`` for a dead tile, one that fetches no row (its every
    lane comes back ``+inf``, so its label row is not needed either)."""
    B, M = cur_ids.shape
    fetch = fetch_rows(cand_ids, words, n).reshape(B, M, -1)
    fetch = jnp.where(cur_ids[..., None] >= 0, fetch, -1)
    cur = jnp.where(jnp.any(fetch >= 0, axis=-1),
                    jnp.minimum(cur_ids, n - 1), -1)
    return cur, fetch.reshape(B, -1)


@functools.partial(jax.jit, static_argnames=("interpret", "te"))
def filter_dist_gather_pallas(
    table: jnp.ndarray,      # [n, D] f32/int8, or its [n, 1, W] rows
    q: jnp.ndarray,          # [B, D]
    cand_ids: jnp.ndarray,   # [B, C] int32, -1 = padding/inactive
    labels: jnp.ndarray,     # [B, C, 4] int32
    state: jnp.ndarray,      # [B, 2] int32
    norms: jnp.ndarray,      # [B, C] f32 gathered ‖c‖² (dequantized scale)
    words: jnp.ndarray,      # [B, C] uint32 gathered visited bitmap words
    scales: jnp.ndarray,     # [B, C] f32 gathered dequant scales
    *,
    interpret: bool = False,
    te: int = TE,
) -> jnp.ndarray:
    b, c = cand_ids.shape
    rows = table_rows(table)
    n = rows.shape[0]
    te, tiles, pc = _pad_tiles(c, te)
    if pc:
        cand_ids = jnp.pad(cand_ids, ((0, 0), (0, pc)), constant_values=-1)
        labels = jnp.pad(labels, ((0, 0), (0, pc), (0, 0)))
        norms = jnp.pad(norms, ((0, 0), (0, pc)))
        words = jnp.pad(words, ((0, 0), (0, pc)))
        scales = jnp.pad(scales, ((0, 0), (0, pc)), constant_values=1.0)
    R = b * tiles
    fetch = fetch_rows(cand_ids, words, n, label_passes(labels, state))
    fetch = fetch.reshape(R, 1, te)                   # DMA rows, -1 = skip
    live = jnp.where(jnp.any(fetch >= 0, axis=(1, 2)), 0, -1)  # -1 = dead
    lab = labels.reshape(b, tiles, te, 4).transpose(0, 1, 3, 2)
    qp = query_planes(q, rows)
    out = _gather_call(
        (state.astype(jnp.int32).reshape(-1), live.astype(jnp.int32)),
        rows=rows, qp=qp, R=R, te=te, tiles=tiles,
        packed=False, extra_in_specs=[], extra_scratch=[],
        interpret=interpret,
        tiled=(rows, fetch, fetch, qp, lab.reshape(R, 4, te),
               _tile_meta(cand_ids, R, te),
               _tile_meta(norms.astype(jnp.float32), R, te),
               _tile_meta(jax.lax.bitcast_convert_type(words, jnp.int32),
                          R, te),
               _tile_meta(scales.astype(jnp.float32), R, te)),
    )
    return out.reshape(b, tiles * te)[:, :c]


@functools.partial(jax.jit, static_argnames=("interpret",))
def filter_dist_gather_packed_pallas(
    table: jnp.ndarray,      # [n, D] f32/int8, or its [n, 1, W] rows
    plabels: jnp.ndarray,    # [n, E, 2] uint32 packed words, or their rows
    q: jnp.ndarray,          # [B, D]
    cur_ids: jnp.ndarray,    # [B, M] int32 expanded beam nodes
    cand_ids: jnp.ndarray,   # [B, M*E] int32, -1 = padding/inactive
    state: jnp.ndarray,      # [B, 2] int32
    norms: jnp.ndarray,      # [B, M*E] f32 gathered ‖c‖²
    words: jnp.ndarray,      # [B, M*E] uint32 gathered visited bitmap words
    scales: jnp.ndarray,     # [B, M*E] f32 gathered dequant scales
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Packed-metadata superkernel: one tile per (query, expanded node),
    the node's ``E`` neighbor rows and its packed label row DMA'd from the
    HBM tables — the label metadata never exists as an XLA-side gathered
    intermediate."""
    b, c = cand_ids.shape
    M = cur_ids.shape[1]
    rows = table_rows(table)
    lrows = label_rows(plabels)
    n = rows.shape[0]
    E = c // M
    if M * E != c or 2 * E > lrows.shape[-1]:
        raise ValueError(
            f"cand_ids width {c} is not M*E for M={M} and the label rows "
            f"of width {lrows.shape[-1]}")
    R = b * M
    cur, fetch = packed_fetch_rows(cur_ids, cand_ids, words, n)
    fetch = fetch.reshape(R, 1, E)                    # DMA rows, -1 = skip
    qp = query_planes(q, rows)
    out = _gather_call(
        (state.astype(jnp.int32).reshape(-1), cur.reshape(R)),
        rows=rows, qp=qp, R=R, te=E, tiles=M, packed=True,
        extra_in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # label rows
        extra_scratch=[pltpu.VMEM((2, 1, lrows.shape[-1]), jnp.int32)],
        interpret=interpret,
        tiled=(rows, lrows, fetch, fetch, qp,
               _tile_meta(cand_ids, R, E),
               _tile_meta(norms.astype(jnp.float32), R, E),
               _tile_meta(jax.lax.bitcast_convert_type(words, jnp.int32),
                          R, E),
               _tile_meta(scales.astype(jnp.float32), R, E)),
    )
    return out.reshape(b, c)
