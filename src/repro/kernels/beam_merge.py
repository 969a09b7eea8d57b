"""Top-L beam merge primitive: dedup + best-L selection over (beam ∪ cands).

Every iteration of the lockstep beam search ends by folding the kernel's
``M·E`` scored candidates into the sorted length-``L`` beam. The original
loop did that with an ``argsort`` over candidate ids (duplicate
suppression) followed by a full stable three-array ``lax.sort`` over
``[B, L + M·E]`` — the two most expensive ops of the whole iteration
(together >70% of measured per-iteration wall-clock on the CPU oracle
path, and O((L+ME)·log²) comparator work on any backend).

This module replaces both with one primitive, ``beam_merge``:

  1. **dedup** — an ``[ME, ME]`` predicated compare ("an earlier finite
     candidate carries the same id") instead of a sort: order-independent,
     branch-free, exactly the keep-first-occurrence rule of the old path;
  2. **selection** — the beam is already sorted, so the merge needs a
     *top-L with stable ties*, not a full sort:

     * jnp path (``beam_merge_jnp``): ``lax.top_k`` over the concatenated
       distances — XLA's TopK breaks ties toward the lower index, which is
       exactly the stable-sort order of the ``[beam, candidates]`` concat;
     * Pallas path (``beam_merge_pallas``): bitonic-sort the candidates by
       ``(distance-key, index)`` then a single bitonic *merge network* with
       the already-sorted beam — ``O(ME·log²(ME) + (L+ME)·log(L+ME))``
       compare-exchange stages, all vectorized, no data-dependent control
       flow. Distances are compared via an order-isomorphic int32 key
       (sign-fixed float bits) with the concat index as tie-break, so the
       network's output is the unique total order that the stable sort
       produces. The kernel puts 128 queries on the lanes and sequence
       positions on the sublanes, so every stage is whole-vreg rolls and
       selects (no per-query reshapes, reversals or zero-size arrays).

``ref.beam_merge_ref`` keeps the stable-``lax.sort`` formulation as the
semantic oracle; ``tests/test_kernels.py`` pins both implementations to it
bitwise (ties, all-inf candidate sets, L and M·E off powers of two).

Tie semantics vs the legacy loop: the legacy path sorted candidates by id
*before* the merge, so exact distance ties between *different* ids resolved
in id order; here they resolve in candidate-arrival order. Both orders are
valid stable merges; results differ only when two distinct rows are at
exactly equal squared distance (same-id duplicates always carry bit-equal
distances and are deduped identically). The legacy path remains available
as the non-packed parity oracle in ``search/batched.py``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INF = jnp.inf
_I32_MAX = np.int32(np.iinfo(np.int32).max)


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (>= 1)."""
    p = 1
    while p < x:
        p *= 2
    return p


def dedup_mask(cand_d: jnp.ndarray, cand_ids: jnp.ndarray, n: int) -> jnp.ndarray:
    """[B, C] bool: True where an *earlier* finite candidate in the batch
    row carries the same id (keep-first-occurrence duplicate suppression).

    Finite distance implies a valid id (the kernels emit +inf for padding /
    label-invalid / visited candidates), so an id match between two finite
    entries is a true duplicate. O(C²) predicated compares — no sort, no
    data movement; C = M·E is a small static width.
    """
    C = cand_d.shape[1]
    fin = jnp.isfinite(cand_d)
    id_key = jnp.where(fin, cand_ids, jnp.int32(n))
    # broadcasted_iota (an op, not an array constant) keeps this helper
    # usable inside Pallas kernel bodies, which may not close over consts
    earlier = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
               < jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))  # j before i
    same = id_key[:, :, None] == id_key[:, None, :]  # [B, j, i]
    return jnp.any(same & earlier[None], axis=1) & fin


def beam_merge_jnp(
    beam_d: jnp.ndarray,     # [B, L] f32 ascending (beam invariant)
    beam_ids: jnp.ndarray,   # [B, L] int32 (-1 padding)
    beam_exp: jnp.ndarray,   # [B, L] bool expanded flags
    cand_d: jnp.ndarray,     # [B, C] f32 (+inf = dead candidate)
    cand_ids: jnp.ndarray,   # [B, C] int32
    *,
    n: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pure-jnp fast path: matrix dedup + ``lax.top_k`` stable selection.

    Returns ``(new_ids, new_d, new_exp)`` — the best L of beam ∪ deduped
    candidates, ascending with ties by concat position (beam first, then
    candidates in arrival order) — plus ``keep [B, C]``: the deduped
    survivor mask used for the visited-bitmap update.
    """
    L = beam_d.shape[1]
    dup = dedup_mask(cand_d, cand_ids, n)
    d_dd = jnp.where(dup, _INF, cand_d)
    keep = jnp.isfinite(d_dd)
    all_d = jnp.concatenate([beam_d, d_dd], axis=1)
    all_ids = jnp.concatenate([beam_ids, cand_ids], axis=1)
    all_exp = jnp.concatenate([beam_exp, ~keep], axis=1)
    # top_k of the negated distances = ascending-by-distance selection;
    # XLA TopK resolves exact ties toward the lower index — the stable
    # order of the concat (pinned vs the lax.sort oracle in tests).
    _, sel = jax.lax.top_k(-all_d, L)
    new_d = jnp.take_along_axis(all_d, sel, 1)
    new_ids = jnp.take_along_axis(all_ids, sel, 1)
    new_exp = jnp.take_along_axis(all_exp, sel, 1)
    return new_ids, new_d, new_exp, keep


# --- Pallas bitonic kernel ------------------------------------------------------
#
# Lanes are queries: every sequence is a ``[P, 128]`` array whose rows are
# sequence positions and whose lanes are 128 queries of the batch, so each
# compare-exchange stage is a few whole-vreg rolls and selects along the
# sublane axis, the same for every query.


def _sort_key(d: jnp.ndarray) -> jnp.ndarray:
    """Order-isomorphic int32 key for f32 (no NaN): a < b iff key(a) <
    key(b). ``-0.0`` is normalized to ``+0.0`` first so exact float
    equality and key equality coincide."""
    bits = jax.lax.bitcast_convert_type(d + 0.0, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _ce_stage(arrs, j: int, asc):
    """One compare-exchange stage at stride ``j`` along axis 0.

    ``arrs = (key, ix, *values)``, all ``[P, lanes]``: int32 primary key,
    int32 tie-break (unique per lane, so the order is total) and carried
    values. Position ``i`` pairs with ``i ^ j``; the pair sorts ascending
    where ``asc`` (bool, broadcastable) is true, else descending. Each
    position fetches its partner with two sublane rolls and keeps either
    itself or the partner — both ends of a pair make complementary
    choices, so the stage is a permutation.
    """
    key, ix = arrs[0], arrs[1]
    P = key.shape[0]
    lo = (jax.lax.broadcasted_iota(jnp.int32, key.shape, 0) & j) == 0

    def partner(x):
        # roll by P - j brings x[i + j] to i; roll by j brings x[i - j]
        return jnp.where(lo, pltpu.roll(x, P - j, 0), pltpu.roll(x, j, 0))

    pk, pi = partner(key), partner(ix)
    p_less = (pk < key) | ((pk == key) & (pi < ix))
    take = lo ^ p_less ^ asc      # lo takes the smaller when ascending
    return (jnp.where(take, pk, key), jnp.where(take, pi, ix)) + tuple(
        jnp.where(take, partner(x), x) for x in arrs[2:])


def _bitonic_sort_desc(arrs):
    """Descending bitonic sort of ``arrs = (key, ix, *values)`` along
    axis 0 by (key, ix)."""
    P = arrs[0].shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, arrs[0].shape, 0)
    k = 2
    while k <= P:
        asc = (i & k) != 0 if k < P else False
        j = k // 2
        while j >= 1:
            arrs = _ce_stage(arrs, j, asc)
            j //= 2
        k *= 2
    return arrs


def _bitonic_merge(arrs):
    """Merge one bitonic sequence (ascending, then descending) of
    power-of-two length into ascending order along axis 0."""
    j = arrs[0].shape[0] // 2
    while j >= 1:
        arrs = _ce_stage(arrs, j, True)
        j //= 2
    return arrs


def _beam_merge_kernel(
    bd_ref, bi_ref, be_ref, cd_ref, ci_ref,
    oi_ref, od_ref, oe_ref, ok_ref,
    key_scr,
    *, n: int, L: int, C: int,
):
    """128 queries per grid step: dedup, candidate bitonic sort
    (descending), one merge network with the ascending beam, emit the best
    L. Inputs arrive padded: the beam to ``Pb`` rows, candidates to ``Pc``
    rows (``Pb + Pc`` a power of two, ``Pb >= L``)."""
    cd = cd_ref[...]                               # [Pc, lanes] f32
    ci = ci_ref[...]                               # [Pc, lanes] int32
    rc = jax.lax.broadcasted_iota(jnp.int32, cd.shape, 0)
    # keep-first duplicate suppression: the rule of dedup_mask, one
    # candidate row at a time against every later row
    fin = jnp.abs(cd) < _INF
    id_key = jnp.where(fin, ci, jnp.int32(n))
    key_scr[...] = id_key

    def dedup(j, dup):
        same = (id_key == key_scr[pl.ds(j, 1), :]) & (rc > j)
        return dup | same.astype(jnp.int32)
    dup = jax.lax.fori_loop(0, C, dedup, jnp.zeros(cd.shape, jnp.int32))
    d_dd = jnp.where((dup > 0) & fin, _INF, cd)
    keep = jnp.abs(d_dd) < _INF
    ok_ref[...] = keep.astype(jnp.int32)

    # candidates: rows >= C are padding, ordered after every real entry
    pad_c = rc >= C
    cand = _bitonic_sort_desc((
        jnp.where(pad_c, _I32_MAX, _sort_key(d_dd)),
        jnp.where(pad_c, _I32_MAX, rc + L),
        d_dd,
        ci,
        (~keep).astype(jnp.int32),
    ))
    # beam: rows >= L form a +inf plateau, so [beam asc | candidates desc]
    # is bitonic and one merge network yields the full ascending order
    bd = bd_ref[...]
    rb = jax.lax.broadcasted_iota(jnp.int32, bd.shape, 0)
    plateau = rb >= L
    beam = (
        jnp.where(plateau, _I32_MAX, _sort_key(bd)),
        jnp.where(plateau, _I32_MAX - 1, rb),
        bd,
        bi_ref[...],
        be_ref[...],
    )
    merged = _bitonic_merge(tuple(
        jnp.concatenate([b, c], axis=0) for b, c in zip(beam, cand)))
    oi_ref[...] = merged[3][:L]
    od_ref[...] = merged[2][:L]
    oe_ref[...] = merged[4][:L]


_LANES = 128


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def beam_merge_pallas(
    beam_d: jnp.ndarray,
    beam_ids: jnp.ndarray,
    beam_exp: jnp.ndarray,
    cand_d: jnp.ndarray,
    cand_ids: jnp.ndarray,
    *,
    n: int,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pallas bitonic sort + merge network; same contract as
    :func:`beam_merge_jnp` (bitwise, incl. ties — pinned in tests)."""
    B, L = beam_d.shape
    C = cand_d.shape[1]
    Pc = next_pow2(max(C, 8))
    Pb = next_pow2(L + Pc) - Pc
    Bp = -(-B // _LANES) * _LANES

    def lanes(x, rows, fill):
        """[B, m] -> [rows, Bp]: positions on sublanes, queries on lanes."""
        x = jnp.pad(x, ((0, Bp - B), (0, rows - x.shape[1])),
                    constant_values=fill)
        return x.T

    blk = lambda rows: pl.BlockSpec((rows, _LANES), lambda g: (0, g))  # noqa: E731
    oi, od, oe, ok = pl.pallas_call(
        functools.partial(_beam_merge_kernel, n=n, L=L, C=C),
        grid=(Bp // _LANES,),
        in_specs=[blk(Pb)] * 3 + [blk(Pc)] * 2,
        out_specs=[blk(L)] * 3 + [blk(Pc)],
        out_shape=[
            jax.ShapeDtypeStruct((L, Bp), jnp.int32),
            jax.ShapeDtypeStruct((L, Bp), jnp.float32),
            jax.ShapeDtypeStruct((L, Bp), jnp.int32),
            jax.ShapeDtypeStruct((Pc, Bp), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((Pc, _LANES), jnp.int32)],
        interpret=interpret,
    )(
        lanes(beam_d.astype(jnp.float32), Pb, _INF),
        lanes(beam_ids, Pb, -1),
        lanes(beam_exp.astype(jnp.int32), Pb, 1),
        lanes(cand_d.astype(jnp.float32), Pc, _INF),
        lanes(cand_ids, Pc, -1),
    )
    return (oi.T[:B], od.T[:B], oe.T[:B].astype(bool),
            ok.T[:B, :C].astype(bool))
