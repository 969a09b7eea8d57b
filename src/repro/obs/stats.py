"""Device-side traversal counters of the jitted searches, at two depths.

**Always-on loop totals.** Every ``_batched_search_core`` returns, beside
its ids and distances, one fixed ``i32[5]`` vector of batch totals of its
padded loop (:data:`LOOP_TOTALS`): ``row_slots`` (the loop's trips × B),
``row_iterations`` (Σ over rows of the trips in which the row expanded
at least one entry), ``candidate_slots`` (trips × B × M·E: the candidate
rows handed to the gather, useful or not), ``kept`` (candidates that
passed the predicate and visited tests and entered the beam merge: the
dedup keep mask, summed) and ``rows_fetched`` (the vector rows the gather
DMA'd: candidates that were not padding, not in an idle row and not
visited, counted from the same gated row ids the kernel receives; every
slot in the unfused loop, whose XLA gather fetches them all). The slot
counts come from the loop's static shapes inside the trace; the loop
itself pays one ``[B]`` add and two scalar sums per iteration, so the
served program carries
the totals at every ``stats`` setting: one compiled program, no second
one for the totals. (Counting ``kept`` after the loop instead, as the
visited bitmap's population less the entry bits, keeps the bitmap alive
past the loop, and at d = 128 that moved XLA's placement of the vector
table out of VMEM: the gather kernel ran 1.5× slower on a TPU v5e.)
:func:`record_loop_totals` folds them into the registry under a ``plan``
label.

**Per-query detail (``stats=True``).** ``SearchStats`` is an optional
extra output of ``_batched_search_core``, ``planned_exec_core`` and the
streaming/sharded serving steps, computed *inside* the jitted loop from
values the loop already carries (the live mask, the kernel's candidate
distances, the dedup keep mask, the visited bitmap) — no extra gathers,
no host sync per iteration. ``stats=True`` is a second jit cache entry
whose shapes are all fixed by (B, beam), so it never recompiles across
epoch swaps or plan mixes.

Counting semantics (pinned against a Python re-execution of the beam
search in ``tests/test_obs.py``):

  * ``iters[b]``        lockstep iterations in which query ``b`` expanded
                        at least one beam entry (== its own sequential
                        iteration count: each query's trajectory is
                        independent of the batch);
  * ``expanded[b]``     beam entries popped and expanded (== ``iters``
                        when ``expand=1``);
  * ``cand_total[b]``   neighbor slots examined (real ids only — the
                        ``-1`` adjacency padding is excluded);
  * ``cand_valid[b]``   candidates surviving the dominance test AND the
                        visited test (finite kernel distance);
  * ``kept[b]``         valid candidates surviving intra-iteration dedup
                        (what actually entered the beam merge);
  * ``visited[b]``      visited-set population at termination (entry point
                        + every kept candidate);
  * ``beam_occupancy[b]``  finite beam entries at termination;
  * ``hit_max_iters[b]``   True when the iteration cap cut the query off
                        while it still had unexpanded finite entries —
                        the early-termination cause (else the beam
                        converged, or the valid set was empty and the
                        query never started);
  * ``delta_valid[b]``  streaming only: delta-tier candidates passing the
                        filter (zeros for pure graph searches).

The loop totals are sums of these: ``row_iterations`` = Σ ``iters``,
``kept`` = Σ ``kept``, ``row_slots`` = B × max ``iters`` (every trip
has a live row) and, in the fused loops, ``kept`` ≤ ``rows_fetched`` ≤
Σ ``cand_total``, pinned in ``tests/test_obs.py``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import (
    COUNT_BUCKETS,
    FRACTION_BUCKETS,
    MetricsRegistry,
    resolve,
)


class SearchStats(NamedTuple):
    """Per-query traversal counters, every field ``[B]``.

    A NamedTuple of arrays, hence a pytree: it flows through ``jit``,
    ``shard_map`` and host conversion unchanged."""

    iters: jnp.ndarray           # [B] i32
    expanded: jnp.ndarray        # [B] i32
    cand_total: jnp.ndarray      # [B] i32
    cand_valid: jnp.ndarray      # [B] i32
    kept: jnp.ndarray            # [B] i32
    visited: jnp.ndarray         # [B] i32
    beam_occupancy: jnp.ndarray  # [B] i32
    hit_max_iters: jnp.ndarray   # [B] bool
    delta_valid: jnp.ndarray     # [B] i32

# the always-on batch totals of one padded search loop, in vector order,
# and the registry counter each is folded into (label ``plan``)
LOOP_TOTALS = ("row_slots", "row_iterations", "candidate_slots", "kept",
               "rows_fetched")
LOOP_COUNTERS = (
    ("repro_search_row_slots_total",
     "row slots of a padded search loop (trips x B)"),
    ("repro_search_iterations_total",
     "row slots in which the row expanded an entry"),
    ("repro_search_candidate_slots_total",
     "candidate rows handed to the gather (trips x B x M*E)"),
    ("repro_search_candidates_kept_total",
     "candidates that passed both tests and entered the merge"),
    ("repro_search_rows_fetched_total",
     "candidate vector rows the gather fetched"),
)


def init_search_stats(B: int) -> SearchStats:
    """All-zero counters for a batch of ``B``."""
    zi = jnp.zeros(B, dtype=jnp.int32)
    return SearchStats(
        iters=zi, expanded=zi, cand_total=zi, cand_valid=zi, kept=zi,
        visited=zi, beam_occupancy=zi,
        hit_max_iters=jnp.zeros(B, dtype=bool), delta_valid=zi,
    )


def accumulate_iteration(
    st: SearchStats,
    *,
    live: jnp.ndarray,    # [B, M] bool — beam entries actually expanded
    nb: jnp.ndarray,      # [B, M*E] i32 — candidate ids (-1 = padding)
    d_new: jnp.ndarray,   # [B, M*E] f32 — kernel distances (inf = filtered)
    keep: jnp.ndarray,    # [B, M*E] bool — dedup survivors
) -> SearchStats:
    """Fold one loop iteration's masks into the counters (trace-time)."""
    exp = jnp.sum(live.astype(jnp.int32), axis=1)
    tot = jnp.sum((nb >= 0).astype(jnp.int32), axis=1)
    val = jnp.sum(jnp.isfinite(d_new).astype(jnp.int32), axis=1)
    kp = jnp.sum(keep.astype(jnp.int32), axis=1)
    return st._replace(
        iters=st.iters + (exp > 0).astype(jnp.int32),
        expanded=st.expanded + exp,
        cand_total=st.cand_total + tot,
        cand_valid=st.cand_valid + val,
        kept=st.kept + kp,
    )


def loop_totals(
    trips: jnp.ndarray,       # scalar i32 — the loop's final iteration count
    row_iters: jnp.ndarray,   # [B] i32 — trips in which the row expanded
    kept: jnp.ndarray,        # scalar i32 — Σ of the keep mask over trips
    fetched: jnp.ndarray,     # scalar i32 — Σ of the rows fetched over trips
    *,
    width: int,               # candidate slots per row and trip (M·E)
) -> jnp.ndarray:
    """The ``i32[5]`` :data:`LOOP_TOTALS` of one loop (trace-time); int32
    holds them while trips·B·M·E < 2^31."""
    row_slots = trips * row_iters.shape[0]
    return jnp.stack(
        [row_slots, jnp.sum(row_iters), row_slots * width, kept, fetched]
    ).astype(jnp.int32)


def finalize_stats(
    st: SearchStats,
    *,
    beam_d: jnp.ndarray,    # [B, L] f32 final beam distances
    beam_exp: jnp.ndarray,  # [B, L] bool final expansion flags
    visited: jnp.ndarray,   # [B, W] u32 bitmap or [B, n] bool dense
) -> SearchStats:
    """Termination-time fields: visited population, occupancy, stop cause."""
    finite = jnp.isfinite(beam_d)
    if visited.dtype == jnp.uint32:
        pop = jnp.sum(
            jax.lax.population_count(visited).astype(jnp.int32), axis=1
        )
    else:
        pop = jnp.sum(visited.astype(jnp.int32), axis=1)
    return st._replace(
        visited=pop,
        beam_occupancy=jnp.sum(finite.astype(jnp.int32), axis=1),
        hit_max_iters=jnp.any(~beam_exp & finite, axis=1),
    )


def combine_stats(a: SearchStats, b: SearchStats) -> SearchStats:
    """Elementwise merge of two instantiations serving DISJOINT row sets
    (the planner's graph + wide searches: a row masked out of one search
    contributes exact zeros there, so addition is selection)."""
    return SearchStats(
        iters=a.iters + b.iters,
        expanded=a.expanded + b.expanded,
        cand_total=a.cand_total + b.cand_total,
        cand_valid=a.cand_valid + b.cand_valid,
        kept=a.kept + b.kept,
        visited=a.visited + b.visited,
        beam_occupancy=a.beam_occupancy + b.beam_occupancy,
        hit_max_iters=a.hit_max_iters | b.hit_max_iters,
        delta_valid=a.delta_valid + b.delta_valid,
    )


def stats_to_host(st: SearchStats) -> SearchStats:
    """Materialize every counter as a numpy array (one device sync)."""
    return SearchStats(*(np.asarray(x) for x in st))


def per_query_dict(st: SearchStats) -> dict:
    """The fields as {name: i32 array} — the sharded steps' stats output."""
    return {
        name: getattr(st, name).astype(jnp.int32)
        for name in SearchStats._fields
    }


def record_loop_totals(
    totals,
    *,
    plans,
    registry: Optional[MetricsRegistry] = None,
) -> None:
    """Fold the always-on totals of one batch into the registry, in one
    locked update: ``totals`` is ``[P, 5]`` (host or device), one
    :data:`LOOP_TOTALS` row per padded loop, labelled ``plans[p]``."""
    totals = np.asarray(totals).reshape(len(plans), len(LOOP_TOTALS))
    resolve(registry).inc_counters(
        (name, help, float(v), {"plan": plan})
        for row, plan in zip(totals, plans)
        for (name, help), v in zip(LOOP_COUNTERS, row)
    )


def record_search_stats(
    st,
    *,
    registry: Optional[MetricsRegistry] = None,
    n_real: Optional[int] = None,
) -> None:
    """Fold one batch's per-query device counters into the host metrics
    registry.

    ``st`` is a ``SearchStats`` (host or device arrays) or the sharded
    steps' ``per_query_dict``. ``n_real`` truncates to the first rows when
    the batch carries sentinel padding (``RequestBatcher``) so no-op rows
    don't dilute the per-query histograms. The iteration and kept totals
    are not counted here: the always-on loop totals count them
    (:func:`record_loop_totals`)."""
    reg = resolve(registry)
    get = (st.get if isinstance(st, dict) else
           lambda name, default=None: getattr(st, name, default))

    def col(name):
        v = get(name)
        if v is None:
            return None
        v = np.asarray(v)
        return v[:n_real] if n_real is not None else v

    iters = col("iters")
    if iters is None or iters.size == 0:
        return
    expanded = col("expanded")
    cand_total = col("cand_total")
    cand_valid = col("cand_valid")
    reg.counter(
        "repro_search_queries_total", "queries with device counters recorded"
    ).inc(int(iters.size))
    for name, v in (
        ("repro_search_nodes_expanded_total", expanded),
        ("repro_search_candidates_examined_total", cand_total),
        ("repro_search_candidates_valid_total", cand_valid),
        ("repro_search_delta_candidates_valid_total", col("delta_valid")),
    ):
        if v is not None:
            reg.counter(name, "batched device traversal counter").inc(
                float(np.sum(v, dtype=np.int64))
            )
    for name, v in (
        ("repro_search_expanded_per_query", expanded),
        ("repro_search_visited_per_query", col("visited")),
        ("repro_search_beam_occupancy", col("beam_occupancy")),
    ):
        if v is not None:
            h = reg.histogram(name, "per-query traversal distribution",
                              buckets=COUNT_BUCKETS)
            h.observe_many(float(x) for x in v)
    if cand_total is not None and cand_valid is not None:
        frac = reg.histogram(
            "repro_search_valid_fraction",
            "valid candidates / examined candidates per query",
            buckets=FRACTION_BUCKETS,
        )
        mask = cand_total > 0
        frac.observe_many(
            (cand_valid[mask] / cand_total[mask]).astype(float)
        )
    hit = col("hit_max_iters")
    if hit is not None:
        term = reg.counter(
            "repro_search_terminations_total", "per-query stop cause"
        )
        hit = hit.astype(bool)
        started = iters > 0
        n_cap = int(np.count_nonzero(hit))
        n_conv = int(np.count_nonzero(~hit & started))
        n_empty = int(np.count_nonzero(~hit & ~started))
        if n_cap:
            term.inc(n_cap, cause="iteration_cap")
        if n_conv:
            term.inc(n_conv, cause="beam_converged")
        if n_empty:
            term.inc(n_empty, cause="no_entry")
