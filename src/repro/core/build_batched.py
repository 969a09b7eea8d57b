"""Batched, device-accelerated UDG construction (paper §V-A/§V-B, wave form).

The sequential practical constructor (``repro.core.build.build_udg`` with
``batched=False``) runs one host-side ``udg_search`` per inserted object —
a Python ``heapq`` best-first traversal — which makes construction the
bottleneck of the whole system once search and streaming are fused Pallas.
This module restructures the same algorithm around *insertion waves*:

1.  Objects are still inserted in ascending transformed-Y order (the §IV-B
    order that Theorem 1's induction needs), but ``wave`` of them at a time.
2.  The broad label-ignoring construction search (§V-A) for a whole wave
    runs as ONE ``broad_batched_search`` launch against the partially built
    index: the full vector table lives on device from the start (all rows
    are known up front; un-inserted rows are unreachable), and the adjacency
    is a ``BroadExport`` — a unique-neighbor dense table folded in edge-by-
    edge on the host and re-uploaded once per wave, never per insert. Rows
    are width-capped at ``max(Z, 2M, 32)`` (earliest neighbors kept): the
    wave search's per-iteration gather cost is linear in row width while
    broad-pool recall stays flat down to width ~ Z, so hub rows would
    otherwise tax every iteration for nothing.
3.  Earlier members of the *same* wave are not yet in the device graph, so
    each member's candidate pool is the merge of its device results with
    exact brute-force distances to its intra-wave predecessors (one
    ``[W, W]`` einsum per wave) — at the point object ``j`` is processed its
    pool draws on exactly the objects the sequential constructor could see.
4.  The threshold sweep + PRUNE + patch-edge emission run on the host but
    vectorized: one pool x pool distance matrix per insertion (reused by
    every sweep round via ``prune_precomputed``), per-edge MaxLeap right
    boundaries as one ``np.minimum``, and label tuples appended in batches
    (``LabeledGraph.add_bidirectional_batch``) instead of per-edge Python
    calls.

The wave loop is factored into :class:`_WaveBuildState` — a resumable
dispatch/process state machine per graph — so that *several graphs can be
built concurrently* (:func:`build_graphs_concurrent`): ``dispatch`` only
launches the wave's device search (JAX dispatch is asynchronous, so it
returns immediately with result handles) while ``process`` blocks on the
handles and runs the host-side sweep. Round-robining dispatch/process
across segment builders keeps one device search in flight per segment
while the host sweeps another segment's wave — the segmented index
(``repro.scale``) builds every per-segment subgraph through this path
with a shared ``pad_nodes``, so all segments reuse ONE compiled wave
search. The single-graph driver ``build_udg_batched`` is the same state
machine stepped to completion and is operation-for-operation identical
to the original fused loop.

The emitted labels are identical in form to the sequential constructor's
(same leap policies, same §V-B patch rule), so Lemma 2 validity holds
unchanged; only the candidate pools differ (device beam search vs host
heapq), which shifts recall by well under the 0.5 pt acceptance band — the
parity test and ``BENCH_build.json`` track it.

All ``a``/``c``/``x_R`` values here are canonical *ranks* (indices into
``U_X``/``U_Y``), never raw floats; distances are squared L2 on raw vectors.
"""
from __future__ import annotations

import time
from typing import List, Sequence, Tuple

import numpy as np

from repro.core.graph import LabeledGraph
from repro.core.patch import add_patch_edges
from repro.core.prune import pool_distance_matrix, prune_precomputed

_NODE_BUCKET = 256  # table rows padded to a multiple of this → compile reuse


def _bucket(n: int) -> int:
    return max(((n + _NODE_BUCKET - 1) // _NODE_BUCKET) * _NODE_BUCKET, _NODE_BUCKET)


class _WaveBuildState:
    """Resumable wave-pipelined build of one ``LabeledGraph``.

    The per-wave work splits into two halves with a natural pipeline
    boundary at the device:

    * :meth:`dispatch` — upload the current ``BroadExport`` adjacency and
      launch the wave's broad device search. JAX dispatch is asynchronous:
      the call returns device-array *handles* without waiting for the
      search to finish, so the caller is free to do host work (another
      graph's sweep) while this wave computes.
    * :meth:`process` — block on the handles (``np.asarray``) and run the
      host-side sweep/PRUNE/patch for every wave member, mutating the
      graph and the ``BroadExport`` for the *next* dispatch.

    A wave's dispatch depends on the previous wave's processed edges, so
    within one graph the two phases strictly alternate; concurrency comes
    from interleaving multiple states (``build_graphs_concurrent``).
    """

    def __init__(
        self,
        vectors: np.ndarray,
        s: np.ndarray,
        t: np.ndarray,
        relation: str,
        *,
        M: int = 16,
        Z: int = 128,
        K_p: int = 8,
        leap: str = "maxleap",
        patch: str = "full",
        wave: int = 256,
        pad_nodes: int | None = None,
        use_ref: bool | None = None,
    ):
        # Deferred so `repro.core` stays importable (and the sequential
        # path usable) without jax — the device stack is only pulled in
        # when a batched build actually runs.
        import jax.numpy as jnp

        from repro.search.device_graph import BroadExport

        self._jnp = jnp
        self.t0 = time.perf_counter()
        self.M = int(M)
        self.Z = int(Z)
        self.K_p = int(K_p)
        self.leap = leap
        self.patch = patch
        self.use_ref = use_ref

        g = LabeledGraph(vectors, s, t, relation)
        self.g = g
        self.order = g.insert_order
        self.n = g.n
        self.y_max = g.num_y - 1
        self.x_rank = g.x_rank
        self.y_rank = g.y_rank

        n_pad = max(_bucket(self.n), pad_nodes or 0)
        table = np.zeros((n_pad, g.dim), dtype=np.float32)
        table[: self.n] = g.vectors
        self.table = table
        self.dev_table = jnp.asarray(table)
        self.dev_norms = jnp.asarray(
            np.einsum("ij,ij->i", table, table).astype(np.float32)
        )

        # Broad rows capped near the pool size: pool recall is flat down to
        # width ~ Z while wave-search iteration cost is linear in width.
        broad_cap = max(self.Z, 2 * self.M, 32)
        self.broadx = BroadExport(n_pad, init_degree=broad_cap, max_width=broad_cap)
        self.W = max(1, min(int(wave), self.n))
        self.global_ep = int(self.order[0])

        self.ins_ids = np.empty(self.n, dtype=np.int64)
        self.ins_x = np.empty(self.n, dtype=np.int64)
        self.cnt = 0
        self.rounds = 0
        self.launches = 0
        self.n_waves = 0
        self.w0 = 0  # start index (into insertion order) of the next wave
        self._pending: tuple | None = None

    @property
    def done(self) -> bool:
        return self._pending is None and self.w0 >= self.n

    def dispatch(self) -> None:
        """Launch the next wave's broad device search (non-blocking)."""
        assert self._pending is None and self.w0 < self.n
        jnp = self._jnp
        w0 = self.w0
        ids_w = self.order[w0 : w0 + self.W].astype(np.int64)
        Wn = int(ids_w.size)
        self.n_waves += 1
        wv = self.table[ids_w]  # [Wn, D] f32

        if w0 > 0:
            # 2. one broad label-ignoring device search for the whole wave
            from repro.search.batched import broad_batched_search

            q_pad = np.zeros((self.W, self.g.dim), dtype=np.float32)
            q_pad[:Wn] = wv
            ep = np.full(self.W, -1, dtype=np.int32)
            ep[:Wn] = self.global_ep
            dev_ids, dev_d = broad_batched_search(
                self.dev_table,
                self.dev_norms,
                jnp.asarray(self.broadx.view()),
                jnp.asarray(q_pad),
                jnp.asarray(ep),
                k=self.Z,
                beam=self.Z,
                expand=min(4, self.Z),  # multi-expand amortizes loop overhead
                use_ref=self.use_ref,
            )
            self.launches += 1
        else:
            dev_ids = dev_d = None

        # 3. exact intra-wave distances (earlier wave members are inserted
        # before this member is processed, so they belong in its pool).
        # Gram form keeps this O(W²) memory — a [W, W, D] diff tensor would
        # not survive production dims.
        intra = pool_distance_matrix(self.table, ids_w)
        self._pending = (ids_w, Wn, dev_ids, dev_d, intra)
        self.w0 = w0 + self.W

    def process(self) -> None:
        """Block on the pending wave's results and run the host sweep."""
        assert self._pending is not None
        ids_w, Wn, dev_ids, dev_d, intra = self._pending
        self._pending = None
        g = self.g
        x_rank, y_rank = self.x_rank, self.y_rank
        M, Z = self.M, self.Z
        if dev_ids is not None:
            pool_ids = np.asarray(dev_ids)[:Wn]
            pool_d = np.asarray(dev_d)[:Wn]
        else:
            pool_ids = np.full((Wn, 1), -1, dtype=np.int32)
            pool_d = np.full((Wn, 1), np.inf, dtype=np.float32)

        for wi in range(Wn):
            vj = int(ids_w[wi])
            xj = int(x_rank[vj])
            yj = int(y_rank[vj])
            if self.cnt > 0:
                dev_row = pool_ids[wi]
                keep = (dev_row >= 0) & np.isfinite(pool_d[wi])
                cids = np.concatenate(
                    [dev_row[keep].astype(np.int64), ids_w[:wi]]
                )
                cds = np.concatenate(
                    [pool_d[wi][keep], intra[wi, :wi]]
                ).astype(np.float32)
                sel = np.lexsort((cids, cds))[:Z]
                ann = cids[sel]
                ann_d = cds[sel]
                uncovered_from = None
                if ann.size == 0:
                    uncovered_from = 0
                else:
                    # 4. vectorized sweep: one pool matrix reused per round
                    dmat = pool_distance_matrix(g.vectors, ann)
                    ann_x = x_rank[ann].astype(np.int64)
                    idx_all = np.arange(ann.size)
                    i = 0
                    while i <= xj:
                        live = ann_x >= i
                        if not live.any():
                            uncovered_from = i
                            break
                        self.rounds += 1
                        li = idx_all[live]
                        N = prune_precomputed(
                            ann[li], ann_d[li], dmat[np.ix_(li, li)], M
                        )
                        nx = x_rank[N].astype(np.int64)
                        if self.leap == "conservative":
                            x_R = int(min(xj, int(nx.min())))
                            added = g.add_bidirectional_batch(
                                vj, N, i, x_R, yj, self.y_max
                            )
                            i = x_R + 1
                        else:  # maxleap
                            x_leap = int(nx.max())
                            r_arr = np.minimum(xj, nx)
                            added = g.add_bidirectional_batch(
                                vj, N, i, r_arr, yj, self.y_max
                            )
                            i = min(xj, x_leap) + 1
                        self.broadx.add_edges(vj, added)
                if uncovered_from is not None and self.patch != "none":
                    sel_patch = add_patch_edges(
                        g, vj, uncovered_from, xj,
                        self.ins_ids[: self.cnt], self.ins_x[: self.cnt],
                        M, self.K_p, self.patch,
                    )
                    self.broadx.add_edges(vj, sel_patch)
            self.ins_ids[self.cnt] = vj
            self.ins_x[self.cnt] = xj
            self.cnt += 1

    def finish(self) -> Tuple[LabeledGraph, "BuildReport"]:
        """Return ``(graph, report)``; the state must be :attr:`done`.

        ``seconds`` is the window from this state's construction — under
        ``build_graphs_concurrent`` the per-graph windows overlap, so they
        sum to more than the fleet's wall-clock (by design: each report
        still describes its own graph's pipeline span)."""
        assert self.done
        from repro.core.build import BuildReport

        return self.g, BuildReport(
            n=self.n,
            seconds=time.perf_counter() - self.t0,
            num_tuples=self.g.num_tuples,
            num_patch_tuples=self.g.num_patch_tuples,
            sweep_rounds=self.rounds,
            broad_searches=self.launches,
            index_bytes=self.g.stats().index_bytes,
            waves=self.n_waves,
        )


def build_udg_batched(
    vectors: np.ndarray,
    s: np.ndarray,
    t: np.ndarray,
    relation: str,
    M: int = 16,
    Z: int = 128,
    K_p: int = 8,
    *,
    leap: str = "maxleap",
    patch: str = "full",
    wave: int = 256,
    pad_nodes: int | None = None,
    use_ref: bool | None = None,
) -> Tuple[LabeledGraph, "BuildReport"]:
    """Wave-pipelined practical constructor; same contract as ``build_udg``.

    ``wave`` is the insertion-wave width (1 degenerates to per-object device
    searches). ``pad_nodes`` pads the device table to a fixed row count —
    pass the streaming tier's ``node_capacity`` so every epoch rebuild hits
    the same compiled wave search. ``use_ref`` selects the in-wave search's
    kernels (``None``: the backend's choice, ``repro.kernels.ops.
    use_reference`` — the Pallas kernels on TPU, the jnp oracle on CPU).
    Wall-clock in the returned ``BuildReport``
    is one perf_counter window around the whole pipeline (device searches,
    host sweeps, patching — no per-insert accumulation), ``waves`` counts
    insertion waves, and ``broad_searches`` counts *device search launches*,
    not per-object searches — the n-to-n/wave reduction is the point.
    """
    st = _WaveBuildState(
        vectors, s, t, relation, M=M, Z=Z, K_p=K_p,
        leap=leap, patch=patch, wave=wave, pad_nodes=pad_nodes,
        use_ref=use_ref,
    )
    while not st.done:
        st.dispatch()
        st.process()
    return st.finish()


def build_graphs_concurrent(
    datasets: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray]],
    relation: str,
    M: int = 16,
    Z: int = 128,
    K_p: int = 8,
    *,
    leap: str = "maxleap",
    patch: str = "full",
    wave: int = 256,
    pad_nodes: int | None = None,
    use_ref: bool | None = None,
) -> List[Tuple[LabeledGraph, "BuildReport"]]:
    """Build several UDGs concurrently through one wave pipeline.

    ``datasets`` is a sequence of ``(vectors, s, t)`` triples — one per
    graph (e.g. one per dominance-space segment). Each graph gets its own
    :class:`_WaveBuildState`; the driver round-robins **dispatch** (launch
    the wave's asynchronous device search) across all unfinished graphs
    first, then **process** (block + host sweep) in the same order, so
    while graph ``i``'s sweep runs on the host, graphs ``i+1..`` already
    have device searches in flight. No threads are involved — the schedule
    is a deterministic interleave, so each graph is bit-identical to what
    ``build_udg_batched`` would have produced for it alone.

    Pass one shared ``pad_nodes`` (>= the largest dataset) so every state
    pads its device table to the same row count and all graphs execute the
    same compiled wave-search program.
    """
    states = [
        _WaveBuildState(
            v, s, t, relation, M=M, Z=Z, K_p=K_p,
            leap=leap, patch=patch, wave=wave, pad_nodes=pad_nodes,
            use_ref=use_ref,
        )
        for (v, s, t) in datasets
    ]
    while True:
        live = [st for st in states if not st.done]
        if not live:
            break
        for st in live:
            st.dispatch()
        for st in live:
            st.process()
    return [st.finish() for st in states]
