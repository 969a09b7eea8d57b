"""Device-resident UDG: padded dense arrays exported from the host index.

TPUs want dense, statically-shaped gathers, so the host adjacency (ragged
lists of labeled tuples) is exported as

  nbr     [n, E] int32       neighbor id per tuple slot (-1 = padding)
  plabels [n, E, 2] uint32   bit-packed canonical rank rectangles — the
                             default layout: (l, r) in the two 16-bit
                             halves of word 0, (b, e) in word 1
  labels  [n, E, 4] int32    the unpacked legacy layout, kept only when the
                             grid exceeds the 16-bit rank budget (or the
                             caller forces ``packed_labels=False``)

with E = max labeled degree rounded up to a lane multiple. Canonical ranks
are indices into the ``U_X``/``U_Y`` grids, so a grid of at most 2^16
distinct values per axis fits two ranks per 32-bit word — the label table
(the single largest index component: 16 B/edge unpacked, ~11x the int8
vector table at d=32) halves at rest and in flight, and streaming epoch
snapshots shrink by the same factor. ``pack_labels``/``unpack_labels`` are
the bijection; ``export_device_graph`` guards the rank width and falls
back to the int32 layout with a warning when a grid overflows.

Entry lookup and canonicalization grids ride along so a query can be
served end-to-end on device, as do per-node squared norms (cached once
here so the gather-fused kernel never re-reduces ``sum(c*c)``) and — with
``quantize_int8=True`` — int8 storage + per-vector scales for the
bandwidth-saving distance path. The static node capacity also fixes the
width of the search loop's bit-packed visited bitmap (``visited_words``).

``DeviceGraph.device()`` memoizes the jnp views of every search-visible
array (table, norms, scales, nbr, labels) so serving entry points stop
re-staging multi-megabyte host buffers on every batch; the cache dies with
the export (streaming epoch swaps publish a fresh ``DeviceGraph``) and can
be dropped explicitly with ``invalidate_device()``.

For the streaming subsystem (repro.stream) the export additionally supports
*fixed capacities*: node and edge dimensions padded to caller-chosen static
sizes so the jitted serving step sees one shape across compaction epochs,
plus a ``DeltaSegment`` — the statically-sized device view of the mutable
delta tier (append-only vectors + per-slot label rectangles encoding the
interval predicate in monotone float-key space).
"""
from __future__ import annotations

import dataclasses
import warnings

import numpy as np

from repro.core.entry import EntryTable
from repro.core.graph import LabeledGraph

# canonical ranks are packed two-per-word in 16-bit halves; a grid axis
# with more distinct values than this cannot use the packed layout
RANK_LIMIT = 1 << 16


def pack_labels(labels: np.ndarray) -> np.ndarray:
    """Bit-pack int32 rank rectangles ``[..., 4]`` (l, r, b, e) into uint32
    word pairs ``[..., 2]``: word 0 = ``l | r << 16``, word 1 =
    ``b | e << 16``. Raises ``ValueError`` when any rank is negative or
    >= 2^16 (use the int32 layout instead — see ``export_device_graph``)."""
    labels = np.asarray(labels)
    if labels.shape[-1] != 4:
        raise ValueError(f"expected trailing dim 4, got {labels.shape}")
    if labels.size and (labels.min() < 0 or labels.max() >= RANK_LIMIT):
        raise ValueError(
            f"rank out of 16-bit range [0, {RANK_LIMIT}): "
            f"min={labels.min() if labels.size else 0} "
            f"max={labels.max() if labels.size else 0}"
        )
    u = labels.astype(np.uint32)
    out = np.empty(labels.shape[:-1] + (2,), dtype=np.uint32)
    out[..., 0] = u[..., 0] | (u[..., 1] << 16)
    out[..., 1] = u[..., 2] | (u[..., 3] << 16)
    return out


def unpack_labels(plabels: np.ndarray) -> np.ndarray:
    """Inverse of :func:`pack_labels`: uint32 ``[..., 2]`` -> int32
    ``[..., 4]`` rectangles. Bitwise round-trip (pinned in tests)."""
    plabels = np.asarray(plabels, dtype=np.uint32)
    if plabels.shape[-1] != 2:
        raise ValueError(f"expected trailing dim 2, got {plabels.shape}")
    out = np.empty(plabels.shape[:-1] + (4,), dtype=np.int32)
    out[..., 0] = (plabels[..., 0] & 0xFFFF).astype(np.int32)
    out[..., 1] = (plabels[..., 0] >> 16).astype(np.int32)
    out[..., 2] = (plabels[..., 1] & 0xFFFF).astype(np.int32)
    out[..., 3] = (plabels[..., 1] >> 16).astype(np.int32)
    return out


def unpack_labels_device(plabels, edges: int | None = None):
    """jnp twin of :func:`unpack_labels` for traced/device arrays — used by
    jitted serving steps that must serve the ``fused=False`` parity
    baseline (int32 layout) from a packed label stack. Takes the packed
    words ``[..., E, 2]`` or the kernels' label rows
    (``repro.kernels.layout``), ``edges`` = E. One definition of the word
    layout, shared with the kernel oracle (lazy import keeps this module
    importable without JAX)."""
    from repro.kernels.layout import label_words
    from repro.kernels.ref import unpack_labels_jnp

    return unpack_labels_jnp(label_words(plabels, slice(None), edges))


@dataclasses.dataclass(frozen=True)
class DeviceIndex:
    """Memoized jnp views of a ``DeviceGraph``'s search-visible arrays,
    in the layouts the gather kernels DMA from (``repro.kernels.layout``).

    ``table`` is the storage the distance kernels score (int8 ``vec_q``
    when quantized, else f32 ``vectors``) as ``[n, 1, W]`` rows; ``labels``
    is the packed table as ``[n, 1, ⌈2E/128⌉·128]`` int32 label rows when
    the export packed, else the int32 ``[n, E, 4]`` layout — the search
    core dispatches on ``repro.kernels.layout.is_int32_rects``.
    """

    table: object             # jnp [n, 1, W] f32 rows or int32 int8-words
    scales: object | None     # jnp [n] f32 (int8 storage only)
    norms: object | None      # jnp [n] f32 cached ‖v‖²
    nbr: object               # jnp [n, E] int32
    labels: object            # jnp [n, 1, W] int32 or [n, E, 4] int32

    @property
    def packed(self) -> bool:
        from repro.kernels.layout import is_int32_rects

        return not is_int32_rects(self.labels)


@dataclasses.dataclass
class DeviceGraph:
    vectors: np.ndarray        # [n, d] f32
    nbr: np.ndarray            # [n, E] int32, -1 padded
    labels: np.ndarray | None  # [n, E, 4] int32 — None when packed-only
    U_X: np.ndarray            # [num_x] f64 canonical X values
    U_Y: np.ndarray            # [num_y] f64 canonical Y values
    entry_node: np.ndarray     # [num_x] int32 (-1 = none)
    entry_y_rank: np.ndarray   # [num_x] int32
    relation: str
    norms: np.ndarray | None = None   # [n] f32 cached ‖v‖² (of the rows the
                                      # search scores: dequantized if int8)
    vec_q: np.ndarray | None = None   # [n, d] int8 quantized storage
    scales: np.ndarray | None = None  # [n] f32 per-vector dequant scales
    planner: object | None = None     # repro.exec.SelectivityEstimator —
                                      # rank-space histogram for the query
                                      # planner, rebuilt with each export
    plabels: np.ndarray | None = None  # [n, E, 2] uint32 bit-packed labels
                                       # (the at-rest layout when ranks fit)
    _cache: dict | None = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def n(self) -> int:
        return int(self.vectors.shape[0])

    @property
    def max_degree(self) -> int:
        return int(self.nbr.shape[1])

    @property
    def visited_words(self) -> int:
        """Width of the bit-packed per-query visited bitmap (uint32 words).

        Node capacity is static, so this is static too — the serving step's
        ``[B, visited_words]`` bitmap keeps one shape across epoch swaps."""
        return (self.n + 31) // 32

    def labels_i32(self) -> np.ndarray:
        """The int32 ``[n, E, 4]`` rectangle view — the stored array when
        the export fell back, otherwise unpacked (and cached) from the
        packed words. Used by the non-packed parity-oracle search paths."""
        if self.labels is not None:
            return self.labels
        cache = self._cache if self._cache is not None else {}
        out = cache.get("labels_i32")
        if out is None:
            out = unpack_labels(self.plabels)
            cache["labels_i32"] = out
            self._cache = cache
        return out

    def device(self) -> DeviceIndex:
        """Memoized device-array bundle of the search-visible index state.

        Built once per export — ``batched_udg_search``, the planned
        executor, the brute-force scan, and the streaming/sharded serving
        paths all draw from it instead of calling ``jnp.asarray`` per
        batch (which re-staged the full table every call). Streaming epoch
        swaps publish a new ``DeviceGraph``, so the stale bundle dies with
        the old epoch object; ``invalidate_device()`` drops it early."""
        import jax.numpy as jnp

        from repro.kernels.layout import label_rows, table_rows

        cache = self._cache if self._cache is not None else {}
        dev = cache.get("device")
        if dev is None:
            if self.vec_q is not None:
                table = table_rows(self.vec_q)
                scales = jnp.asarray(self.scales)
            else:
                table = table_rows(np.asarray(self.vectors, np.float32))
                scales = None
            lab = (label_rows(self.plabels) if self.plabels is not None
                   else self.labels)
            dev = DeviceIndex(
                table=jnp.asarray(table),
                scales=scales,
                norms=jnp.asarray(self.norms) if self.norms is not None else None,
                nbr=jnp.asarray(self.nbr),
                labels=jnp.asarray(lab),
            )
            cache["device"] = dev
            self._cache = cache
        return dev

    def serving_labels(self, *, fused: bool = True, packed: bool | None = None):
        """The device label view a serving call should search with — ONE
        definition of the layout rule for every entry point
        (``batched_udg_search``, ``exec.execute_batch``,
        ``StreamingIndex.search``):

        * ``packed=None`` — packed words whenever the export carries them;
        * ``packed=True`` — require the packed export (``ValueError`` on a
          rank-width fallback, regardless of ``fused``);
        * ``packed=False`` — force the int32 parity-oracle layout;
        * ``fused=False`` — the pre-gather baseline only understands int32
          rectangles, so the packed words are never returned.
        """
        if packed is None:
            packed = self.plabels is not None
        elif packed and self.plabels is None:
            raise ValueError(
                "packed=True but the export carries no packed labels "
                "(grid exceeded the 16-bit rank budget or "
                "packed_labels=False)"
            )
        dev = self.device()
        if fused and packed:
            return dev.labels
        return self.device_labels_i32() if dev.packed else dev.labels

    def device_labels_i32(self):
        """Memoized jnp int32 label view (the parity-oracle layout)."""
        import jax.numpy as jnp

        cache = self._cache if self._cache is not None else {}
        out = cache.get("device_labels_i32")
        if out is None:
            out = jnp.asarray(self.labels_i32())
            cache["device_labels_i32"] = out
            self._cache = cache
        return out

    def invalidate_device(self) -> None:
        """Drop the memoized device bundle (and unpacked-label cache)."""
        self._cache = None

    def nbytes_by_component(self) -> dict:
        """Host bytes of each index component (the at-rest layout: packed
        labels when available; the lazily unpacked cache is not counted)."""
        lab = self.plabels if self.plabels is not None else self.labels
        out = {
            "vectors": self.vectors.nbytes,
            "nbr": self.nbr.nbytes,
            "labels": lab.nbytes if lab is not None else 0,
            "grids": self.U_X.nbytes + self.U_Y.nbytes,
            "entry": self.entry_node.nbytes + self.entry_y_rank.nbytes,
        }
        if self.norms is not None:
            out["norms"] = self.norms.nbytes
        if self.vec_q is not None:
            out["vec_q"] = self.vec_q.nbytes
        if self.scales is not None:
            out["scales"] = self.scales.nbytes
        return out

    def nbytes(self) -> int:
        return sum(self.nbytes_by_component().values())


def export_device_graph(
    g: LabeledGraph,
    et: EntryTable | None = None,
    *,
    lane: int = 8,
    node_capacity: int | None = None,
    edge_capacity: int | None = None,
    quantize_int8: bool = False,
    planner_buckets: int = 64,
    packed_labels: bool | None = None,
) -> DeviceGraph:
    """Pad the host adjacency into dense arrays (E = max degree, lane-aligned).

    ``node_capacity``/``edge_capacity`` fix the padded dims to static sizes
    (for epoch-swapped streaming serving). Padding node rows carry no edges
    and are unreachable (never referenced by ``nbr`` or the entry table).
    Rows whose labeled degree exceeds ``edge_capacity`` keep their earliest
    tuples — those come from the threshold sweep (the connectivity-critical
    edges); patch tuples are appended last and are the first to be dropped.

    ``packed_labels`` selects the label layout: ``None`` (default) packs
    the rank rectangles into ``[n, E, 2]`` uint32 words whenever both
    canonical grids fit 16-bit ranks and falls back to the int32
    ``[n, E, 4]`` layout *with a warning* otherwise; ``True`` requires the
    packed layout (raises ``ValueError`` on overflow — used by streaming,
    which must keep one layout across epochs); ``False`` forces int32 (the
    parity-oracle layout).

    Per-node squared norms are precomputed here — once per export instead of
    once per beam expansion — so the gather-fused kernel scores candidates
    as ``‖c‖² − 2·q·c + ‖q‖²`` with a cached vector load. With
    ``quantize_int8`` the export additionally carries int8 storage
    (``vec_q`` + per-vector ``scales``; 4x less gather traffic), and the
    cached norms are of the *dequantized* rows so distances match a
    dequantize-then-score oracle exactly.
    """
    if et is None:
        et = EntryTable(g)
    degs = [g.adj[u].size for u in range(g.n)]
    E = max(degs) if degs else 1
    E = max(((E + lane - 1) // lane) * lane, lane)
    if edge_capacity is not None:
        E = edge_capacity
    n_pad = g.n if node_capacity is None else node_capacity
    if n_pad < g.n:
        raise ValueError(f"node_capacity {n_pad} < graph size {g.n}")
    nbr = np.full((n_pad, E), -1, dtype=np.int32)
    labels = np.zeros((n_pad, E, 4), dtype=np.int32)
    for u in range(g.n):
        nb, l, r, b, e = g.tuples(u)
        k = min(nb.shape[0], E)
        nbr[u, :k] = nb[:k]
        labels[u, :k, 0] = l[:k]
        labels[u, :k, 1] = r[:k]
        labels[u, :k, 2] = b[:k]
        labels[u, :k, 3] = e[:k]
    vectors = g.vectors
    if n_pad > g.n:
        vectors = np.zeros((n_pad, g.dim), dtype=np.float32)
        vectors[: g.n] = g.vectors
    vec_q = scales = None
    if quantize_int8:
        v32 = np.asarray(vectors, dtype=np.float32)
        amax = np.maximum(np.max(np.abs(v32), axis=1), 1e-12)
        scales = (amax / 127.0).astype(np.float32)
        vec_q = np.clip(np.round(v32 / scales[:, None]), -127, 127).astype(np.int8)
        scored = vec_q.astype(np.float32) * scales[:, None]
    else:
        scored = np.asarray(vectors, dtype=np.float32)
    norms = np.sum(scored * scored, axis=1, dtype=np.float32)
    ent = et.device_arrays()
    # rank-width guard: two 16-bit ranks per packed word, so both grids
    # must stay under RANK_LIMIT (ranks are grid indices, and the emitted
    # rectangles never exceed them — belt-and-braces checked by pack_labels)
    num_x, num_y = g.space.U_X.shape[0], g.space.U_Y.shape[0]
    fits = num_x <= RANK_LIMIT and num_y <= RANK_LIMIT
    plabels = None
    if packed_labels is None:
        if fits:
            plabels = pack_labels(labels)
            labels = None
        else:
            warnings.warn(
                f"canonical grid ({num_x} x {num_y}) exceeds the 16-bit "
                f"rank budget ({RANK_LIMIT}); falling back to the int32 "
                "label layout", RuntimeWarning, stacklevel=2,
            )
    elif packed_labels:
        if not fits:
            raise ValueError(
                f"packed_labels=True but canonical grid ({num_x} x {num_y})"
                f" exceeds the 16-bit rank budget ({RANK_LIMIT})"
            )
        plabels = pack_labels(labels)
        labels = None
    # planner state rides along with the export, like the cached norms:
    # the selectivity estimator is built over the REAL nodes only (padding
    # rows have no rank coordinates) and is rebuilt on every epoch swap.
    # Lazy import: repro.exec sits above the search layer.
    from repro.exec.estimator import SelectivityEstimator

    planner = SelectivityEstimator.from_graph(g, buckets=planner_buckets)
    return DeviceGraph(
        vectors=vectors,
        nbr=nbr,
        labels=labels,
        U_X=g.space.U_X.copy(),
        U_Y=g.space.U_Y.copy(),
        entry_node=ent["entry_node"],
        entry_y_rank=ent["entry_y_rank"],
        relation=g.relation.name,
        norms=norms,
        vec_q=vec_q,
        scales=scales,
        planner=planner,
        plabels=plabels,
    )


class SegmentStack:
    """Flat device-resident concatenation of uniform-capacity segment exports.

    The segmented tier's one-dispatch worklist scheduler
    (``repro.scale.segmented``) executes ANY routed-segment mix through a
    single compiled program by searching one *flat* graph: segment ``i``
    owns rows ``[i·node_capacity, (i+1)·node_capacity)`` of every stacked
    view, and each part's neighbor table is **pre-offset** by that base at
    stack time (``nbr + i·node_capacity`` where real, ``-1`` where
    padding). Pre-offsetting is the whole trick — adjacency is
    segment-closed, so the unmodified batched search core traverses the
    flat graph and every query row stays inside its own segment with zero
    per-row index arithmetic in the inner loop.

    ``gids`` is the device-resident flat-node → global-object id table
    (``-1`` on capacity-padding rows), indexed inside the jitted merge
    fold so the per-segment host-side ``np.where`` remap disappears.

    ``set_segment`` replaces exactly one part and drops only the memoized
    flat concatenations; untouched parts keep the SAME device buffers
    (object identity — pinned by the streaming epoch-swap regression
    test), so a segment-local epoch swap restages one segment, not the
    fleet.
    """

    def __init__(self, *, node_capacity: int, edge_capacity: int):
        self.node_capacity = int(node_capacity)
        self.edge_capacity = int(edge_capacity)
        self._parts: list = []
        self._flat: dict = {}

    @property
    def num_segments(self) -> int:
        return len(self._parts)

    @property
    def packed(self) -> bool:
        from repro.kernels.layout import is_int32_rects

        return bool(self._parts) and not is_int32_rects(
            self._parts[0]["labels"])

    @property
    def quantized(self) -> bool:
        return bool(self._parts) and self._parts[0]["scales"] is not None

    def part(self, i: int) -> dict:
        """Segment ``i``'s device part dict (table/scales/norms/nbr/labels/
        gids) — exposed for the identity assertions in tests."""
        return self._parts[i]

    def _make_part(self, si: int, dg: "DeviceGraph", gids: np.ndarray) -> dict:
        import jax.numpy as jnp

        dev = dg.device()
        ncap, ecap = self.node_capacity, self.edge_capacity
        if dev.table.shape[0] != ncap:
            raise ValueError(
                f"segment export has {dev.table.shape[0]} node rows, "
                f"stack capacity is {ncap}"
            )
        if dev.nbr.shape[1] != ecap:
            raise ValueError(
                f"segment export has edge capacity {dev.nbr.shape[1]}, "
                f"stack capacity is {ecap}"
            )
        if self._parts:
            ref = self._parts[0]
            if (dev.scales is None) != (ref["scales"] is None):
                raise ValueError("mixed quantized/f32 segments in one stack")
            if dev.labels.shape[-1] != ref["labels"].shape[-1]:
                raise ValueError("mixed label layouts in one stack")
        base = jnp.int32(si * ncap)
        nbr = jnp.where(dev.nbr >= 0, dev.nbr + base, jnp.int32(-1))
        g = np.full(ncap, -1, dtype=np.int32)
        gids = np.asarray(gids).reshape(-1)
        g[: gids.shape[0]] = gids.astype(np.int32)
        return {
            "table": dev.table,
            "scales": dev.scales,
            "norms": dev.norms,
            "nbr": nbr,
            "labels": dev.labels,
            "gids": jnp.asarray(g),
        }

    def append_segment(self, dg: "DeviceGraph", gids: np.ndarray) -> None:
        """Append one segment's export as the next leading-axis slice."""
        self._parts.append(self._make_part(len(self._parts), dg, gids))
        self._flat.clear()

    def set_segment(self, i: int, dg: "DeviceGraph", gids: np.ndarray) -> None:
        """Replace segment ``i``'s part (epoch swap); every other part's
        device buffers are untouched — only the flat memos rebuild."""
        self._parts[i] = self._make_part(i, dg, gids)
        self._flat.clear()

    def blank_segment(self, i: int) -> None:
        """Scrub segment ``i``'s slice in place: zeroed table/labels, empty
        adjacency, all gids -1. Quarantine uses this so a poisoned
        segment's rows can never surface — even through a stale route mask,
        a traversal landing here yields gid -1 (dropped at merge) and no
        edges to follow. Shapes and dtypes are unchanged, so downstream
        compiled programs see the same signature (zero recompiles)."""
        import jax.numpy as jnp

        ref = self._parts[i]
        self._parts[i] = {
            "table": jnp.zeros_like(ref["table"]),
            "scales": None if ref["scales"] is None
            else jnp.zeros_like(ref["scales"]),
            "norms": jnp.zeros_like(ref["norms"]),
            "nbr": jnp.full_like(ref["nbr"], -1),
            "labels": jnp.zeros_like(ref["labels"]),
            "gids": jnp.full_like(ref["gids"], -1),
        }
        self._flat.clear()

    def flat(self, key: str):
        """Memoized flat ``[S·node_capacity, ...]`` concatenation of one
        component (``table``/``scales``/``norms``/``nbr``/``labels``/
        ``labels_i32``/``gids``). ``scales`` returns ``None`` on a pure
        f32 stack."""
        out = self._flat.get(key)
        if out is None:
            import jax.numpy as jnp

            from repro.kernels.layout import is_int32_rects

            if key == "labels_i32":
                parts = [
                    p["labels"] if is_int32_rects(p["labels"])
                    else unpack_labels_device(p["labels"], self.edge_capacity)
                    for p in self._parts
                ]
            else:
                parts = [p[key] for p in self._parts]
                if any(v is None for v in parts):
                    return None
            out = jnp.concatenate(parts, axis=0)
            self._flat[key] = out
        return out

    def flat_labels(self, *, fused: bool = True, packed: bool | None = None):
        """Flat label view under the same layout rule as
        ``DeviceGraph.serving_labels`` (packed words when available and the
        caller runs fused; the int32 parity-oracle layout otherwise)."""
        if packed is None:
            packed = self.packed
        elif packed and not self.packed:
            raise ValueError(
                "packed=True but the stack carries no packed labels"
            )
        if fused and packed:
            return self.flat("labels")
        return self.flat("labels_i32") if self.packed else self.flat("labels")

    def nbytes_by_component(self) -> dict:
        """DEVICE bytes per stacked component (the scheduler's resident
        footprint — reported separately from ``SegmentedIndex.nbytes``,
        whose at-rest accounting stays host-side and sums-exact)."""
        out: dict = {}
        for p in self._parts:
            for key in ("table", "scales", "norms", "nbr", "labels", "gids"):
                v = p.get(key)
                if v is not None:
                    out[key] = out.get(key, 0) + int(v.nbytes)
        return out

    def nbytes(self) -> int:
        return sum(self.nbytes_by_component().values())


class BroadExport:
    """Incrementally-maintained *broad* (label-ignoring) device adjacency.

    The batched constructor needs the partially built index on device once
    per insertion wave, but only for the broad construction-time search —
    which ignores labels and collapses multi-tuples. So instead of
    re-running the full :func:`export_device_graph` per wave (O(total
    tuples) every time), this structure maintains the padded dense
    ``[n_pad, E] int32`` unique-neighbor table *incrementally*: each edge
    pair added to the host graph is folded in as it is emitted, and a wave
    export is a zero-copy column slice.

    ``max_width`` bounds the per-row degree: once a row is full, later
    neighbors are dropped. Rows fill in discovery order, so what survives
    is the node's own sweep-time neighborhood (diversity-PRUNEd close
    neighbors) plus the earliest reverse edges — the connectivity-critical
    set, same policy as ``export_device_graph`` under ``edge_capacity``.
    Capping is what keeps the wave search's per-iteration gather narrow as
    hub degrees grow: broad-pool recall is flat down to width ≈ Z while the
    iteration cost scales linearly with width.
    """

    def __init__(
        self,
        n_pad: int,
        *,
        init_degree: int = 64,
        lane: int = 32,
        max_width: int | None = None,
    ):
        self._lane = lane
        self._max_width = None
        if max_width is not None:
            self._max_width = ((int(max_width) + lane - 1) // lane) * lane
        cap = max(int(init_degree), lane)
        if self._max_width is not None:
            cap = min(cap, self._max_width)
        self._nbr = np.full((n_pad, cap), -1, dtype=np.int32)
        self._deg = np.zeros(n_pad, dtype=np.int32)
        self.max_degree = 0

    def _grow(self, need: int) -> None:
        cap = self._nbr.shape[1]
        new_cap = max(need, cap * 2)
        new_cap = ((new_cap + self._lane - 1) // self._lane) * self._lane
        if self._max_width is not None:
            new_cap = min(new_cap, self._max_width)
        if new_cap <= cap:
            return
        grown = np.full((self._nbr.shape[0], new_cap), -1, dtype=np.int32)
        grown[:, :cap] = self._nbr
        self._nbr = grown

    def add_edges(self, u: int, vs: np.ndarray) -> None:
        """Fold the bidirectional pairs (u, v) for v in ``vs`` into the table,
        deduplicating; full rows (``max_width``) drop further neighbors."""
        vs = np.unique(np.asarray(vs, dtype=np.int32))
        vs = vs[vs != u]
        if vs.size == 0:
            return
        du = int(self._deg[u])
        new = vs[~np.isin(vs, self._nbr[u, :du])]
        if new.size == 0:
            return
        if du + new.size > self._nbr.shape[1]:
            self._grow(du + int(new.size))
        space = self._nbr.shape[1] - du
        fwd = new[:space]
        self._nbr[u, du : du + fwd.size] = fwd
        self._deg[u] = du + fwd.size
        self.max_degree = max(self.max_degree, du + int(fwd.size))
        for v in new.tolist():
            dv = int(self._deg[v])
            if dv >= self._nbr.shape[1]:
                self._grow(dv + 1)  # no-op once at max_width
                if dv >= self._nbr.shape[1]:
                    continue  # row full under max_width
            # capping breaks the symmetry invariant, so membership is
            # re-checked (rows are <= max_width wide; O(width) scan)
            if u in self._nbr[v, :dv]:
                continue
            self._nbr[v, dv] = u
            self._deg[v] = dv + 1
            if dv + 1 > self.max_degree:
                self.max_degree = dv + 1

    def export_width(self) -> int:
        """Current lane-aligned export width (bucketed so the wave search
        recompiles only when the max broad degree crosses a lane multiple)."""
        w = max(self.max_degree, 1)
        return ((w + self._lane - 1) // self._lane) * self._lane

    def view(self, width: int | None = None) -> np.ndarray:
        """``[n_pad, width]`` int32 neighbor table (-1 padded), no copy."""
        return self._nbr[:, : (width or self.export_width())]


@dataclasses.dataclass
class DeltaSegment:
    """Statically-shaped device view of the mutable delta tier.

    ``labels`` rectangles are in *monotone float-key space* (see
    ``repro.stream.delta.sort_key``): slot i is active for query key state
    (a, c) iff ``l <= a <= r and b <= c <= e`` with
    ``(l, r, b, e) = (INT32_MIN, key(X_i), key(Y_i), INT32_MAX)`` — exactly
    the predicate ``X_i >= x_q and Y_i <= y_q`` of Eq. (1), evaluated by the
    same fused Pallas ``filter_dist`` kernel as graph-tier edges. Dead /
    unwritten slots have ``slot_ids = -1`` (kernel-masked) and an empty
    rectangle.
    """

    vectors: np.ndarray    # [C, d] f32
    labels: np.ndarray     # [C, 4] int32 key-space rectangles
    slot_ids: np.ndarray   # [C] int32, slot index or -1 = dead
    ext_ids: np.ndarray    # [C] int32 external ids (-1 = dead)

    @property
    def capacity(self) -> int:
        return int(self.vectors.shape[0])

    def nbytes(self) -> int:
        return sum(
            a.nbytes for a in (self.vectors, self.labels, self.slot_ids, self.ext_ids)
        )
