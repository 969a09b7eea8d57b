"""Per-kernel allclose sweeps: Pallas (interpret=True on CPU) vs pure-jnp
oracles, across shapes and dtypes."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.layout import label_rows, table_rows

RNG = np.random.default_rng(0)


def _arr(shape, dtype=np.float32):
    return jnp.asarray(RNG.normal(size=shape).astype(dtype))


@pytest.mark.parametrize("bq,bc,d", [
    (1, 1, 4), (7, 33, 16), (128, 128, 64), (37, 215, 70), (130, 50, 200),
])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_l2dist_matches_ref(bq, bc, d, dtype):
    q = _arr((bq, d), dtype)
    c = _arr((bc, d), dtype)
    got = ops.l2dist(q, c)
    want = ref.l2dist_ref(q, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_l2dist_is_true_squared_distance():
    q = _arr((5, 12))
    c = _arr((9, 12))
    got = np.asarray(ops.l2dist(q, c))
    brute = np.sum(
        (np.asarray(q)[:, None, :] - np.asarray(c)[None, :, :]) ** 2, axis=-1
    )
    np.testing.assert_allclose(got, brute, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,e,d", [(1, 1, 4), (3, 17, 8), (8, 128, 32), (5, 200, 64)])
def test_filter_dist_matches_ref(b, e, d):
    q = _arr((b, d))
    cand = _arr((b, e, d))
    labels = jnp.asarray(RNG.integers(0, 12, size=(b, e, 4)).astype(np.int32))
    state = jnp.asarray(RNG.integers(0, 12, size=(b, 2)).astype(np.int32))
    ids = jnp.asarray(RNG.integers(-1, 40, size=(b, e)).astype(np.int32))
    got = np.asarray(ops.filter_dist(q, cand, labels, state, ids))
    want = np.asarray(ref.filter_dist_ref(q, cand, labels, state, ids))
    fin = np.isfinite(want)
    np.testing.assert_array_equal(np.isfinite(got), fin)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-4, atol=1e-4)


def test_filter_dist_label_semantics():
    """a in [l, r] and c in [b, e] — closed on both ends (paper §IV-A)."""
    q = jnp.zeros((1, 4))
    cand = jnp.ones((1, 3, 4))
    #               active       a==r boundary   b > c (inactive)
    labels = jnp.asarray([[[0, 5, 0, 5], [2, 2, 0, 5], [0, 5, 3, 5]]], dtype=jnp.int32)
    state = jnp.asarray([[2, 2]], dtype=jnp.int32)
    ids = jnp.asarray([[0, 1, 2]], dtype=jnp.int32)
    out = np.asarray(ops.filter_dist(q, cand, labels, state, ids))
    assert np.isfinite(out[0, 0]) and np.isfinite(out[0, 1])
    assert np.isinf(out[0, 2])


def _gather_case(n, b, c, d, seed=0):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    norms = jnp.sum(table * table, axis=1)
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    ids = jnp.asarray(rng.integers(-1, n, size=(b, c)).astype(np.int32))
    labels = jnp.asarray(rng.integers(0, 12, size=(b, c, 4)).astype(np.int32))
    state = jnp.asarray(rng.integers(0, 12, size=(b, 2)).astype(np.int32))
    W = (n + 31) // 32
    vis = jnp.asarray(
        rng.integers(0, 2 ** 32, size=(b, W), dtype=np.uint64).astype(np.uint32)
    )
    return table, norms, q, ids, labels, state, vis


@pytest.mark.parametrize("n,b,c,d", [
    (33, 1, 5, 4),        # B=1, n not a multiple of 32 (bitmap tail word)
    (100, 3, 24, 7),      # odd D
    (200, 4, 130, 16),    # C not a multiple of the tile
    (513, 2, 260, 32),    # multi-tile with n % 32 != 0
])
@pytest.mark.parametrize("rows", [False, True])
def test_filter_dist_gather_matches_ref(n, b, c, d, rows):
    """Kernel vs oracle, given the logical ``[n, d]`` table or the
    ``[n, 1, W]`` rows the kernel DMAs from (``repro.kernels.layout``)."""
    table, norms, q, ids, labels, state, vis = _gather_case(n, b, c, d)
    if rows:
        table = table_rows(table)
    got = np.asarray(
        ops.filter_dist_gather(table, norms, q, ids, labels, state, vis,
                               use_ref=False)[0]
    )
    want = np.asarray(
        ops.filter_dist_gather(table, norms, q, ids, labels, state, vis,
                               use_ref=True)[0]
    )
    fin = np.isfinite(want)
    np.testing.assert_array_equal(np.isfinite(got), fin)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-4, atol=1e-4)


def test_filter_dist_gather_small_tile_boundaries():
    """Direct kernel call with te=8: 3 tiles + padded tail exercises the
    double-buffered DMA pipeline across tile steps."""
    from repro.kernels.filter_dist import filter_dist_gather_pallas

    n, b, c, d = 75, 2, 20, 12
    table, norms, q, ids, labels, state, vis = _gather_case(n, b, c, d, seed=5)
    safe = jnp.clip(ids, 0, n - 1)
    g_norms = norms[safe]
    g_words = jnp.take_along_axis(vis, safe >> 5, axis=1)
    g_scales = jnp.ones_like(g_norms)
    got = np.asarray(filter_dist_gather_pallas(
        table, q, ids, labels, state, g_norms, g_words, g_scales,
        interpret=True, te=8,
    ))
    want = np.asarray(
        ops.filter_dist_gather(table, norms, q, ids, labels, state, vis,
                               use_ref=True)[0]
    )
    fin = np.isfinite(want)
    np.testing.assert_array_equal(np.isfinite(got), fin)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-4, atol=1e-4)


def test_filter_dist_gather_all_invalid_tile():
    """A tile of nothing but -1 padding must come back all +inf (a dead
    tile: it fetches no row and skips its compute)."""
    n, b, c, d = 64, 2, 16, 8
    table, norms, q, ids, labels, state, vis = _gather_case(n, b, c, d, seed=7)
    ids = jnp.full((b, c), -1, jnp.int32)
    got = np.asarray(
        ops.filter_dist_gather(table, norms, q, ids, labels, state, vis)[0]
    )
    assert np.all(np.isinf(got))


def test_filter_dist_gather_visited_bitmap_semantics():
    """Bit i>>5 : i&31 set => candidate i suppressed; includes the tail word
    of an n that is not a multiple of 32."""
    n, d = 45, 8            # words: [32, 13-bit tail]
    table = jnp.asarray(RNG.normal(size=(n, d)).astype(np.float32))
    norms = jnp.sum(table * table, axis=1)
    q = jnp.zeros((1, d), jnp.float32)
    ids = jnp.asarray([[3, 31, 32, 44]], dtype=jnp.int32)
    labels = jnp.zeros((1, 4, 4), jnp.int32)
    labels = labels.at[..., 1].set(10).at[..., 3].set(10)   # wide-open rects
    state = jnp.asarray([[5, 5]], jnp.int32)
    vis = np.zeros((1, 2), np.uint32)
    vis[0, 0] = (np.uint32(1) << 31) | np.uint32(1 << 3)    # marks 31 and 3
    vis[0, 1] = np.uint32(1 << (44 - 32))                   # marks 44 (tail)
    for use_ref in (True, False):
        out = np.asarray(ops.filter_dist_gather(
            table, norms, q, ids, labels, state, jnp.asarray(vis),
            use_ref=use_ref,
        )[0])
        assert np.isinf(out[0, 0]) and np.isinf(out[0, 1])   # 3, 31 visited
        assert np.isfinite(out[0, 2])                        # 32 clear
        assert np.isinf(out[0, 3])                           # 44 visited


@pytest.mark.slow
def test_filter_dist_gather_exhaustive_sweep():
    """Randomized shape sweep (marked slow): every combination of B=1/odd
    D/tile-straddling C/bitmap-tail n across several seeds."""
    cases = [
        (n, b, c, d, seed)
        for n in (31, 64, 257)
        for b in (1, 5)
        for c in (3, 129)
        for d in (6, 32)
        for seed in (0, 1)
    ]
    for n, b, c, d, seed in cases:
        table, norms, q, ids, labels, state, vis = _gather_case(n, b, c, d, seed)
        got = np.asarray(
            ops.filter_dist_gather(table, norms, q, ids, labels, state, vis)[0]
        )
        want = np.asarray(
            ops.filter_dist_gather(table, norms, q, ids, labels, state, vis,
                                   use_ref=True)[0]
        )
        fin = np.isfinite(want)
        np.testing.assert_array_equal(np.isfinite(got), fin, err_msg=str((n, b, c, d)))
        np.testing.assert_allclose(got[fin], want[fin], rtol=1e-4, atol=1e-4,
                                   err_msg=str((n, b, c, d)))


@pytest.mark.parametrize("rows", [False, True])
def test_filter_dist_gather_int8_scales(rows):
    """int8 storage: as a logical table, or as the int32 words (four int8
    values each) the kernel unpacks."""
    n, b, c, d = 90, 3, 33, 16
    table, _, q, ids, labels, state, vis = _gather_case(n, b, c, d, seed=9)
    tq, sc = ops.quantize_int8(table)
    deq = tq.astype(jnp.float32) * sc[:, None]
    norms = jnp.sum(deq * deq, axis=1)
    if rows:
        tq = table_rows(tq)
    got = np.asarray(ops.filter_dist_gather(
        tq, norms, q, ids, labels, state, vis, scales=sc, use_ref=False,
    )[0])
    want = np.asarray(ops.filter_dist_gather(
        tq, norms, q, ids, labels, state, vis, scales=sc, use_ref=True,
    )[0])
    fin = np.isfinite(want)
    np.testing.assert_array_equal(np.isfinite(got), fin)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-3, atol=1e-3)


def _gated_table(n, b, d, int8, rng):
    """(table, norms, scales, q): f32, or int8 with dequantized norms."""
    table = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    if not int8:
        return table, jnp.sum(table * table, axis=1), None, q
    tq, sc = ops.quantize_int8(table)
    deq = tq.astype(jnp.float32) * sc[:, None]
    return tq, jnp.sum(deq * deq, axis=1), sc, q


def _gated_tiles(pattern, b, te, n, rng):
    """Candidate ids ``[b, len(pattern) // b · te]``, the visited bitmap and
    a ``[b, C]`` label-pass mask, one tile of ``te`` per letter, in grid
    order: ``M`` mixed (some padding, half visited, half failing the
    labels), ``P`` padding only, ``S`` visited only, ``L`` failing the
    labels only, ``D`` like ``M`` (its expanded node is made ``-1``)."""
    R = len(pattern)
    ids = rng.integers(0, n, size=(R, te)).astype(np.int32)
    seen = np.zeros((R, te), bool)
    passes = np.ones((R, te), bool)
    for r, kind in enumerate(pattern):
        if kind in "MD":
            ids[r, rng.random(te) < 0.25] = -1
            seen[r] = rng.random(te) < 0.5
            passes[r] = rng.random(te) < 0.5
        elif kind == "P":
            ids[r] = -1
        elif kind == "S":
            seen[r] = True
        elif kind == "L":
            passes[r] = False
    ids = ids.reshape(b, -1)
    vis = np.zeros((b, (n + 31) // 32), np.uint32)
    for i, j in zip(*np.nonzero(seen.reshape(b, -1) & (ids >= 0))):
        vis[i, ids[i, j] >> 5] |= np.uint32(1) << np.uint32(ids[i, j] & 31)
    return ids, vis, passes.reshape(b, -1)


def _bit_set(vis, ids):
    safe = np.maximum(ids, 0)
    word = np.take_along_axis(vis, safe >> 5, axis=1)
    return ((word >> (safe & 31).astype(np.uint32)) & 1) == 1


@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("pattern", ["PMMMMP", "PMPMPM", "SLMSLM", "PPPPPP"])
def test_filter_dist_gather_gated_tiles(pattern, int8):
    """The gather kernel fetches only rows that can still pass (not
    padding, not visited, passing the labels), with te=8 so that tiles of
    nothing to fetch come first, last and alternate with live ones through
    the double buffer. It equals its oracle on which lanes are finite, and
    each finite lane is bitwise what a call that fetches every row gives.
    ``fetched`` counts the rows that can pass."""
    from repro.kernels.filter_dist import filter_dist_gather_pallas

    rng = np.random.default_rng(len(pattern) + 7 * int8)
    n, b, te, d = 75, 2, 8, 12
    table, norms, scales, q = _gated_table(n, b, d, int8, rng)
    ids, vis, passes = _gated_tiles(pattern, b, te, n, rng)
    state = jnp.full((b, 2), 5, jnp.int32)
    labels = np.zeros(ids.shape + (4,), np.int32)
    labels[..., 1] = labels[..., 3] = 10                 # (0, 10, 0, 10)
    labels[..., 0] = np.where(passes, 0, 6)              # l > a fails
    ids, labels, vis = map(jnp.asarray, (ids, labels, vis))

    def kernel(ids, vis):
        safe = jnp.clip(ids, 0, n - 1)
        return np.asarray(filter_dist_gather_pallas(
            table, q, ids, labels, state, norms[safe],
            jnp.take_along_axis(vis, safe >> 5, axis=1),
            scales[safe] if int8 else jnp.ones(ids.shape, jnp.float32),
            interpret=True, te=te))

    want, fetched = ops.filter_dist_gather(
        table, norms, q, ids, labels, state, vis, scales=scales,
        use_ref=True)
    want = np.asarray(want)
    got = kernel(ids, vis)
    every_row = kernel(ids, jnp.zeros_like(vis))
    np.testing.assert_array_equal(
        got, np.where(np.isfinite(want), every_row, np.inf))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
    can_pass = ((np.asarray(ids) >= 0) & ~_bit_set(np.asarray(vis), ids)
                & passes)
    assert int(fetched) == int(can_pass.sum())


def _packed_case(n, b, m, e, d, seed=0, rank_hi=12):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    norms = jnp.sum(table * table, axis=1)
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    cur = jnp.asarray(rng.integers(0, n, size=(b, m)).astype(np.int32))
    cand = jnp.asarray(rng.integers(-1, n, size=(b, m * e)).astype(np.int32))
    lo = rng.integers(0, rank_hi, size=(n, e, 2)).astype(np.uint32)
    hi = rng.integers(0, rank_hi, size=(n, e, 2)).astype(np.uint32)
    plabels = jnp.asarray(lo | (hi << 16))
    state = jnp.asarray(rng.integers(0, rank_hi, size=(b, 2)).astype(np.int32))
    W = (n + 31) // 32
    vis = jnp.asarray(
        rng.integers(0, 2 ** 32, size=(b, W), dtype=np.uint64).astype(np.uint32)
    )
    return table, plabels, norms, q, cur, cand, state, vis


@pytest.mark.parametrize("n,b,m,e,d", [
    (33, 1, 1, 5, 4),       # B=1, bitmap tail word
    (100, 3, 2, 12, 7),     # odd D, multi-expand label rows
    (200, 4, 1, 130, 16),   # M*E not a multiple of the tile
    (257, 2, 4, 65, 32),    # wide multi-expand straddling tiles
    (300, 2, 2, 96, 24),    # deployment degree: word 1 straddles a lane tile
])
@pytest.mark.parametrize("rows", [False, True])
def test_filter_dist_gather_packed_matches_ref(n, b, m, e, d, rows):
    """The packed superkernel (in-kernel label-row DMA + mask-and-shift
    dominance test) matches its jnp oracle across tile/expand shapes,
    given the logical tables or the row layouts the kernel DMAs from."""
    args = _packed_case(n, b, m, e, d)
    if rows:
        args = (table_rows(args[0]), label_rows(args[1])) + args[2:]
    got = np.asarray(ops.filter_dist_gather_packed(*args, use_ref=False)[0])
    want = np.asarray(ops.filter_dist_gather_packed(*args, use_ref=True)[0])
    fin = np.isfinite(want)
    np.testing.assert_array_equal(np.isfinite(got), fin)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-4, atol=1e-4)


def test_filter_dist_gather_packed_matches_int32_kernel():
    """Packed words and the int32 rectangles encode the same test: the
    packed superkernel agrees with the int32 gather kernel given the
    unpacked layout of the same labels."""
    from repro.search.device_graph import unpack_labels

    n, b, m, e, d = 90, 3, 2, 11, 8
    table, plabels, norms, q, cur, cand, state, vis = _packed_case(
        n, b, m, e, d, seed=3)
    got = np.asarray(ops.filter_dist_gather_packed(
        table, plabels, norms, q, cur, cand, state, vis)[0])
    lab4 = jnp.asarray(unpack_labels(np.asarray(plabels)))
    lab_g = lab4[jnp.clip(cur, 0, n - 1)].reshape(b, m * e, 4)
    want = np.asarray(ops.filter_dist_gather(
        table, norms, q, cand, lab_g, state, vis)[0])
    fin = np.isfinite(want)
    np.testing.assert_array_equal(np.isfinite(got), fin)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("pattern", ["DMMMMD", "DMDMDM", "MDMDMD", "PSMPSM",
                                     "DDDDDD"])
def test_filter_dist_gather_packed_gated_tiles(pattern, int8):
    """The packed kernel with dead tiles (a ``-1`` expanded node, ``D``)
    first, last, on either slot parity and everywhere, and tiles of only
    padding (``P``) or only visited candidates (``S``), which fetch no row
    and become dead too. It equals its oracle on which lanes are finite,
    each finite lane is bitwise what a call that fetches every row gives,
    and ``fetched`` counts the rows of live tiles that are neither padding
    nor visited."""
    from repro.search.device_graph import pack_labels

    rng = np.random.default_rng(len(pattern) + 7 * int8)
    n, b, m, e, d = 90, 3, 2, 16, 12
    table, norms, scales, q = _gated_table(n, b, d, int8, rng)
    ids, vis, _ = _gated_tiles(pattern, b, e, n, rng)
    cur = rng.integers(0, n, size=(b * m,)).astype(np.int32)
    cur[[kind == "D" for kind in pattern]] = -1
    cur = cur.reshape(b, m)
    lab4 = np.zeros((n, e, 4), np.int32)
    lab4[..., 1] = lab4[..., 3] = 10
    lab4[..., 0] = np.where(rng.random((n, e)) < 0.5, 0, 6)
    plabels = jnp.asarray(pack_labels(lab4))
    state = jnp.full((b, 2), 5, jnp.int32)
    args = (table, plabels, norms, q)

    def call(cur, vis, use_ref):
        out, fetched = ops.filter_dist_gather_packed(
            *args, jnp.asarray(cur), jnp.asarray(ids), state,
            jnp.asarray(vis), scales=scales, use_ref=use_ref)
        return np.asarray(out), int(fetched)

    want, fetched = call(cur, vis, True)
    got, fetched_kernel = call(cur, vis, False)
    every_row, _ = call(np.maximum(cur, 0), np.zeros_like(vis), False)
    assert np.all(np.isinf(want.reshape(b * m, e)[cur.reshape(-1) < 0]))
    np.testing.assert_array_equal(
        got, np.where(np.isfinite(want), every_row, np.inf))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
    live = np.repeat(cur >= 0, e, axis=1)
    assert fetched == fetched_kernel == int(
        ((ids >= 0) & ~_bit_set(vis, ids) & live).sum())


def test_gather_fetched_hand_counted():
    """``fetched`` on a call small enough to count by hand: B = 2, M = 2,
    E = 4; visited rows 3 and 9 of query 0 and row 5 of query 1."""
    n, d = 16, 4
    table = jnp.asarray(RNG.normal(size=(n, d)).astype(np.float32))
    norms = jnp.sum(table * table, axis=1)
    q = jnp.zeros((2, d), jnp.float32)
    vis = np.zeros((2, 1), np.uint32)
    vis[0, 0] = (1 << 3) | (1 << 9)
    vis[1, 0] = 1 << 5
    # per tile, the rows fetched: 4 | 1, 2 || 6 | none (a dead tile)
    cand = jnp.asarray([[3, 4, -1, 9, 1, 2, 3, -1],
                        [5, 5, 6, -1, 7, 8, 9, 10]], jnp.int32)
    cur = jnp.asarray([[0, 1], [2, -1]], jnp.int32)
    plabels = jnp.zeros((n, 4, 2), jnp.uint32)
    state = jnp.zeros((2, 2), jnp.int32)
    for use_ref in (True, False):
        _, fetched = ops.filter_dist_gather_packed(
            table, plabels, norms, q, cur, cand, state, jnp.asarray(vis),
            use_ref=use_ref)
        assert int(fetched) == 4
    # the int32 kernel has no expanded nodes, and skips a candidate that
    # fails its labels: query 1's 6 (l = 1 > a = 0). Fetched: 4, 1, 2 ||
    # 7, 8, 9, 10
    labels = np.zeros((2, 8, 4), np.int32)
    labels[1, 2, 0] = 1
    for use_ref in (True, False):
        _, fetched = ops.filter_dist_gather(
            table, norms, q, cand, jnp.asarray(labels), state,
            jnp.asarray(vis), use_ref=use_ref)
        assert int(fetched) == 7


def test_packed_label_semantics_boundaries():
    """Closed rectangle bounds survive the 16-bit packing: a == r and the
    b > c inactive case behave exactly as the int32 label test."""
    from repro.search.device_graph import pack_labels

    n, d = 8, 4
    table = jnp.zeros((n, d), jnp.float32)
    norms = jnp.zeros((n,), jnp.float32)
    q = jnp.zeros((1, d), jnp.float32)
    #            active        a==r boundary   b > c (inactive)
    lab4 = np.array([[[0, 5, 0, 5], [2, 2, 0, 5], [0, 5, 3, 5]]], np.int32)
    plabels = jnp.asarray(np.broadcast_to(pack_labels(lab4[0])[None], (n, 3, 2)))
    cur = jnp.zeros((1, 1), jnp.int32)
    cand = jnp.asarray([[0, 1, 2]], dtype=jnp.int32)
    state = jnp.asarray([[2, 2]], jnp.int32)
    vis = jnp.zeros((1, 1), jnp.uint32)
    for use_ref in (True, False):
        out = np.asarray(ops.filter_dist_gather_packed(
            table, plabels, norms, q, cur, cand, state, vis, use_ref=use_ref)[0])
        assert np.isfinite(out[0, 0]) and np.isfinite(out[0, 1])
        assert np.isinf(out[0, 2])


def _merge_case(b, l, c, n, seed=0, tie_heavy=False, all_inf=False):
    rng = np.random.default_rng(seed)
    beam_d = np.sort(rng.normal(size=(b, l)).astype(np.float32) ** 2, axis=1)
    ninf = int(rng.integers(0, max(l // 2, 1)))
    if ninf:
        beam_d[:, l - ninf:] = np.inf
    beam_ids = rng.integers(-1, n, size=(b, l)).astype(np.int32)
    beam_ids[~np.isfinite(beam_d)] = -1
    beam_exp = rng.random((b, l)) < 0.5
    if tie_heavy:
        # few distinct distances + few distinct ids: every tie-break and
        # duplicate rule is exercised
        cand_d = rng.integers(0, 4, size=(b, c)).astype(np.float32)
        cand_ids = rng.integers(0, min(8, n), size=(b, c)).astype(np.int32)
        beam_d = np.sort(
            rng.integers(0, 4, size=(b, l)).astype(np.float32), axis=1)
    else:
        cand_d = rng.normal(size=(b, c)).astype(np.float32) ** 2
        cand_ids = rng.integers(-1, n, size=(b, c)).astype(np.int32)
    cand_d[rng.random((b, c)) < 0.3] = np.inf
    if all_inf:
        cand_d[:] = np.inf
        cand_ids[:] = -1
    return tuple(map(jnp.asarray,
                     (beam_d, beam_ids, beam_exp, cand_d, cand_ids)))


@pytest.mark.parametrize("b,l,c,n,tie,all_inf", [
    (3, 64, 88, 4000, False, False),   # bench shape
    (2, 48, 17, 100, False, False),    # L and C not powers of two
    (1, 7, 3, 10, True, False),        # tiny, tie-heavy
    (2, 32, 40, 40, True, False),      # heavy duplicate ids + tied dists
    (2, 16, 8, 50, False, True),       # all-inf candidate set
    (2, 96, 352, 65000, False, False), # wide-beam / multi-expand scale
    (2, 64, 128, 1000, False, False),  # power-of-two C (no padding rows)
    (1, 64, 96, 65536, True, False),   # B=1 at the deployment's L and E
])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_beam_merge_matches_stable_sort_oracle(b, l, c, n, tie, all_inf, seed):
    """Both beam_merge implementations — the jnp top_k path and the Pallas
    bitonic sort+merge network (interpret) — are bitwise equal to the
    stable lax.sort oracle, including exact distance ties, duplicate ids,
    all-inf candidates, and non-power-of-two L / M·E."""
    from repro.kernels.beam_merge import beam_merge_jnp, beam_merge_pallas

    case = _merge_case(b, l, c, n, seed, tie, all_inf)
    want = ref.beam_merge_ref(*case, n=n)
    got_jnp = beam_merge_jnp(*case, n=n)
    got_pl = beam_merge_pallas(*case, n=n, interpret=True)
    names = ("ids", "d", "exp", "keep")
    for g, w, nm in zip(got_jnp, want, names):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"jnp {nm}")
    for g, w, nm in zip(got_pl, want, names):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"pallas {nm}")


def test_beam_merge_dedup_keeps_first_and_marks_bits():
    """Duplicate ids: exactly the first finite occurrence survives (keep
    bit set), later copies are suppressed, and the merged beam holds the
    id once."""
    beam_d = jnp.asarray([[1.0, jnp.inf]])
    beam_ids = jnp.asarray([[7, -1]], dtype=jnp.int32)
    beam_exp = jnp.asarray([[True, False]])
    cand_d = jnp.asarray([[0.5, 0.5, 2.0, jnp.inf]])
    cand_ids = jnp.asarray([[3, 3, 3, 3]], dtype=jnp.int32)
    ids, d, exp, keep = ops.beam_merge(
        beam_d, beam_ids, beam_exp, cand_d, cand_ids, n=10, use_ref=True)
    np.testing.assert_array_equal(np.asarray(keep), [[True, False, False, False]])
    np.testing.assert_array_equal(np.asarray(ids), [[3, 7]])
    np.testing.assert_array_equal(np.asarray(d), [[0.5, 1.0]])
    np.testing.assert_array_equal(np.asarray(exp), [[False, True]])


@pytest.mark.parametrize("bq,bc,d", [(4, 9, 8), (65, 200, 48)])
def test_int8dist_matches_ref_and_f32(bq, bc, d):
    q = _arr((bq, d))
    c = _arr((bc, d))
    cq, cs = ops.quantize_int8(c)
    got = np.asarray(ops.int8_l2dist(q, cq, cs))
    want = np.asarray(ref.int8_l2dist_ref(q, cq, cs))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
    # quantization error vs exact f32 distances stays small & relative
    exact = np.asarray(ref.l2dist_ref(q, c))
    rel = np.abs(got - exact) / np.maximum(exact, 1e-3)
    assert np.median(rel) < 0.05


def test_quantize_int8_bounds():
    v = _arr((20, 16))
    q, scale = ops.quantize_int8(v)
    assert q.dtype == jnp.int8
    recon = np.asarray(q, dtype=np.float32) * np.asarray(scale)[:, None]
    err = np.max(np.abs(recon - np.asarray(v)))
    assert err <= np.max(np.abs(np.asarray(v))) / 127.0 * 0.51 + 1e-6
