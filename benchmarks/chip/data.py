"""The benchmark's own data and query generators.

Copies of the paper-protocol generators (``repro.data.synthetic``,
``repro.data.workloads``), kept here so that no change to the program can
change the benchmark's inputs. Two departures, both deliberate:

* query vectors come from the data's own mixture (the same centres, fresh
  assignments and noise), so queries lie in the data's distribution;
* ``make_pool`` draws a fixed number of queries per selectivity bucket, so
  every seed serves the same mix of sizes, in another order.

The interval endpoints are quantized to float32-representable values, as in
the original, so a float32 predicate on the device is exact.
"""
from __future__ import annotations

import dataclasses

import numpy as np

T_DOMAIN = 1000.0

# --- raw-interval predicates (paper Table II), written on the intervals ---------

PREDICATES = {
    "containment": lambda s, t, sq, tq: (s >= sq) & (t <= tq),
    "overlap": lambda s, t, sq, tq: (t >= sq) & (s <= tq),
    "query_within_data": lambda s, t, sq, tq: (s <= sq) & (t >= tq),
    "both_after": lambda s, t, sq, tq: (s >= sq) & (t >= tq),
    "both_before": lambda s, t, sq, tq: (s <= sq) & (t <= tq),
}

# (data -> X, Y) and the inverse query map (x_q, y_q) -> (s_q, t_q) that put
# each relation into the dominance form X >= x_q and Y <= y_q
_DOMINANCE = {
    "containment": (lambda s, t: (s, t), lambda x, y: (x, y)),
    "overlap": (lambda s, t: (t, s), lambda x, y: (x, y)),
    "query_within_data": (lambda s, t: (t, s), lambda x, y: (y, x)),
    "both_after": (lambda s, t: (s, -t), lambda x, y: (x, -y)),
    "both_before": (lambda s, t: (-s, t), lambda x, y: (-x, y)),
}


def predicate(relation: str):
    try:
        return PREDICATES[relation]
    except KeyError:
        raise KeyError(f"unknown relation {relation!r}; "
                       f"known: {sorted(PREDICATES)}") from None


# --- vectors ----------------------------------------------------------------------


def make_vectors_and_queries(n: int, nq: int, dim: int, *, clusters: int,
                             spread: float, seed: int):
    """``n`` Gaussian-mixture data vectors (exactly those of
    ``repro.data.synthetic.make_vectors(n, dim, seed=seed)``) and ``nq``
    query vectors drawn from the same centres with a stream of their own."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(clusters, dim))
    asg = rng.integers(0, clusters, size=n)
    x = centers[asg] + spread * rng.normal(size=(n, dim))
    qrng = np.random.default_rng([seed, 1])
    qa = qrng.integers(0, clusters, size=nq)
    q = centers[qa] + spread * qrng.normal(size=(nq, dim))
    return (np.ascontiguousarray(x, dtype=np.float32),
            np.ascontiguousarray(q, dtype=np.float32))


# --- intervals --------------------------------------------------------------------


def _uniform(rng, n, T):
    """Length ~ U(0, 0.01 T), start uniform over the feasible range."""
    ln = rng.uniform(0.0, 0.01 * T, size=n)
    s = rng.uniform(0.0, T - ln)
    return s, s + ln


def _uncapped(rng, n, T):
    """Heavy-tailed lognormal lengths with no cap (paper Fig. 4a)."""
    ln = np.minimum(T * rng.lognormal(mean=-4.5, sigma=1.6, size=n), T)
    s = rng.uniform(0.0, np.maximum(T - ln, 1e-9))
    return s, np.minimum(s + ln, T)


INTERVALS = {"uniform": _uniform, "uncapped": _uncapped}


def _clamp_ordered(s, t):
    lo = np.minimum(s, t)
    bad = s > t
    return np.where(bad, lo, s), np.where(bad, lo, t)


def make_intervals(n: int, distribution: str, *, seed: int, T: float = T_DOMAIN):
    """``n`` closed intervals, as ``repro.data.synthetic.make_intervals``:
    endpoints rounded to float32 values, spans that rounding reversed
    clamped to zero length. Returns float64 ``(s, t)``."""
    rng = np.random.default_rng(seed + 7919)
    s, t = INTERVALS[distribution](rng, n, T)
    s, t = _clamp_ordered(np.asarray(s, np.float64), np.asarray(t, np.float64))
    s = s.astype(np.float32).astype(np.float64)
    t = t.astype(np.float32).astype(np.float64)
    return _clamp_ordered(s, t)


# --- query intervals at an exact selectivity ---------------------------------------


def query_intervals(s, t, relation: str, selectivity: float, count: int, *,
                    k: int, seed: int, max_tries: int = 200):
    """``count`` query intervals that each select about ``selectivity·n``
    objects (at least ``k``), by the exact-count construction of
    ``repro.data.workloads.generate_queries``: pick a data X as ``x_q``,
    set ``y_q`` to the m-th smallest Y of the X-suffix, map back to an
    interval. Returns ``(s_q, t_q, valid_count)``."""
    to_xy, from_xy = _DOMINANCE[relation]
    pred = predicate(relation)
    X, Y = to_xy(s, t)
    n = X.shape[0]
    m = max(int(round(selectivity * n)), k)
    hi = n - m
    if hi < 0:
        raise RuntimeError(f"selectivity {selectivity} needs m={m} > n={n}")
    order = np.argsort(X, kind="stable")
    x_sorted, y_by_x = X[order], Y[order]
    rng = np.random.default_rng(seed + 104729)

    def attempt(pos):
        x_q = float(x_sorted[pos])
        lo = int(np.searchsorted(x_sorted, x_q, side="left"))
        suffix = y_by_x[lo:]
        if suffix.shape[0] < m:
            return None
        y_q = float(np.partition(suffix, m - 1)[m - 1])
        s_q, t_q = from_xy(x_q, y_q)
        if s_q > t_q:
            return None
        cnt = int(np.count_nonzero(pred(s, t, s_q, t_q)))
        if cnt < k:
            return None
        return float(s_q), float(t_q), cnt

    grid = np.unique(np.linspace(0, hi, num=min(hi + 1, 128)).astype(np.int64))
    feasible = [int(p) for p in grid if attempt(int(p)) is not None]
    if not feasible:
        raise RuntimeError(f"no feasible {relation} query at selectivity "
                           f"{selectivity} (n={n})")
    step = max(1, (hi + 1) // max(len(grid) - 1, 1))
    out = []
    for _ in range(count):
        res = None
        for _try in range(max_tries):
            base = feasible[int(rng.integers(0, len(feasible)))]
            res = attempt(int(np.clip(base + rng.integers(-step, step + 1),
                                      0, hi)))
            if res is not None:
                break
        if res is None:
            res = attempt(feasible[int(rng.integers(0, len(feasible)))])
        out.append(res)
    s_q, t_q, cnt = (np.asarray(c) for c in zip(*out))
    return s_q.astype(np.float64), t_q.astype(np.float64), cnt.astype(np.int64)


@dataclasses.dataclass
class Pool:
    """The queries a run draws from: vectors, intervals, the selectivity
    bucket each was made for and its exact number of valid objects."""

    q: np.ndarray          # [P, d] float32
    s_q: np.ndarray        # [P] float64
    t_q: np.ndarray        # [P] float64
    bucket: np.ndarray     # [P] selectivity asked for
    valid: np.ndarray      # [P] objects that satisfy the predicate

    def __len__(self) -> int:
        return int(self.s_q.shape[0])


def make_pool(qv: np.ndarray, s, t, relation: str, selectivities, *, k: int,
              seed: int) -> Pool:
    """One query interval per row of ``qv``: the rows are dealt round-robin
    to the selectivity buckets (equal counts, to within one), each bucket's
    intervals made by :func:`query_intervals` from its own seed, and the
    rows then shuffled by the seed."""
    P = qv.shape[0]
    which = np.arange(P) % len(selectivities)
    s_q, t_q = np.zeros(P), np.zeros(P)
    bucket, valid = np.zeros(P), np.zeros(P, np.int64)
    for b, sel in enumerate(selectivities):
        rows = np.flatnonzero(which == b)
        s_q[rows], t_q[rows], valid[rows] = query_intervals(
            s, t, relation, sel, rows.size, k=k, seed=seed + 1000 * b)
        bucket[rows] = sel
    perm = np.random.default_rng([seed, 2]).permutation(P)
    return Pool(qv, s_q[perm], t_q[perm], bucket[perm], valid[perm])
