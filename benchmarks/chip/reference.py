"""The plain reference and the comparison that decides ``correct``.

The reference is an exact predicate-filtered top-k in plain ``jax.numpy``:
every object is scored against every query, objects that fail the interval
predicate (written on the raw intervals, :data:`data.PREDICATES`) are
dropped, and the k nearest remain. It imports nothing of the program and
takes nothing the program made; it runs on the device in blocks of
queries, after the measured window.

``precision="highest"`` is the reference: float32 matrix products at
``Precision.HIGHEST``. ``precision="bfloat16"`` is the control: the same
computation with the vectors rounded to bfloat16 (float32 accumulation),
the step below the float32 that the configurations state.

:func:`compare` holds every answer the timed path produced against the
reference, and returns the numbers that decide ``correct``:

* ``missing``  requests that never got an answer (limit 0);
* ``bad``      answers that are malformed: an id out of range or repeated,
               an object that fails the predicate, padding before a real
               id, a non-finite distance, or distances out of order
               (limit 0);
* ``dist_err`` the widest relative gap between a distance the program
               reported and the reference's float32 distance of that
               object to that query (limit from the configuration).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.data import predicate

PRECISIONS = ("highest", "bfloat16")


def _f32_exact(a: np.ndarray, what: str) -> np.ndarray:
    """``a`` as float32, refusing values that float32 would round: the
    predicate runs in float32 on the device and must be exact."""
    a = np.asarray(a, dtype=np.float64)
    b = a.astype(np.float32)
    if not np.array_equal(b.astype(np.float64), a):
        raise ValueError(f"{what}: endpoints are not float32 values")
    return b


@functools.partial(jax.jit, static_argnames=("relation", "k", "precision"))
def _block_topk(x, xn, s, t, q, s_q, t_q, *, relation, k, precision):
    if precision == "highest":
        cross = jax.lax.dot_general(
            q, x, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        qn = jnp.sum(q * q, axis=1)
    else:
        qb = q.astype(jnp.bfloat16)
        cross = jax.lax.dot_general(
            qb, x.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        qf = qb.astype(jnp.float32)
        qn = jnp.sum(qf * qf, axis=1)
    d = xn[None, :] - 2.0 * cross + qn[:, None]
    ok = predicate(relation)(s[None, :], t[None, :], s_q[:, None], t_q[:, None])
    neg, ids = jax.lax.top_k(jnp.where(ok, -d, -jnp.inf), k)
    found = jnp.isfinite(neg)
    return jnp.where(found, ids, -1), jnp.where(found, -neg, jnp.inf)


@jax.jit
def _pair_dist(x, q, ids):
    """Float32 squared distance of each ``ids[a, j]`` to ``q[a]``, by the
    difference (no cancellation), -1 ids read as +inf."""
    g = x[jnp.clip(ids, 0, x.shape[0] - 1)]
    d = jnp.sum((g - q[:, None, :]) ** 2, axis=-1)
    return jnp.where(ids >= 0, d, jnp.inf)


@dataclasses.dataclass
class Truth:
    """The reference's answers for a set of queries."""

    ids: np.ndarray     # [Q, k] exact top-k ids, -1 padded
    d: np.ndarray       # [Q, k] their distances as the top-k scored them
    kth: np.ndarray     # [Q] float32 distance of the k-th (or last) id


class Reference:
    """The data on the device, and the two computations the check needs."""

    def __init__(self, vectors, s, t, relation: str, *, block: int = 256):
        self.relation = relation
        self.block = block
        self.s_host = np.asarray(s, np.float64)
        self.t_host = np.asarray(t, np.float64)
        self.x = jnp.asarray(np.asarray(vectors, np.float32))
        self.s = jnp.asarray(_f32_exact(s, "data s"))
        self.t = jnp.asarray(_f32_exact(t, "data t"))
        self._xn = {}

    def _norms(self, precision: str):
        if precision not in self._xn:
            x = self.x if precision == "highest" else self.x.astype(
                jnp.bfloat16).astype(jnp.float32)
            self._xn[precision] = jnp.sum(x * x, axis=1)
        return self._xn[precision]

    def topk(self, q, s_q, t_q, k: int, precision: str = "highest") -> Truth:
        """Exact filtered top-k of every query, in blocks of queries."""
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        q = np.asarray(q, np.float32)
        s_q = _f32_exact(s_q, "query s")
        t_q = _f32_exact(t_q, "query t")
        Q, B = q.shape[0], self.block
        pad = -Q % B
        qp = np.pad(q, ((0, pad), (0, 0)))
        sp = np.pad(s_q, (0, pad), constant_values=1.0)
        tp = np.pad(t_q, (0, pad), constant_values=-1.0)   # empty: no-op rows
        xn = self._norms(precision)
        ids, d = [], []
        for i in range(0, Q + pad, B):
            bi, bd = _block_topk(
                self.x, xn, self.s, self.t, jnp.asarray(qp[i:i + B]),
                jnp.asarray(sp[i:i + B]), jnp.asarray(tp[i:i + B]),
                relation=self.relation, k=k, precision=precision)
            ids.append(np.asarray(bi))
            d.append(np.asarray(bd))
        ids = np.concatenate(ids)[:Q].astype(np.int64)
        d = np.concatenate(d)[:Q]
        exact = self.pair_dist(q, ids)
        kth = np.max(np.where(ids >= 0, exact, -np.inf), axis=1)
        return Truth(ids, d, kth.astype(np.float32))

    def pair_dist(self, q, ids, block: int = 2048) -> np.ndarray:
        """Float32 distances of ``ids [A, k]`` to the queries ``q [A, d]``."""
        A = ids.shape[0]
        out = []
        for i in range(0, A, block):
            qb, ib = q[i:i + block], ids[i:i + block]
            pad = block - qb.shape[0]
            qb = np.pad(qb, ((0, pad), (0, 0)))
            ib = np.pad(ib, ((0, pad), (0, 0)), constant_values=-1)
            out.append(np.asarray(_pair_dist(
                self.x, jnp.asarray(qb, jnp.float32),
                jnp.asarray(ib, jnp.int32)))[:block - pad])
        return np.concatenate(out) if out else np.zeros((0, ids.shape[1]),
                                                         np.float32)

    def valid(self, ids, s_q, t_q) -> np.ndarray:
        """Whether each id satisfies its query's predicate (float64, host)."""
        safe = np.clip(ids, 0, self.s_host.shape[0] - 1)
        return predicate(self.relation)(
            self.s_host[safe], self.t_host[safe], s_q[:, None], t_q[:, None])


@dataclasses.dataclass
class Verdict:
    """The numbers compared, and each answer's recall."""

    missing: int
    bad: int
    dist_err: float
    recall: np.ndarray           # [A] recall@k of each answer

    def checks(self, limits: dict) -> dict:
        """``{name: {"value", "limit"}}`` in the order they are reported."""
        return {
            "missing": {"value": self.missing, "limit": 0},
            "bad": {"value": self.bad, "limit": 0},
            "dist_err": {"value": self.dist_err,
                         "limit": float(limits["dist_err"])},
        }

    def correct(self, limits: dict) -> bool:
        return all(c["value"] <= c["limit"]
                   for c in self.checks(limits).values())


def compare(ref: Reference, truth: Truth, pool_idx: np.ndarray,
            q: np.ndarray, s_q: np.ndarray, t_q: np.ndarray,
            ids: np.ndarray, d: np.ndarray, *, missing: int,
            n: int) -> Verdict:
    """Hold the answers ``ids, d [A, k]`` (answer ``a`` is for pool query
    ``pool_idx[a]``; ``q, s_q, t_q`` are the pool's) against the
    reference's ``truth`` for the pool."""
    ids = np.asarray(ids, np.int64)
    d = np.asarray(d, np.float32)
    A, k = ids.shape
    qa, sa, ta = q[pool_idx], s_q[pool_idx], t_q[pool_idx]
    real = ids >= 0
    in_range = ~real | (ids < n)
    prefix = np.all(real[:, 1:] <= real[:, :-1], axis=1)
    srt = np.sort(np.where(real, ids, -1 - np.arange(k)), axis=1)
    dup = np.any(srt[:, 1:] == srt[:, :-1], axis=1)
    ok_pred = ~real | ref.valid(ids, sa, ta)
    finite = ~real | np.isfinite(d)
    dd = np.where(real, d, np.inf)
    ordered = np.all(~real[:, 1:] | (dd[:, 1:] >= dd[:, :-1]), axis=1)
    bad_rows = ~(np.all(in_range & ok_pred & finite, axis=1) & prefix
                 & ~dup & ordered)

    exact = ref.pair_dist(qa, np.where(in_range, ids, -1))
    use = real & in_range & finite
    gap = np.abs(np.where(use, d, 0.0).astype(np.float64)
                 - np.where(use, exact, 0.0))
    rel = gap / np.maximum(np.where(use, exact, 1.0), 1e-30)
    dist_err = float(np.max(rel)) if rel.size else 0.0

    kth = truth.kth[pool_idx]
    want = np.minimum(np.sum(truth.ids[pool_idx] >= 0, axis=1), k)
    hit = use & ok_pred & (exact <= kth[:, None])
    hits = np.where(dup, 0, np.sum(hit, axis=1))
    recall = np.where(want > 0, np.minimum(hits, want) / np.maximum(want, 1),
                      1.0)
    return Verdict(int(missing), int(np.count_nonzero(bad_rows)), dist_err,
                   recall)
