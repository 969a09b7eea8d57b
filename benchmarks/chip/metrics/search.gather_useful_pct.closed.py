"""Share of the candidate rows handed to the packed gather kernel that
entered the beam: 100 × Σ ``repro_search_candidates_kept_total`` ÷ Σ
``repro_search_candidate_slots_total``, over both loops (``plan`` =
``GRAPH`` and ``GRAPH_WIDE``). The slots are every row of every trip
(trips × B × M·E): masked rows, rows of idle queries, adjacency padding,
candidates that fail the predicate or were visited. A kept candidate is
one that passed the predicate and visited tests and entered the merge.

Reads the program's metrics registry, which covers the whole traced
process: warm-up, window and drain. None where the program keeps no such
counters."""

PLANS = ("GRAPH", "GRAPH_WIDE")


def summed(reg, name):
    if name not in reg.names():
        return None
    c = reg.counter(name)
    return sum(c.value(plan=p) for p in PLANS)


def read(run):
    from repro.obs.metrics import get_registry

    reg = get_registry()
    kept = summed(reg, "repro_search_candidates_kept_total")
    slots = summed(reg, "repro_search_candidate_slots_total")
    if kept is None or not slots:
        return None
    return 100.0 * kept / slots
