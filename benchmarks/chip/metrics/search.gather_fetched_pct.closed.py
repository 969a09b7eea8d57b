"""Share of the candidate rows handed to the gather kernels whose vector
row the kernel fetched: 100 × Σ ``repro_search_rows_fetched_total`` ÷ Σ
``repro_search_candidate_slots_total``, over both loops (``plan`` =
``GRAPH`` and ``GRAPH_WIDE``). The slots are every row of every trip
(trips × B × M·E); a row is fetched when its candidate is not adjacency
padding, not in a row that expands nothing and not visited. The gap to
``search.gather_useful_pct.closed`` is the rows fetched and then thrown
away, most of them by the label test.

Reads the program's metrics registry, which covers the whole traced
process: warm-up, window and drain. None where the program keeps no such
counter."""

PLANS = ("GRAPH", "GRAPH_WIDE")


def summed(reg, name):
    if name not in reg.names():
        return None
    c = reg.counter(name)
    return sum(c.value(plan=p) for p in PLANS)


def read(run):
    from repro.obs.metrics import get_registry

    reg = get_registry()
    fetched = summed(reg, "repro_search_rows_fetched_total")
    slots = summed(reg, "repro_search_candidate_slots_total")
    if fetched is None or not slots:
        return None
    return 100.0 * fetched / slots
