"""Share of the padded search loops' row slots in which the row expanded a
beam entry: 100 × Σ ``repro_search_iterations_total`` ÷ Σ
``repro_search_row_slots_total``, over both loops (``plan`` = ``GRAPH``
and ``GRAPH_WIDE``). A slot is one row in one trip of a loop; a row that
has converged, or was planned to another strategy, idles in its slots
until the loop's longest row is done.

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
    used = summed(reg, "repro_search_iterations_total")
    slots = summed(reg, "repro_search_row_slots_total")
    if used is None or not slots:
        return None
    return 100.0 * used / slots
