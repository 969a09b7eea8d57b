"""Share of the planner's routes that went to the brute path: 100 ×
``BRUTE_VALID`` ÷ all routes of ``repro_planner_routes_total`` (``plan`` =
``BRUTE_VALID``, ``GRAPH``, ``GRAPH_WIDE``), counted by the harness from
the window's start to the end of its drain (``Run.plans``). The program
routes the padding rows of a partial batch to ``BRUTE_VALID`` and counts
them there; a closed loop's steps are full, so only a partial last batch
of its drain would add any. None where no route was counted."""


def read(run):
    routes = run.plans or {}
    total = sum(routes.values())
    return 100.0 * routes["BRUTE_VALID"] / total if total else None
