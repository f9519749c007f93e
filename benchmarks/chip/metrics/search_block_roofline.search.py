"""Share of its roofline that the fused graph search reaches, in %: a
program-level reading of the search layer, not of a kernel. The least
time of the search's necessary operations and bytes
(``work.search_tile``) over the device time of its ``_search_block``
programs. Each query block scores its routed entry seeds once
(``seed_width`` candidates per query), then expand*k candidates per
query in each round; the trace's ``knn_search_dists`` kernel events give
the number of each, told apart by their output width. The program's
time holds the candidate gathers, the kernel, the top-C select and the
pool merge."""
from benchmarks.chip import work


def read(run):
    t = run.trace
    if not run.record.get("traced_dispatches") or t is None:
        return None
    secs = t.module_seconds("_search_block")
    if secs <= 0:
        return None
    cfg = run.spec.config
    s, dim = cfg["search"], cfg["data"]["dim"]
    qb, width = s["q_block"], s["expand"] * cfg["k"]
    rounds = t.op_count("knn_search_dists", (qb, width))
    seeds = t.op_count("knn_search_dists") - rounds
    f1, b1 = work.search_tile(qb, s["seed_width"], dim)
    f2, b2 = work.search_tile(qb, width, dim)
    least = work.least_seconds(seeds * f1 + rounds * f2,
                               seeds * b1 + rounds * b2, run.peaks)
    return 100.0 * least / secs
