"""Share of its roofline that a build iteration reaches, in %: a
program-level reading of the build layer, not of a kernel. The least
time of the sampled local join's necessary operations and bytes
(``work.join_iteration``: the rows, the rho*k new and rho*k old
candidates per row, the unpadded d) over the device time of the whole
``nn_descent_iteration`` programs that ran in the traced part of a
build: selection, the feature gather, the ``knn_join_dists`` kernel, the
incidence sort, select and merge. Only the join's work is counted, so a
gain anywhere in the iteration moves it. The kernel's own time is not
used: it reads its gathered operand from on-chip memory, so its HBM
bytes over its time would overstate the share."""
from benchmarks.chip import work


def read(run):
    traced = run.record.get("traced_builds")
    t = run.trace
    if not traced or t is None:
        return None
    secs = t.module_seconds("nn_descent_iteration")
    iters = sum(b["traced_iters"] for b in traced)
    if secs <= 0 or iters <= 0:
        return None
    cfg = run.spec.config
    rho_k = max(1, round(cfg["descent"]["rho"] * cfg["k"]))
    flops, nbytes = work.join_iteration(cfg["data"]["rows"],
                                        cfg["data"]["dim"], rho_k, rho_k)
    least = work.least_seconds(flops * iters, nbytes * iters, run.peaks)
    return 100.0 * least / secs
