"""Share of a build's time taken by its reorder step (paper section 3.2:
``greedy_reorder`` and the ``apply_permutation`` after it,
core/reorder.py), in %. A host-clock estimate, not a device reading: the
step runs between the first and second iterations' ends, so its time is
taken as that span less the median span of the later iterations, whose
shapes are the same (in a traced build the second's span also holds the
profiler's start, which the median leaves out). The build's time leaves
out the profiler's start and the collection of its trace. Mean over the
run's builds."""
import statistics


def read(run):
    shares = []
    for b in run.record.get("builds", []):
        t = b.get("iteration_ends", [])
        if len(t) < 4:
            continue
        later = statistics.median(hi - lo for lo, hi in zip(t[1:], t[2:]))
        reorder = (t[1] - t[0]) - later
        if reorder > 0:
            shares.append(100.0 * reorder
                          / (b["seconds"] - b.get("profiler_s", 0.0)))
    return sum(shares) / len(shares) if shares else None
