"""Distance evaluations per row of a build (``DescentStats.dist_evals``
over the rows), the paper's measure of wasted selection work; mean over
the run's builds."""


def read(run):
    builds = run.record.get("builds")
    if not builds:
        return None
    return sum(b["dist_evals"] / b["rows"] for b in builds) / len(builds)
