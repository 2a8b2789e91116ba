"""Cut weight over total edge weight, mean over the window's solves; each
returned assignment re-scored on the host in float64 by the benchmark."""


def read(run):
    if run.host_cuts:
        return sum(run.host_cuts) / len(run.host_cuts) / run.total_weight
    return None
