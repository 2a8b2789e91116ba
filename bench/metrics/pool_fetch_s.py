"""The program's `pool_fetch` span (reported as the solve's ``pool_fetch_s``
timing): the host copy of the pool's candidates, gathered from every chip
once the device result is ready, mean seconds per solve in the window.
None where the program reports no such timing."""


def read(run):
    if run.answers and "pool_fetch_s" in run.answers[0].timings:
        return sum(a.timings["pool_fetch_s"] for a in run.answers) / len(
            run.answers)
    return None
