"""The program's `pool_place` span (reported as the solve's ``pool_place_s``
timing): placing the packed batch on the device, or on the solvers' mesh,
through the transfer's end, mean seconds per solve in the window. None
where the program reports no such timing."""


def read(run):
    if run.answers and "pool_place_s" in run.answers[0].timings:
        return sum(a.timings["pool_place_s"] for a in run.answers) / len(
            run.answers)
    return None
