"""The program's `solve_pool` span (reported as the solve's ``solve_s``
timing), mean seconds per solve in the window."""


def read(run):
    if run.answers:
        return sum(a.timings["solve_s"] for a in run.answers) / len(
            run.answers)
    return None
