"""The program's `refine` span, mean seconds per solve in the window; only
where the configuration refines."""


def read(run):
    if run.answers and run.solver["refine_steps"] > 0:
        return sum(a.timings["refine_s"] for a in run.answers) / len(
            run.answers)
    return None
