"""The program's `merge_plan` span (reported as the solve's
``merge_plan_s`` timing): the merge's host plan and beam width, mean
seconds per solve in the window. None where the program reports no such
timing."""


def read(run):
    if run.answers and "merge_plan_s" in run.answers[0].timings:
        return sum(a.timings["merge_plan_s"] for a in run.answers) / len(
            run.answers)
    return None
