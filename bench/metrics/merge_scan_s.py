"""The program's `merge_scan` span (reported as the solve's
``merge_scan_s`` timing): the merge's scan through the host copy of its
answer, mean seconds per solve in the window. None where the program
reports no such timing."""


def read(run):
    if run.answers and "merge_scan_s" in run.answers[0].timings:
        return sum(a.timings["merge_scan_s"] for a in run.answers) / len(
            run.answers)
    return None
