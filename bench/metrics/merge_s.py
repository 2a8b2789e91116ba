"""The program's `merge` span, mean seconds per solve in the window."""


def read(run):
    if run.answers:
        return sum(a.timings["merge_s"] for a in run.answers) / len(
            run.answers)
    return None
