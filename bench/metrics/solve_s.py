"""Time to solution: the window's length over the solves it completed."""


def read(run):
    if run.answers:
        return run.window_s / len(run.answers)
    return None
