"""Seconds of JAX compiling inside each solve (the solve's ``compile_s``
timing: the union of JAX's trace, lowering and backend-compile phases,
cache loads included, billed to the program's `solve` span), mean per
solve in the window. None where the program reports no such timing."""


def read(run):
    if run.answers and "compile_s" in run.answers[0].timings:
        return sum(a.timings["compile_s"] for a in run.answers) / len(
            run.answers)
    return None
