"""Programs compiled or loaded from the compilation cache inside the
window, counted from JAX's own backend-compile events."""


def read(run):
    return run.compiles
