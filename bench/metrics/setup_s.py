"""Process start to the first timed solve: imports, instance generation,
compiling or loading the programs, and the warm-up solve."""


def read(run):
    return run.setup_s
