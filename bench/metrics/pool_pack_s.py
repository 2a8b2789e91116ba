"""The program's `pool_pack` span (reported as the solve's ``pool_pack_s``
timing): packing the subgraphs into the pool's batch arrays and handing
them to the device, mean seconds per solve in the window. None where the
program reports no such timing."""


def read(run):
    if run.answers and "pool_pack_s" in run.answers[0].timings:
        return sum(a.timings["pool_pack_s"] for a in run.answers) / len(
            run.answers)
    return None
