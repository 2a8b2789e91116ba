"""The solver pool's share of its roofline: the work `work.pool_batch`
counts from the shapes, against the device time of the pool's program in
the trace, over the chip's peaks (`peaks.py`)."""

import peaks


def read(run):
    t = run.trace
    if t is None or not t.pool_calls or t.pool_s <= 0:
        return None
    flops, nbytes = run.pool_work
    share, _ = peaks.roofline_share(flops * t.pool_calls,
                                    nbytes * t.pool_calls, t.pool_s,
                                    run.device_kind)
    return share
