"""The serve step's share of its roofline (%).

The least time the chip could take for one timed decode step, the larger
of its FLOPs over peak FLOP/s and its bytes over peak HBM bytes/s
(``bench/counts.py``: weights once, the batch's embedding rows, the keys
and values of the positions in use), over the step program's mean device
time.  Memory bound at these shapes.
"""
from bench.metrics import _serve


def read(ctx, records):
    _, progs = _serve.steps(ctx)
    if not progs:
        return None
    flops, nbytes = _serve.per_step(ctx)
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    device_s = 1e-9 * sum(e.duration for e in progs) / len(progs)
    return 100.0 * least / device_s
