"""The whole decode step's share of the chip's bf16 peak (%).

Model FLOPs of the timed decode steps (``bench/counts.py``: 2 per matmul
weight per sequence, attention over the positions in use) over the decode
window's length in the trace, over the peak.  Host time counts: it moves
with ``decode_step_ms``.
"""
from bench.metrics import _serve


def read(ctx, records):
    window, progs = _serve.steps(ctx)
    if not progs:
        return None
    flops, _ = _serve.per_step(ctx)
    seconds = 1e-9 * (window[1] - window[0])
    return 100.0 * flops * len(progs) / seconds / ctx.peaks["bf16_flops_per_s"]
