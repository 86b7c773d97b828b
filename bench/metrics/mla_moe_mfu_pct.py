"""The whole decode step's share of the chip's bf16 peak (%): model FLOPs
of the timed steps (``bench/counts_mla_moe.decode_step``) over the decode
window's length in the trace, over the peak.  Host time counts: it moves
with ``decode_step_ms``."""
from bench.metrics import _mla_moe, _serve


def read(ctx, records):
    window, progs = _serve.steps(ctx)
    if not progs:
        return None
    flops, _ = _mla_moe.counted(ctx, "step")
    seconds = 1e-9 * (window[1] - window[0])
    return 100.0 * flops * len(progs) / seconds / ctx.peaks["bf16_flops_per_s"]
