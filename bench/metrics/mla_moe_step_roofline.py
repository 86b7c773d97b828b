"""The serve step's share of its roofline (%): the least time of one whole
decode step (``bench/counts_mla_moe.decode_step``) over the step program's
mean device time."""
from bench.metrics import _mla_moe, _serve


def read(ctx, records):
    _, progs = _serve.steps(ctx)
    if not progs:
        return None
    device_s = 1e-9 * sum(e.duration for e in progs) / len(progs)
    return 100.0 * _mla_moe.least_s(ctx, "step") / device_s
