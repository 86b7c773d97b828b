"""Device time of the serve-step program per timed decode step (ms)."""
from bench.metrics import _serve


def read(ctx, records):
    _, progs = _serve.steps(ctx)
    if not progs:
        return None
    return 1e-6 * sum(e.duration for e in progs) / len(progs)
