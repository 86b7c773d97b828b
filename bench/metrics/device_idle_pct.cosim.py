"""Share of the window of calls in which no operation ran on the device.

Window: from the start of the first traced ``compare`` call to the end of
the last (the benchmark's own host spans).  Busy: the
union of the device's operation intervals in it.
"""
from bench import tracing


def read(ctx, records):
    window = ctx.driver_window(ctx.trace)
    if window is None:
        return None
    idle = tracing.idle_share(ctx.trace, *window)
    return None if idle is None else 100.0 * idle
