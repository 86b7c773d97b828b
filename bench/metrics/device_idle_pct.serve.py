"""Share of the decode window in which no operation ran on the device.

Window: from the second generated step's program to the end of the last
step's, in the traced call (the steps ``run_serve`` times).  Busy: the
union of the device's operation intervals in it.
"""
from bench import tracing


def read(ctx, records):
    window = ctx.driver_window(ctx.trace)
    if window is None:
        return None
    idle = tracing.idle_share(ctx.trace, *window)
    return None if idle is None else 100.0 * idle
