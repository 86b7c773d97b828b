"""Simulator program launches per ``compare`` call in the window: one per
remediation rung, so 1 when no design needs its FIFOs grown.  Read from the
simulator's own launch counter (``compile_stats()["launches"]``); a count,
it repeats exactly."""


def read(ctx, records):
    calls = len(records["calls"])
    return records["launches"] / calls if calls else None
