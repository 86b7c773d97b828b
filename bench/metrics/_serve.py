"""Decode-step arithmetic the serve readers share."""
from bench import counts


def steps(ctx):
    """Executions of the serve step in the traced call's decode window."""
    window = ctx.driver_window(ctx.trace)
    if window is None:
        return None, None
    lo, hi = window
    progs = [e for e in ctx.trace.modules(ctx.records["program"])
             if e.start >= lo and e.end <= hi]
    return window, progs


def per_step(ctx):
    """(FLOPs, bytes) one timed decode step needs, at the mean position."""
    r = ctx.records
    pos = counts.mean_decode_positions(r["prompt_len"], r["gen"])
    return (counts.decode_step_flops(r["config"], r["batch"], pos),
            counts.decode_step_bytes(r["config"], r["batch"], pos))
