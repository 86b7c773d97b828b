"""Device programs the profiling path runs per timed decode step.

Executions of compiled programs (the device's ``XLA Modules`` line) that
start inside the program's ``serve.profile`` spans of the timed steps,
the serve step itself left out, over the number of those spans.
"""
from bench.metrics import _spans


def read(ctx, records):
    spans = _spans.in_window(ctx, "serve.profile")
    if not spans:
        return None
    others = [e for e in ctx.trace.modules("")
              if records["program"] not in e.name]
    return _spans.count_starting_inside(others, spans) / len(spans)
