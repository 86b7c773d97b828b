"""Host time of the profiling path per timed decode step (ms).

The mean length of the program's ``serve.profile`` spans over the traced
call's timed steps (the spans that overlap its decode window).  One span
covers a step's profiling after the model step is dispatched: building the
stream (``serve.profile.build``), verifying it with its device reads
(``serve.profile.verify``), folding it (``serve.profile.fold``), the
watchdog and the supervisor.
"""
from bench.metrics import _spans


def read(ctx, records):
    spans = _spans.in_window(ctx, "serve.profile")
    if not spans:
        return None
    return 1e-6 * sum(s.duration for s in spans) / len(spans)
