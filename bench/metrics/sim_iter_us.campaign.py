"""Device time of the batched simulator program per ``while_loop``
iteration (us), averaged over the traced calls.

A call's program runs until its slowest lane stops, so its iterations are
the largest lane ``cycles`` of the call.
"""


def read(ctx, records):
    spans = ctx.trace.spans(ctx.driver.span_name)
    calls = records["calls"][-len(spans):] if spans else []
    per_iter = []
    for span, call in zip(spans, calls):
        device_ns = sum(e.duration for e in ctx.trace.modules("_simulate")
                        if e.start >= span.start and e.end <= span.end)
        if device_ns > 0 and call["iterations"] > 0:
            per_iter.append(1e-3 * device_ns / call["iterations"])
    return sum(per_iter) / len(per_iter) if per_iter else None
