"""Host time unpacking each traced ``run_sim_batch`` call's lanes (ms).

The program's ``sim.unpack`` spans (the outputs, once on the host, into
one ``SimResult`` per lane) inside each of the driver's call spans, mean
per call.
"""
from bench.metrics import _spans


def read(ctx, records):
    return _spans.ms_per_call(ctx, "sim.unpack")
