"""Host time packing each traced ``run_sim_batch`` call's lanes (ms).

The program's ``sim.pack`` spans (machine and fault packing, stacking and
the host-to-device transfer, up to the jitted call) inside each of the
driver's call spans, mean per call.
"""
from bench.metrics import _spans


def read(ctx, records):
    return _spans.ms_per_call(ctx, "sim.pack")
