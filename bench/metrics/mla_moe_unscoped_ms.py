"""Device time of the decode step under no model scope, per timed step
(ms): the layer scan's stacking of the latent cache, weight-slice copies
and the loop itself, each operation's own time."""
from bench.metrics import _mla_moe


def read(ctx, records):
    return _mla_moe.scope_ms(ctx, "unscoped")
