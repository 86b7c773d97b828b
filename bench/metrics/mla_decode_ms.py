"""Device time of the decode step under the model's ``attn`` scope, per
timed step (ms): latent attention, its absorbed products over the latent
cache and the cache write (``latent_update``), each operation's own time."""
from bench.metrics import _mla_moe


def read(ctx, records):
    return _mla_moe.scope_ms(ctx, "attn")
