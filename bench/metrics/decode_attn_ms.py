"""Device time of the decode step under the model's ``attn`` scope, per
timed step (ms): projections, rotary, scores and the cache write
(``kv_update``), each operation's own time (``_scopes``)."""
from bench.metrics import _scopes


def read(ctx, records):
    return _scopes.read(ctx, "attn")
