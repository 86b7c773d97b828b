"""Device time of the decode step under the model's ``mlp`` scope, per
timed step (ms), each operation's own time (``_scopes``)."""
from bench.metrics import _scopes


def read(ctx, records):
    return _scopes.read(ctx, "mlp")
