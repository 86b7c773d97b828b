"""Device time of the decode step under no model scope, per timed step
(ms): the layer scan's cache stacking and weight-slice copies and the loop
itself, each operation's own time (``_scopes``)."""
from bench.metrics import _scopes


def read(ctx, records):
    return _scopes.read(ctx, "unscoped")
