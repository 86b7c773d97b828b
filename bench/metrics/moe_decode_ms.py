"""Device time of the decode step under the model's ``mlp`` scope, per
timed step (ms): layer 0's dense MLP and the MoE layers' ``router``,
held ``experts`` and ``shared_experts``, each operation's own time."""
from bench.metrics import _mla_moe


def read(ctx, records):
    return _mla_moe.scope_ms(ctx, "mlp")
