"""The ``mlp`` scope's share of its roofline (%): the least time of one
step's feed-forward slot (``bench/counts_mla_moe.ffn_step``: held, shared
and router weights once a MoE layer, layer 0's MLP, FLOPs of the tokens
routed to held experts) over the scope's measured device time per step."""
from bench.metrics import _mla_moe


def read(ctx, records):
    return _mla_moe.scope_roofline(ctx, "mlp")
