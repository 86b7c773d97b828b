"""The ``attn`` scope's share of its roofline (%): the least time of one
step's latent attention (``bench/counts_mla_moe.mla_step``: MLA weights
once, the latent positions in use and the one written, the absorbed score
and value FLOPs) over the scope's measured device time per step."""
from bench.metrics import _mla_moe


def read(ctx, records):
    return _mla_moe.scope_roofline(ctx, "attn")
