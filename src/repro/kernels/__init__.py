"""Pallas TPU kernels for the perf-critical hot spots (+ in-band profiling).

Each kernel has a pl.pallas_call implementation with explicit BlockSpec
VMEM tiling (<name>.py) and a pure-jnp oracle (ref.py).  ``mla_decode`` is
on the serve path: latent-attention decode (``models/mla.py``) calls it
for every MLA layer, lowered as the Mosaic kernel for the TPU and
interpreted for any other platform.  The others run where a caller or a
test calls them.  Tests on
the CPU pass ``interpret=True``; ``tests/test_tpu_compile.py`` compiles
each kernel for a described TPU v5e.
"""
from .flash_attention import flash_attention
from .mla_decode import mla_decode
from .moe_dispatch import moe_dispatch
from .profiled_matmul import profiled_matmul
from .ssd_scan import ssd_state_passing
from . import ref

__all__ = [
    "flash_attention", "mla_decode", "moe_dispatch", "profiled_matmul",
    "ssd_state_passing", "ref",
]
