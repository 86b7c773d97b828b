from .ctx import activation_sharding, logical_pspec, shard_act
from .sharding import (batch_shardings, cache_shardings, default_rules,
                       param_shardings, replicated)
from .collectives import (compressed_mean, compressed_mean_tree,
                          dequantize_int8, exact_mean_tree, quantize_int8)
from .pipeline import (make_pipelined_forward, pipeline_stage_fn,
                       pipeline_utilization)
from .fault import (DegradationEvent, PROFILING_LADDER, ProfilingSupervisor,
                    RetryPolicy, Watchdog, retry_with_backoff)
