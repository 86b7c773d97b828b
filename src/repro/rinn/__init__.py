"""RINN benchmarks: generation, functional execution, streaming simulation."""
from .graphgen import PATTERNS, RinnConfig, RinnGraph, generate_rinn
from .layers import (
    AddSpec, AvgPool2DSpec, CloneSpec, ConcatSpec, Conv2DSpec,
    DenseSpec, DepthwiseConv2DSpec, FlattenSpec, InputSpec, LayerSpec,
    MaxPool2DSpec, ReluSpec, ReshapeSpec, SigmoidSpec, beats_for_shape,
)
from .hls import BOARDS, PYNQ_Z2, TimingProfile, ZCU102
from .build import (
    forward, forward_batch, init_params, synthetic_mnist16,
    to_profiled_dag, train_symbolically,
)
from .streamsim import (
    BeatFault, CapacityFault, CompiledSim, FaultPlan, NodeStall, SimResult,
    WordCorruption, compile_graph, critical_path_actors, critical_path_edges,
)
from .batchsim import (
    FaultOps, MachineOps, ShapeBucket, TraceBuffers, compile_stats,
    machine_bucket, reset_compile_stats, run_sim, run_sim_batch,
    run_sim_many, run_sim_traced, run_sim_traced_batch,
)
from .cosim import (
    BlockedActor, CosimReport, DeadlockError, DeadlockReport, FifoRow,
    RemediationAttempt, compare, cosim_many, cosim_only, diagnose,
    remediate_pair, run_with_remediation,
)

__all__ = [
    "PATTERNS", "RinnConfig", "RinnGraph", "generate_rinn",
    "AddSpec", "AvgPool2DSpec", "CloneSpec", "ConcatSpec", "Conv2DSpec",
    "DenseSpec", "DepthwiseConv2DSpec", "MaxPool2DSpec",
    "FlattenSpec", "InputSpec", "LayerSpec", "ReluSpec", "ReshapeSpec",
    "SigmoidSpec", "beats_for_shape",
    "BOARDS", "PYNQ_Z2", "TimingProfile", "ZCU102",
    "forward", "forward_batch", "init_params", "synthetic_mnist16",
    "to_profiled_dag", "train_symbolically",
    "CompiledSim", "SimResult", "compile_graph", "run_sim",
    "BeatFault", "CapacityFault", "FaultPlan", "NodeStall", "WordCorruption",
    "critical_path_actors", "critical_path_edges",
    "FaultOps", "MachineOps", "ShapeBucket", "TraceBuffers", "compile_stats",
    "machine_bucket", "reset_compile_stats", "run_sim_batch", "run_sim_many",
    "run_sim_traced", "run_sim_traced_batch",
    "CosimReport", "FifoRow", "compare", "cosim_many", "cosim_only",
    "BlockedActor", "DeadlockError", "DeadlockReport", "RemediationAttempt",
    "diagnose", "remediate_pair", "run_with_remediation",
]
