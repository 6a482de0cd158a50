# SupraSNN core: the paper's primary contribution.
#   graph         SNN-as-graph (Eq. 6)
#   memory_model  Eqs. (9)-(11)
#   mapping/      the mapping search subsystem (§6.2): vectorized
#                 partitioner core, lockstep restart population, portfolio
#                 search, strategy registry, legacy parity reference
#   partition     single-seed compatibility shim over mapping/
#   baselines     round-robin baselines (§7.4.1)
#   scheduling/   the scheduling subsystem (§6.3): vectorized array core,
#                 schedule-strategy registry, legacy parity reference,
#                 OpTables/LoweredProgram + lowering, legality checks
#   schedule      compatibility shim over scheduling/
#   engine        functional executor + cycle/energy model (§4, §7)
#   engine_jax    compiled batched executor (lax.scan + Pallas NU)
#   cost          FPGA resource model (Table 2 fit)
#   passes        explicit compile passes (partition/search/schedule/
#                 validate/lower)
#   execution     ExecutionSpec: ONE frozen value naming engine/kernel
#                 tier/interpret/mesh/donation; the engine cache key
#   aot           AOT bucket precompile + persistent XLA cache
#   program       the Program artifact: compile -> run/profile/save/load
#   compiler      deprecated pre-Program wrappers
from repro.core.aot import enable_persistent_cache
from repro.core.execution import (ExecutionSpec, KERNELS, default_kernel)
from repro.core.graph import SNNGraph, from_quantized, random_graph
from repro.core.memory_model import (HardwareConfig, spu_score, spu_usage,
                                     scores_from_assignment,
                                     total_memory_bits, total_memory_kb,
                                     bram_count)
from repro.core.partition import PartitionResult, partition
from repro.core.mapping import (CandidateTrace, MappingStrategy,
                                SearchConfig, SearchTrace, STRATEGIES,
                                framework_partition, get_strategy,
                                portfolio_search, register_strategy)
from repro.core.baselines import (BASELINES, post_neuron_round_robin,
                                  synapse_round_robin, weight_round_robin)
from repro.core.scheduling import (NOP, LoweredProgram, OpTables,
                                   SCHEDULE_STRATEGIES, ScheduleStrategy,
                                   get_schedule_strategy, lower_tables,
                                   register_schedule_strategy, schedule,
                                   validate_schedule)
from repro.core.engine import (CycleModel, CycleReport, PowerModel,
                               MergeAlignmentError, oracle_packet_counts,
                               packet_stats, run_mapped, run_oracle,
                               run_oracle_state)
from repro.core.engine_jax import JaxMappedEngine, run_mapped_batched
from repro.core.cost import ResourceModel, ResourceReport, resources
from repro.core.passes import (CompileReport, build_report,
                               initialization_packets, lower_pass,
                               partition_pass, schedule_pass, search_pass,
                               validate_pass)
from repro.core.program import (ENGINES, PROGRAM_FORMAT_VERSION, Program,
                                ProfileReport, compile)
from repro.core.compiler import compile_snn, compile_quantized

__all__ = [
    "SNNGraph", "from_quantized", "random_graph", "HardwareConfig",
    "spu_score", "spu_usage", "scores_from_assignment", "total_memory_bits",
    "total_memory_kb", "bram_count", "PartitionResult", "partition",
    "BASELINES", "post_neuron_round_robin", "synapse_round_robin",
    "weight_round_robin", "NOP", "LoweredProgram", "OpTables", "lower_tables",
    "schedule", "validate_schedule",
    # scheduling subsystem
    "SCHEDULE_STRATEGIES", "ScheduleStrategy", "get_schedule_strategy",
    "register_schedule_strategy",
    "CycleModel", "CycleReport", "PowerModel", "MergeAlignmentError",
    "oracle_packet_counts", "packet_stats", "run_mapped", "run_oracle",
    "run_oracle_state",
    "JaxMappedEngine", "run_mapped_batched", "ResourceModel", "ResourceReport",
    "resources",
    # mapping search subsystem
    "CandidateTrace", "MappingStrategy", "SearchConfig", "SearchTrace",
    "STRATEGIES", "framework_partition", "get_strategy", "portfolio_search",
    "register_strategy",
    # pass pipeline + artifact API
    "CompileReport", "build_report", "initialization_packets", "lower_pass",
    "partition_pass", "schedule_pass", "search_pass", "validate_pass",
    "ENGINES", "PROGRAM_FORMAT_VERSION", "Program", "ProfileReport",
    "compile",
    # execution spec + AOT layer
    "ExecutionSpec", "KERNELS", "default_kernel", "enable_persistent_cache",
    # deprecated wrappers
    "compile_snn", "compile_quantized",
]
