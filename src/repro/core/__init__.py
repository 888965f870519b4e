"""The paper's primary contribution: distributed zero-copy SpTRSV."""
from repro.core.analysis import in_degrees, level_sets, metrics
from repro.core.blocking import BlockStructure, build_blocks, pad_rhs, unpad_x
from repro.core.partition import (
    Partition,
    cut_stats,
    make_partition,
    merge_levels,
    remote_source_levels,
)
from repro.core.solver import (
    AXIS,
    DistributedSolver,
    Plan,
    SolverConfig,
    build_plan,
    dispatch_stats,
    fused_segments,
    fused_streaming,
    fused_vmem_bytes,
    refresh_plan,
    schedule_table_bytes,
    solve_local,
    step_offsets,
    step_widths,
    stream_dma_bytes_per_solve,
    stream_vmem_limit,
    streamed_stores,
)
