"""TPU batched-assignment solver.

The genuinely new component of the rebuild (SURVEY.md §7 step 6): the
reference's per-task greedy allocate loop re-expressed as dense tensor ops —
feasibility mask, cost matrix, round-based conflict-resolved assignment —
jitted for TPU, with a sharded multi-chip variant. Which program a cycle
runs is decided once per cycle by the solve plan (plan.py).
"""

from .kernels import (
    PackedInputs,
    jit_compilation_count,
    less_equal,
    make_inputs,
    segmented_cumsum,
    solve,
    solve_full_jit,
    solve_jit,
    solve_sparse,
    solve_sparse_jit,
    solve_staged,
    solve_staged_jit,
)
from .topk import select_candidates
from .sharding import (
    default_mesh,
    init_distributed,
    pad_nodes,
    pad_tasks,
    sharded_step,
    solve_sharded,
)
from .plan import SolvePlan, plan_for, solve_plan
from .snapshot import tensorize
from .spmd import solve_sparse_spmd

__all__ = [
    "PackedInputs",
    "SolvePlan",
    "default_mesh",
    "init_distributed",
    "jit_compilation_count",
    "less_equal",
    "make_inputs",
    "pad_nodes",
    "pad_tasks",
    "plan_for",
    "segmented_cumsum",
    "select_candidates",
    "sharded_step",
    "solve",
    "solve_full_jit",
    "solve_jit",
    "solve_plan",
    "solve_sharded",
    "solve_sparse",
    "solve_sparse_jit",
    "solve_sparse_spmd",
    "solve_staged",
    "solve_staged_jit",
    "tensorize",
]
