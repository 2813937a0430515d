"""Share of the engine's local SDCA passes that ran on the Pallas kernel:
the program's ``solve_kernel_passes`` counter over its ``solve_passes``
counter (``repro.core.tracing.STATS``).  1.0 where every worker pass runs
on the kernel (a TPU, a closed-form loss, d within the kernel's VMEM
budget), 0.0 where each runs the jnp pass over its copy of ``X[k]``.  None
from a program without these counters.

Like ``host_syncs_per_round`` it counts over the whole process, the
warm-up job and the traced job; a cell runs one problem, so the share is
the window's too.
"""


def read(ctx):
    from repro.core.executor import STATS

    passes = STATS.get("solve_passes")
    if not passes or "solve_kernel_passes" not in STATS:
        return None
    return STATS["solve_kernel_passes"] / passes
