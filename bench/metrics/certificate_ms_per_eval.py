"""Device milliseconds per gap certificate: the programs
``primal_from_dual``, ``primal_objective`` and ``dual_objective`` that a
streamed evaluation launches, over the evaluations in the traced window."""

PROGRAMS = ("primal_from_dual", "primal_objective", "dual_objective")


def read(ctx):
    from bench.readers import module_ms

    return module_ms(ctx, PROGRAMS, ctx.window.traced_evals)
