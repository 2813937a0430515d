"""Device milliseconds per round in ``_worker_rounds_fused``: the relaunched
workers' local SDCA passes and their top-k filter, one program."""

PROGRAMS = ("_worker_rounds_fused",)


def read(ctx):
    from bench.readers import module_ms

    return module_ms(ctx, PROGRAMS, ctx.window.traced_rounds)
