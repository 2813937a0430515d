"""Device milliseconds per round in ``_server_apply_fused``: the server's
apply of the arrived payloads and its catch-up replies."""

PROGRAMS = ("_server_apply_fused",)


def read(ctx):
    from bench.readers import module_ms

    return module_ms(ctx, PROGRAMS, ctx.window.traced_rounds)
