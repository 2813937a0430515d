"""Device program launches per round of the traced window: every XLA module
event on the chip, over the rounds the event loop applied."""


def read(ctx):
    modules = ctx.trace_summary["modules"]
    rounds = ctx.window.traced_rounds
    if not modules or not rounds:
        return None
    return sum(m["launches"] for m in modules.values()) / rounds
