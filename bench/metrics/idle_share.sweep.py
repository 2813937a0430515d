"""Percent of the traced window of a sweep grid in which no operation ran on
the chip: 100 (1 - busy / window), busy the union of op intervals."""


def read(ctx):
    s = ctx.trace_summary
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
