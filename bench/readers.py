"""Arithmetic shared by the per-layer metric readers in ``metrics/``."""

from __future__ import annotations


def module_ms(ctx, programs, count: int):
    """Device milliseconds of the named programs over ``count`` (rounds or
    evaluations) in the traced window; None when none of them ran."""
    modules = ctx.trace_summary["modules"]
    found = [modules[p]["seconds"] for p in programs if p in modules]
    if not found or not count:
        return None
    return 1e3 * sum(found) / count


def round_share(ctx, ops: float, nbytes: float):
    """Percent of one chip's roofline: the least time of one round's required
    work over the traced window's time per round."""
    from bench import roofline

    rounds = ctx.window.traced_rounds
    if not rounds:
        return None
    least = roofline.least_seconds(ops, nbytes, ctx.devices[0].device_kind)
    return 100.0 * least / (ctx.trace_summary["window_s"] / rounds)
