"""The whole round's share of the chip's roofline in a gap run, percent: the
least time the round's required work could take (``roofline.group_round``,
counted from the nonzeros and k = rho d) over the measured time per round of
the traced window, certificates included."""


def read(ctx):
    from bench import roofline
    from bench.data import shape_of
    from bench.readers import round_share

    K, n_k, d = shape_of(ctx.config)
    t = ctx.traffic
    ops, nbytes = roofline.group_round(
        K=K, n_k=n_k, d=d, nnz_row=ctx.job.sparse.nnz_per_row().mean(),
        B=t["B"], T=t["T"], H=n_k * t["local_passes"],
        k=min(d, t["rho_d"]), eval_every=t["eval_every"])
    return round_share(ctx, ops, nbytes)
