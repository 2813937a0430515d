"""The whole round's share of the chip's roofline in a sweep grid, percent:
the least time one lockstep round of every cell of the grid could take
(``roofline.lockstep_round``, counted from the nonzeros) over the measured
time per round of the traced grid, certificates included."""


def read(ctx):
    from bench import roofline
    from bench.data import shape_of
    from bench.readers import round_share

    K, n_k, d = shape_of(ctx.config)
    t = ctx.traffic
    ops, nbytes = roofline.lockstep_round(
        cells=t["seeds_per_grid"] * len(t["gammas"]), K=K, n_k=n_k, d=d,
        nnz_row=ctx.job.sparse.nnz_per_row().mean(),
        H=n_k * t["local_passes"], eval_every=t["eval_every"])
    return round_share(ctx, ops, nbytes)
