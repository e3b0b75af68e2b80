"""HBM roofline share (%) of the row gather/scatter kernels, over the
rows each call touches: the local models training starts from."""
from bench import work
from bench.readers import kernel_roofline, per_round

KERNELS = ('gather_rows', 'scatter_rows')


def read(ctx):
    s = ctx.shape
    return kernel_roofline(ctx, KERNELS, per_round(
        s.rows_read, lambda k: work.rows_bytes(k, s.n)))
