"""HBM roofline share (%) of the dense Eq. 6-8 aggregation kernel, one
call a round over every client's row."""
from bench import work
from bench.readers import kernel_roofline

KERNELS = ('safa_aggregate_packed',)


def read(ctx):
    s = ctx.shape
    return kernel_roofline(ctx, KERNELS, work.dense_aggregate_bytes(s.m, s.n))
