"""HBM roofline share (%) of the packed int8 quantisation kernel, one
call a round over the uploads."""
from bench import work
from bench.readers import kernel_roofline, per_round

KERNELS = ('quantize_packed',)


def read(ctx):
    s = ctx.shape
    return kernel_roofline(ctx, KERNELS, per_round(
        s.committed, lambda k: work.quantize_bytes(k, s.n)))
