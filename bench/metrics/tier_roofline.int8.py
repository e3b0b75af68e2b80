"""HBM roofline share (%) of the fused int8 tier-rows Eq. 6-8 kernel,
one call a round over the uploads and the cache entries that change."""
from bench import work
from bench.readers import kernel_roofline, per_round

KERNELS = ('safa_aggregate_packed_q8_tier_rows',)


def read(ctx):
    s = ctx.shape
    return kernel_roofline(ctx, KERNELS, per_round(
        (s.committed, s.rows_written),
        lambda up, cache: work.tier_q8_bytes(up, cache, s.n)))
