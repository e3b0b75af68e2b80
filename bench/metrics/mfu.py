"""The whole round's share (%) of the chip's peak: the least time the
round's required work could take -- the larger of its FLOPs over peak
FLOP/s and its bytes over peak HBM bandwidth -- over the measured time
per round (rounds counted at segment boundaries in the traced window).

Required work: the training of the clients whose upload is used, the
data their training reads once, and the SAFA state a round must read
and write (``work.round_state_bytes``)."""
from bench import work
from bench.readers import per_round


def read(ctx):
    if not ctx.round_s:
        return None
    s = ctx.shape
    flops = per_round(s.committed, lambda k: k * s.flops_per_client)
    nbytes = per_round(
        (s.committed, s.rows_read, s.rows_written),
        lambda k, read, cache: k * s.bytes_per_client
        + work.round_state_bytes(read, cache, s.n))
    least = max(flops / ctx.peaks.flops_bf16, nbytes / ctx.peaks.hbm_bw)
    return 100.0 * least / ctx.round_s
