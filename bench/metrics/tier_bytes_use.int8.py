"""Share (%) of the bytes the fused int8 tier-rows kernel moves that the
round requires: ``work.tier_q8_bytes`` per call (the uploads and the
cache entries that change, read and written once) over the bytes the
program counts for one call when it traces the kernel
(``repro.obs.counters()``: its explicit 8-row-group DMAs and its
pipelined blocks).  None where the trace holds no call of the kernel or
the program keeps no such counter."""
from bench import work
from bench.readers import calls_of, per_round

KERNEL = 'safa_aggregate_packed_q8_tier_rows'


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    moved = obs.counters().get(KERNEL, {}).get('bytes')
    if ctx.trace is None or not moved \
            or not ctx.trace.time_of(calls_of((KERNEL,)))[1]:
        return None
    s = ctx.shape
    need = per_round((s.committed, s.rows_written),
                     lambda up, cache: work.tier_q8_bytes(up, cache, s.n))
    return 100.0 * need / moved
