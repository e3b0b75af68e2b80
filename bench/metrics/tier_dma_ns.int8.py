"""Device nanoseconds of the fused int8 tier-rows kernel per explicit
DMA it issues: its device time in the trace over its calls times the
DMAs the program counts for one call when it traces the kernel
(``repro.obs.counters()``).  None where the trace holds no call of it or
the program keeps no such counter."""
from bench.readers import calls_of

KERNEL = 'safa_aggregate_packed_q8_tier_rows'


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    dmas = obs.counters().get(KERNEL, {}).get('dmas')
    if ctx.trace is None or not dmas:
        return None
    secs, calls = ctx.trace.time_of(calls_of((KERNEL,)))
    if not calls or secs <= 0:
        return None
    return 1e9 * secs / (calls * dmas)
