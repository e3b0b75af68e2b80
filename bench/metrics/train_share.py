"""Share (%) of the device's busy time spent in client local training:
ops under the adapter's ``jax.named_scope`` around ``local_train``."""
from bench.adapter import TRAIN_SCOPE


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    secs = ctx.trace.scope_s.get(TRAIN_SCOPE, 0.0)
    if secs <= 0:
        return None
    return 100.0 * secs / ctx.trace.busy_s
