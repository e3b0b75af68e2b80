"""Share (%) of the FLOPs the program performs in local training that the
round requires: the committed clients' training FLOPs
(``Shape.committed`` x ``flops_per_client``, from logical shapes, no
recomputation) over the FLOPs the program counts for one
``local_train`` call when it traces it (``repro.obs.counters()``
``lm_local_train``: every client, with recomputation).  None where the
program keeps no such counter."""
from bench.readers import per_round

COUNTER = 'lm_local_train'


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    performed = obs.counters().get(COUNTER, {}).get('flops')
    if not performed:
        return None
    s = ctx.shape
    need = per_round(s.committed, lambda k: k * s.flops_per_client)
    return 100.0 * need / performed
