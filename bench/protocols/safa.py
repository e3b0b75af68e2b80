"""SAFA (Wu et al., arXiv:1910.01355): lag-tolerant distribution (Eq. 3),
CFCFM selection and the three-bypass aggregation (Eq. 6-8)."""
from bench import events, reference

#: every schedule form the program offers computes the same rounds
SCHEDULES = ('dense', 'sparse', 'sparse_delta', 'sparse_tier')
PARAMS = ('fraction', 'lag_tolerance')

replay = reference.replay


def api_spec(params: dict):
    from repro import api
    return api.SafaSpec(fraction=params['fraction'],
                        lag_tolerance=params['lag_tolerance'])


def masks(draws: events.Draws, params: dict, rounds: int) -> events.Masks:
    return events.safa_masks(draws, fraction=params['fraction'],
                             lag_tolerance=params['lag_tolerance'],
                             rounds=rounds)
