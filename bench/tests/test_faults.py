"""A whole run on the CPU with the timed path broken underneath: the
harness must report ``correct: false`` for each fault a cell can have.

The runs skip only the look for a chip (``run.py``'s); the rest --
set-up, the window of back-to-back runs, the reference and the limits of
the cell -- is the benchmark's own, at the tiny sizes of ``tiny.py``.
One chip per cell, so no exchange between chips exists to leave out.
"""
import time

import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests import tiny

DENSE = 'paper_cnn.safa_dense'
TIER = 'xdevice_1m.safa_tier_int8'


def run(cell):
    return harness.run_cell(tiny.spec(cell), seed=3, seconds=1.0,
                            trace=False, chips=1,
                            t_start=time.perf_counter(),
                            log=lambda *a, **k: None)


def _half(w):
    """Weights of the first half of the slots, scaled to the same total:
    the mean over half of the batch."""
    keep = jnp.arange(w.shape[-1]) < w.shape[-1] // 2
    kept = jnp.where(keep, w, 0.0)
    return kept * (jnp.sum(w) / jnp.maximum(jnp.sum(kept), 1e-30))


def unchanged(monkeypatch, cell):
    """The segment returns its state unchanged."""
    from repro.core import protocol
    name = ('safa_run_scan' if cell == DENSE
            else 'safa_run_scan_sparse_tier_packed')
    monkeypatch.setattr(protocol, name, lambda a, b, c, *_, **__: (a, b, c))


def half_batch(monkeypatch, cell):
    """The aggregation takes the mean over half of the clients' uploads."""
    from repro.kernels import ops
    if cell == DENSE:
        real = ops.safa_aggregate_tree_packed

        def agg(*args, weights, **kw):
            return real(*args, weights=_half(weights), **kw)
        monkeypatch.setattr(ops, 'safa_aggregate_tree_packed', agg)
    else:
        real = ops.safa_aggregate_packed_q8_tier_rows

        def agg(*args, **kw):
            return real(*args[:-1], _half(args[-1]), **kw)
        monkeypatch.setattr(ops, 'safa_aggregate_packed_q8_tier_rows', agg)


def altered(monkeypatch, cell):
    """The aggregation kernel writes a new global (and, in the lag-tier
    form, a running aggregate) half its change off."""
    from repro.kernels import ops
    if cell == DENSE:
        real = ops.safa_aggregate_tree_packed

        def agg(cache, trained, global_prev, **kw):
            res = real(cache, trained, global_prev, **kw)
            off = {k: v + 0.5 * (v - global_prev[k])
                   for k, v in res.new_global.items()}
            return res._replace(new_global=off)
        monkeypatch.setattr(ops, 'safa_aggregate_tree_packed', agg)
    else:
        real = ops.safa_aggregate_packed_q8_tier_rows

        def agg(*args, **kw):   # the running aggregate carries it on
            ng, na, buf = real(*args, **kw)
            return (ng + 0.5 * (ng - args[4]), na + 0.5 * (na - args[5]),
                    buf)
        monkeypatch.setattr(ops, 'safa_aggregate_packed_q8_tier_rows', agg)


@pytest.mark.parametrize('cell', [DENSE, TIER])
def test_sound_run_is_correct(cell):
    result = run(cell)
    assert result['correct'], result['checks']
    assert result['checks']['change_gap']['value'] < 1e-3


@pytest.mark.parametrize('fault', [unchanged, half_batch, altered])
@pytest.mark.parametrize('cell', [DENSE, TIER])
def test_fault_is_not_correct(monkeypatch, cell, fault):
    fault(monkeypatch, cell)
    result = run(cell)
    assert not result['correct'], result['checks']
    assert result['failed'] == result['attempted']
