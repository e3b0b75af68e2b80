"""The trace reduction on two small traces recorded on a TPU v5e by
``record_trace.py``: a tiny lag-tier int8 cell and a tiny dense CNN cell
(``data/*.xplane.pb.gz``; ``data/*.json`` holds what the harness printed
from the same trace when it was recorded)."""
import json
import pathlib
import types

import pytest

from bench import harness, readers, trace_reduce
from bench.adapter import TRAIN_SCOPE

DATA = pathlib.Path(__file__).resolve().parent / 'data'


@pytest.fixture(scope='module', params=['tier_int8', 'dense'])
def recorded(request):
    name = request.param
    trace = trace_reduce.load(str(DATA / f'{name}.xplane.pb.gz'))
    printed = json.loads((DATA / f'{name}.json').read_text())
    return name, trace, trace_reduce.reduce(trace), printed


def test_device_and_host_spans_are_found(recorded):
    _, trace, r, printed = recorded
    assert list(trace.devices) == ['/device:TPU:0']
    assert {s[0] for s in trace.spans} == {'bench_window', 'bench_run',
                                           'bench_segment', 'bench_evaluate'}
    assert 0 < r.busy_s < r.window_s
    assert r.window_s == pytest.approx(printed['device']['window_s'],
                                       rel=1e-12)
    assert r.busy_s == pytest.approx(printed['device']['busy_s'], rel=1e-12)
    # own times cover the busy time once: nested ops are not counted
    # twice (a few ops overlap without nesting, by microseconds)
    assert r.busy_s <= sum(r.op_s.values()) <= 1.001 * r.busy_s


def test_shares_match_what_the_harness_printed(recorded):
    _, _, r, printed = recorded
    idle = 100 * (1 - r.busy_s / r.window_s)
    train = 100 * r.scope_s[TRAIN_SCOPE] / r.busy_s
    assert idle == pytest.approx(printed['metrics']['idle_share']['value'],
                                 rel=1e-12)
    assert train == pytest.approx(
        printed['metrics']['train_share']['value'], rel=1e-12)


KERNEL_METRICS = {'dense_aggregate': 'agg_roofline.dense',
                  'gather_rows': 'rows_roofline',
                  'quantize_packed': 'quant_roofline',
                  'tier_q8': 'tier_roofline.int8'}


def _names(kernel):
    return harness.metric_module(KERNEL_METRICS[kernel]).KERNELS


def test_kernels_are_found_by_name(recorded):
    name, _, r, _ = recorded
    calls = {k: r.time_of(readers.calls_of(_names(k)))[1]
             for k in KERNEL_METRICS}
    if name == 'tier_int8':
        # one gather, one quantise and one tier-rows call per round
        assert calls['dense_aggregate'] == 0
        assert calls['gather_rows'] == calls['quantize_packed'] \
            == calls['tier_q8'] > 0
    else:
        assert calls['dense_aggregate'] > 0
        assert calls['gather_rows'] == calls['tier_q8'] == 0
        # nearly all device time of the CNN cell is local training
        assert r.scope_s[TRAIN_SCOPE] > 0.9 * r.busy_s


def test_roofline_reads_bytes_over_time(recorded):
    name, _, r, _ = recorded
    names = _names('tier_q8' if name == 'tier_int8' else 'dense_aggregate')
    secs, calls = r.time_of(readers.calls_of(names))
    ctx = types.SimpleNamespace(
        trace=r, peaks=types.SimpleNamespace(hbm_bw=819e9))
    assert readers.kernel_roofline(ctx, names, 1e6) == pytest.approx(
        100 * 1e6 * calls / (819e9 * secs))
    assert readers.kernel_roofline(ctx, ('no_such_kernel',), 1e6) is None


def test_breakdown_is_capped_and_sorted(recorded):
    _, _, r, printed = recorded
    b = readers.breakdown(r)
    assert b == printed['breakdown']
    for key in ('device_ops', 'idle_gaps'):
        secs = [s for _, s in b[key]]
        assert len(secs) <= 10 and secs == sorted(secs, reverse=True)


def test_every_reader_reads_a_share_from_its_cell(recorded):
    """Each per-layer share of the recorded cell, counted from the tiny
    cell's own shapes, lies in (0, 100]; a kernel the cell does not run
    leaves its metric out."""
    from bench import build
    from bench.peaks import peaks
    from bench.tests import tiny
    name, _, r, printed = recorded
    cell = {'tier_int8': 'xdevice_1m.safa_tier_int8',
            'dense': 'paper_cnn.safa_dense'}[name]
    ctx = types.SimpleNamespace(
        trace=r, shape=build.build(tiny.spec(cell), 5).shape(),
        round_s=r.window_s / printed['attempted'],
        peaks=peaks(printed['device']['kind']))
    runs = {'tier_int8': {'tier_roofline.int8', 'rows_roofline',
                          'quant_roofline', 'mfu'},
            'dense': {'agg_roofline.dense', 'mfu'}}[name]
    for metric in ('agg_roofline.dense', 'tier_roofline.int8',
                   'rows_roofline', 'quant_roofline', 'mfu'):
        value = harness.metric_module(metric).read(ctx)
        if metric in runs:
            assert 0 < value <= 100, (metric, value)
        else:
            assert value is None, (metric, value)
