"""Per-phase device time and program-span gap names (``bench/phases.py``),
and the readers of the program's counters, on synthetic traces and on
small traces recorded on a TPU v5e; and every earlier reader's value on
the first two recorded traces, pinned, so that what this module adds
provably moves none of them."""
import json
import pathlib
import types

import pytest

from bench import harness, phases, trace_reduce
from bench.trace_reduce import Op, Trace

DATA = pathlib.Path(__file__).resolve().parent / 'data'
#: the same tiny cells recorded with the program's phase scopes, by
#: ``record_scoped.py``
SCOPED = DATA / 'scoped'
CELL = {'tier_int8': 'xdevice_1m.safa_tier_int8',
        'dense': 'paper_cnn.safa_dense'}

# -- every earlier reader on the first recorded traces -------------------------

#: each reader's value on ``data/<trace>.xplane.pb.gz`` and the tiny
#: cell's shapes at seed 5, as read before the program had phase scopes
PINNED = {
    'tier_int8': {'idle_share': 94.08335962080582,
                  'train_share': 0.8495545460788192,
                  'mfu': 0.036913710495172974,
                  'agg_roofline.dense': None,
                  'tier_roofline.int8': 6.0564521175478,
                  'rows_roofline': 1.4069958885903833,
                  'quant_roofline': 62.38974344875618},
    'dense': {'idle_share': 67.0718739214368,
              'train_share': 99.12324088052272,
              'mfu': 0.010303695382316808,
              'agg_roofline.dense': 94.48508381375655,
              'tier_roofline.int8': None,
              'rows_roofline': None,
              'quant_roofline': None},
}


def _ctx(name, trace, data=DATA):
    from bench import build
    from bench.peaks import peaks
    from bench.tests import tiny
    printed = json.loads((data / f'{name}.json').read_text())
    r = trace_reduce.reduce(trace)
    return types.SimpleNamespace(
        trace=r, shape=build.build(tiny.spec(CELL[name]), 5).shape(),
        round_s=r.window_s / printed['attempted'],
        peaks=peaks(printed['device']['kind']))


@pytest.fixture(scope='module')
def first_traces():
    out = {}
    for name in PINNED:
        trace = trace_reduce.load(str(DATA / f'{name}.xplane.pb.gz'))
        out[name] = _ctx(name, trace)
    return out


@pytest.mark.parametrize('name,metric', [
    (n, m) for n in sorted(PINNED) for m in sorted(PINNED[n])])
def test_earlier_readers_read_as_pinned(first_traces, name, metric):
    value = harness.metric_module(metric).read(first_traces[name])
    want = PINNED[name][metric]
    if want is None:
        assert value is None
    else:
        assert value == pytest.approx(want, rel=1e-12)


def test_the_first_traces_have_no_phase_scopes():
    """Recorded before the program had scopes: every op is unscoped."""
    for name in PINNED:
        trace = trace_reduce.load(str(DATA / f'{name}.xplane.pb.gz'))
        found, _ = phases.phase_seconds(trace)
        assert set(found) == {'unscoped'}


# -- synthetic traces ------------------------------------------------------------

def _synthetic():
    S = 'jit(run)/while/body/closed_call/'
    ops = [
        Op('while.1', 0, 100, 'jit(run)/while'),
        Op('gather_rows.6', 5, 15, S + 'repro.rows/jit(gather_rows)'),
        Op('fusion.1', 15, 45, S + 'repro.train/jit(_train_all)/'
           'transpose(jvp(cnn.conv1))/conv'),
        Op('fusion.2', 45, 55, S + 'repro.train/jit(_train_all)/'
           'jvp(cnn.pool2)/reduce_window'),
        Op('quantize_packed.6', 55, 60, S + 'repro.wire/quantize_packed'),
        Op('tier.6', 60, 80, S + 'repro.aggregate/safa_tier'),
        Op('fusion.3', 80, 82, S + 'repro.train/repro.wire/quantize'),
        Op('dynamic-slice.4', 82, 84, 'jit(run)/while/body/dynamic_slice'),
        Op('fusion.9', 110, 120, 'jit(_eval)/repro.eval/cnn.fc/dot'),
        Op('xor.1', 130, 140, ''),
    ]
    spans = [('bench_window', 0, 200), ('bench_run', 0, 150),
             ('bench_segment', 0, 105), ('bench_evaluate', 105, 125),
             ('bench_segment', 125, 150)]
    return Trace({'/device:TPU:0': ops}, spans)


def test_each_op_goes_to_its_innermost_phase():
    found, layers = phases.phase_seconds(_synthetic())
    ns = {k: round(v * 1e9) for k, v in found.items()}
    # the while loop's own 100 - 79 ns and the scan's slice are unscoped,
    # like the eager op; the wire scope nested in train counts as wire
    assert ns == {'rows': 10, 'train': 40, 'wire': 7, 'aggregate': 20,
                  'eval': 10, 'unscoped': 21 + 2 + 10}
    assert {k: round(v * 1e9) for k, v in layers.items()} == \
        {'conv': 30, 'pool': 10, 'fc': 10}


def test_phases_and_unscoped_add_up_to_busy_time():
    trace = _synthetic()
    found, _ = phases.phase_seconds(trace)
    assert sum(found.values()) == pytest.approx(
        trace_reduce.reduce(trace).busy_s, rel=1e-12)


def test_gaps_take_the_innermost_program_span():
    trace = _synthetic()
    program = [('repro.segment', 0, 100), ('repro.evaluate', 100, 124),
               ('repro.run.prepare', 126, 129)]
    gaps = dict(phases.named_gaps(trace, program))
    # 100-110 sits in repro.evaluate, 120-130 in bench_segment once the
    # program's evaluate span has closed (midpoint 125), 140-200 in the
    # window alone
    assert gaps == {'repro.evaluate': pytest.approx(10e-9),
                    'bench_segment': pytest.approx(10e-9),
                    'bench_window': pytest.approx(60e-9)}


def test_without_program_spans_gaps_are_named_as_before():
    trace = _synthetic()
    assert phases.named_gaps(trace, []) == trace_reduce.reduce(trace).gaps


# -- the counter readers ------------------------------------------------------------

COUNTER_READERS = ('tier_bytes_use.int8', 'tier_dma_ns.int8')


@pytest.mark.parametrize('metric', COUNTER_READERS)
def test_counter_readers_read_nothing_without_the_counter(monkeypatch,
                                                          first_traces,
                                                          metric):
    from repro import obs
    monkeypatch.setattr(obs, 'counters', lambda: {})
    assert harness.metric_module(metric).read(first_traces['tier_int8']) \
        is None


def test_counter_readers_divide_as_documented(monkeypatch, first_traces):
    from bench import work
    from bench.readers import calls_of, per_round
    from repro import obs
    kernel = 'safa_aggregate_packed_q8_tier_rows'
    monkeypatch.setattr(obs, 'counters', lambda: {
        kernel: {'bytes': 10 ** 9, 'dmas': 1000}})
    ctx = first_traces['tier_int8']
    s = ctx.shape
    need = per_round((s.committed, s.rows_written),
                     lambda up, cache: work.tier_q8_bytes(up, cache, s.n))
    secs, calls = ctx.trace.time_of(calls_of((kernel,)))
    use = harness.metric_module('tier_bytes_use.int8').read(ctx)
    ns = harness.metric_module('tier_dma_ns.int8').read(ctx)
    assert use == pytest.approx(100 * need / 1e9)
    assert ns == pytest.approx(1e9 * secs / (calls * 1000))


# -- traces recorded with the phase scopes ------------------------------------------

@pytest.fixture(scope='module', params=['tier_int8', 'dense'])
def scoped(request):
    name = request.param
    path = str(SCOPED / f'{name}.xplane.pb.gz')
    trace = trace_reduce.load(path)
    return name, path, trace, _ctx(name, trace, SCOPED)


def test_scoped_phases_add_up_to_busy_time(scoped):
    name, _, trace, ctx = scoped
    found, _ = phases.phase_seconds(trace)
    assert sum(found.values()) == pytest.approx(ctx.trace.busy_s, rel=1e-3)
    assert set(found) == {
        'tier_int8': {'rows', 'train', 'wire', 'aggregate', 'unscoped'},
        'dense': {'rows', 'train', 'aggregate', 'eval', 'unscoped'}}[name]


def test_train_phase_agrees_with_train_share(scoped):
    _, _, trace, ctx = scoped
    found, _ = phases.phase_seconds(trace)
    share = harness.metric_module('train_share').read(ctx)
    assert 100 * found['train'] / ctx.trace.busy_s == pytest.approx(
        share, abs=0.5)


def test_earlier_readers_read_what_the_harness_printed(scoped):
    name, _, _, ctx = scoped
    printed = json.loads((SCOPED / f'{name}.json').read_text())['metrics']
    for metric in ('idle_share', 'train_share'):
        assert harness.metric_module(metric).read(ctx) == pytest.approx(
            printed[metric]['value'], rel=1e-12)


def test_cnn_layers_sit_inside_training(scoped):
    name, _, trace, _ = scoped
    found, layers = phases.phase_seconds(trace)
    if name == 'tier_int8':
        assert layers == {}
        return
    assert set(layers) == {'conv', 'pool', 'fc'}
    # the eval phase runs the same layers, outside training
    assert sum(layers.values()) <= found['train'] + found['eval']


def test_boundary_gaps_carry_program_span_names(scoped):
    _, path, trace, ctx = scoped
    gaps = phases.named_gaps(trace, phases.program_spans(path))
    assert {n for n, _ in gaps} & {'repro.segment', 'repro.evaluate',
                                   'repro.run.prepare'}
    assert sum(g for _, g in gaps) == pytest.approx(
        sum(g for _, g in ctx.trace.gaps), rel=1e-12)


def test_counter_readers_on_the_recorded_tier_cell(monkeypatch, scoped):
    name, _, _, ctx = scoped
    from repro import obs
    counted = json.loads((SCOPED / 'counters.json').read_text())
    monkeypatch.setattr(obs, 'counters', lambda: counted)
    use = harness.metric_module('tier_bytes_use.int8').read(ctx)
    ns = harness.metric_module('tier_dma_ns.int8').read(ctx)
    if name == 'dense':
        assert use is None and ns is None       # no tier kernel ran
        return
    roofline = harness.metric_module('tier_roofline.int8').read(ctx)
    assert 0 < use <= 100 and ns > 0
    # the moved-bytes roofline cannot pass the chip's peak bandwidth
    assert 0 < 100 * roofline / use <= 100
