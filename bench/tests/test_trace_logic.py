"""The trace reduction's interval arithmetic on hand-made traces."""
from bench import trace_reduce as tr
from bench.adapter import TRAIN_SCOPE


def test_busy_is_the_union_clipped_to_the_window():
    ops = [tr.Op('a', 0, 30), tr.Op('b', 20, 50), tr.Op('c', 70, 80),
           tr.Op('d', 95, 130)]
    busy, gaps = tr._busy(ops, 10, 100)
    assert busy == (50 - 10) + (80 - 70) + (100 - 95)
    assert gaps == [(50, 70), (80, 95)]


def test_own_time_leaves_out_nested_ops():
    # a while of 100 ns holding a loop of 60 ns holding two ops
    ops = [tr.Op('while.1', 0, 100), tr.Op('while.2', 10, 70),
           tr.Op('fusion.1', 10, 30), tr.Op('fusion.2', 40, 70),
           tr.Op('copy.1', 80, 90)]
    assert tr._own_times(ops, 0, 100) == [100 - 60 - 10, 60 - 20 - 30,
                                          20, 30, 10]


def test_base_name():
    assert tr.base_name('gather_rows.6') == 'gather_rows'
    assert tr.base_name('safa_aggregate_packed.3') == 'safa_aggregate_packed'
    assert tr.base_name('xor') == 'xor'


def test_reduce_names_gaps_after_the_innermost_span():
    scope = f'jit(f)/while/body/{TRAIN_SCOPE}/dot'
    trace = tr.Trace(
        devices={'/device:TPU:0': [
            tr.Op('while.1', 100, 500),
            tr.Op('fusion.1', 100, 400, scope),
            tr.Op('safa_aggregate_packed.3', 400, 500),
            tr.Op('fusion.2', 800, 900),
        ]},
        spans=[('bench_window', 100, 1100), ('bench_run', 100, 1000),
               ('bench_segment', 100, 600), ('bench_evaluate', 600, 700),
               ('bench_segment', 700, 1000)])
    r = tr.reduce(trace)
    assert abs(r.window_s - 1000e-9) < 1e-15
    assert abs(r.busy_s - 500e-9) < 1e-15
    assert abs(r.scope_s[TRAIN_SCOPE] - 300e-9) < 1e-15
    assert r.op_s['while.1'] == 0.0
    names = [g[0] for g in r.gaps]
    # gaps: 500-800 (mid 650, in evaluate), 900-1100 (mid 1000: the run
    # has closed, so the window)
    assert names == ['bench_evaluate', 'bench_window']
    secs, calls = r.time_of(
        lambda n: tr.base_name(n) == 'safa_aggregate_packed')
    assert calls == 1 and abs(secs - 100e-9) < 1e-15
