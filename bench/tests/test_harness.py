"""The harness on the CPU: it refuses to run, finds its files by name,
and ``BENCHMARK.json`` keeps to the shape the harness reads."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCHMARK = json.loads((ROOT / 'BENCHMARK.json').read_text())
NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.-]{1,16}$')


def run(args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    return subprocess.run([sys.executable, 'bench/run.py', *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_a_tpu():
    out = run(['--workload', BENCHMARK['workloads'][0]['name'], '--seed',
               '2147483659', '--seconds', '1', '--trace', '0'])
    assert out.returncode != 0
    assert out.stdout == ''
    assert 'TPU' in out.stderr


def test_unknown_workload_is_an_error():
    out = run(['--workload', 'no_such.cell', '--seed', '1', '--seconds',
               '1', '--trace', '0'])
    assert out.returncode == 2 and out.stdout == ''
    assert "no workload 'no_such.cell'" in out.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / 'BENCHMARK.json', tmp_path)
    shutil.copytree(ROOT / 'bench', tmp_path / 'bench',
                    ignore=shutil.ignore_patterns('__pycache__'))
    out = run(['--workload', BENCHMARK['workloads'][0]['name'], '--seed',
               '1', '--seconds', '1', '--trace', '0'], cwd=tmp_path)
    assert out.returncode != 0 and out.stdout == ''


def _with(workload, **files):
    bench = json.loads(json.dumps(BENCHMARK))
    bench['workloads'].append(workload)
    for key, entry in files.items():
        bench[key].append(entry)
    return bench


def test_missing_config_or_mix_file_is_an_error():
    bench = _with({'name': 'ghost.safa_dense', 'config': 'ghost',
                   'traffic': 'safa_dense', 'chips': 1, 'why': 'x'},
                  configs={'name': 'ghost', 'source': 'x', 'reduced': [],
                           'file': 'bench/configs/ghost.json', 'why': 'x'})
    with pytest.raises(harness.SpecError, match='ghost.json'):
        harness.cell_spec('ghost.safa_dense', bench)
    bench = _with({'name': 'paper_cnn.ghost', 'config': 'paper_cnn',
                   'traffic': 'ghost', 'chips': 1, 'why': 'x'})
    with pytest.raises(harness.SpecError, match='ghost.json'):
        harness.cell_spec('paper_cnn.ghost', bench)
    bench = _with({'name': 'paper_cnn.nowhere', 'config': 'nowhere',
                   'traffic': 'safa_dense', 'chips': 1, 'why': 'x'})
    with pytest.raises(harness.SpecError, match='no configuration'):
        harness.cell_spec('paper_cnn.nowhere', bench)


def test_every_cell_finds_its_files():
    for wl in BENCHMARK['workloads']:
        spec = harness.cell_spec(wl['name'])
        assert spec['config']['name'] == wl['config']
        assert spec['mix']['rounds'] % spec['mix']['exec']['eval_every'] == 0
        assert set(spec['limits']) >= {'change_gap', 'window_vs_setup',
                                       'window_compiles'}
        assert {m['name'] for m in spec['end_to_end']} >= {'setup_s'}
        assert spec['per_layer']


@pytest.mark.parametrize('change,message', [
    (lambda c, m: m['exec'].update(wire='int4'), 'wire'),
    (lambda c, m: m['exec'].update(engine='loop'), 'exec keys'),
    (lambda c, m: m.update(arrivals='poisson'), 'mix keys'),
    (lambda c, m: m.update(protocol='fedbuff'), 'protocols/fedbuff.py'),
    (lambda c, m: m.pop('protocol'), 'names no protocol'),
    (lambda c, m: m['exec'].update(schedule='tree'), 'schedule'),
    (lambda c, m: c['protocol'].update(alpha=0.5), 'protocol takes'),
    (lambda c, m: c['protocol'].pop('lag_tolerance'), 'protocol takes'),
    (lambda c, m: c.update(task='lstm'), 'tasks/lstm.py'),
])
def test_what_the_reference_cannot_replay_is_refused(tmp_path, change,
                                                     message):
    """A mix or configuration file that asks for what the reference
    cannot replay is refused before anything runs."""
    for wl in BENCHMARK['workloads']:
        spec = harness.cell_spec(wl['name'])
        config = json.loads(json.dumps(spec['config']))
        mix = json.loads(json.dumps(spec['mix']))
        change(config, mix)
        with pytest.raises(harness.SpecError, match=message):
            harness.build.protocol_of(config, mix)
            harness.build.task_module(config)


def test_every_metric_has_a_reader_and_a_unit():
    for m in BENCHMARK['per_layer']:
        assert callable(harness.metric_module(m['name']).read)
    for m in BENCHMARK['end_to_end'] + BENCHMARK['per_layer']:
        assert NAME.match(m['name']) and UNIT.match(m['unit'])
        assert m['better'] in ('lower', 'higher')
    with pytest.raises(harness.SpecError):
        harness.metric_module('no_such_metric')


def test_benchmark_json_shape():
    keys = {'configs': {'name', 'source', 'file', 'reduced', 'why'},
            'workloads': {'name', 'config', 'traffic', 'chips', 'why'},
            'end_to_end': {'name', 'unit', 'better', 'bound', 'source'},
            'per_layer': {'name', 'unit', 'better', 'source', 'layer',
                          'moves'}}
    assert set(BENCHMARK) == set(keys) | {'command', 'paths', 'run_seconds'}
    for section, allowed in keys.items():
        names = [e['name'] for e in BENCHMARK[section]]
        assert len(names) == len(set(names))
        for e in BENCHMARK[section]:
            assert set(e) - {'workloads'} == allowed, e
            assert NAME.match(e['name'])
            for text in ('why', 'layer', 'source'):
                if text in e:
                    assert 1 <= len(e[text]) <= 200 and '\n' not in e[text]
    e2e = {m['name'] for m in BENCHMARK['end_to_end']}
    for m in BENCHMARK['per_layer']:
        assert m['moves'] in e2e
    for c in BENCHMARK['configs']:
        cfg = json.loads((ROOT / c['file']).read_text())
        assert cfg['name'] == c['name'] and cfg['reduced'] == c['reduced']
        assert cfg['source'] == c['source']
    assert 1 <= BENCHMARK['run_seconds'] <= 51


def test_a_new_mix_is_a_file_alone():
    """A mix the benchmark does not have yet -- the paper CNN under the
    lag-tier schedule and the int8 wire -- runs correct through the
    same files: the reference takes the wire from the mix, so it
    replays the int8 uplink the program sends."""
    import time

    from bench.tests import tiny
    mix = {'protocol': 'safa', 'rounds': 4,
           'exec': dict(use_kernel='packed', schedule='sparse_tier',
                        wire='int8', eval_every=2)}
    spec = tiny.spec('paper_cnn.safa_dense', mix=mix)
    result = harness.run_cell(spec, seed=4, seconds=1.0, trace=False,
                              chips=1, t_start=time.perf_counter(),
                              log=lambda *a, **k: None)
    assert result['correct'], result['checks']
    gap = result['checks']['change_gap']['value']
    # the same program against a reference with the f32 wire reads far
    # above what the int8 reference reads
    cell = harness.build.build(spec, 4)
    s = harness.set_up(cell, harness.Compiles(), time.perf_counter())
    got = s.first.capture
    harness.free(s)
    f32 = harness.build.build(
        tiny.spec('paper_cnn.safa_dense',
                  mix={**mix, 'exec': {**mix['exec'], 'wire': 'f32'}}), 4)
    assert f32.check(got)['change_gap'] > 10 * gap
