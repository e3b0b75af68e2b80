"""The Granite LoRA cell on the CPU, at a tiny size that keeps the
published period of ten layers (9 Mamba2, attention at index 5): a whole
run through the harness is correct at the cell's own limits, its
control and planted faults are not, and the per-layer reader of the
program's training counter reads a share in (0, 100]."""
import json
import pathlib
import time
import types

import pytest

from bench import calibrate, harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = 'granite_h_micro_lora.safa_dense'

#: published keys cut to CPU size; every other key is the configuration's
TINY_MODEL = dict(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=2, intermediate_size=128,
                  shared_intermediate_size=128, vocab_size=512,
                  mamba_d_state=16, mamba_n_heads=8, mamba_d_head=16,
                  mamba_chunk_size=16, attention_multiplier=1 / 16)
#: the tiny model's gradients are smaller than the published one's, so its
#: step is larger: enough that every A matrix (which trains only once B is
#: off zero) moves far above float32 round-off in the checked segment
TINY_SIZES = dict(m=4, seq=64, rows=48, min_rows=4, max_rows=20,
                  heldout_rows=2, upload_mb=0.1, lr=5.0)
TINY_MIX = {'protocol': 'safa', 'rounds': 4,
            'exec': dict(use_kernel='packed', schedule='dense', wire='f32',
                         eval_every=2)}


def tiny_spec(per_layer=()):
    config = json.loads((ROOT / 'bench' / 'configs'
                         / 'granite_h_micro_lora.json').read_text())
    config.update(TINY_MODEL)
    config['sizes'] = {**config['sizes'], **TINY_SIZES}
    config['documents'] = {**config['documents'], 'median': 24,
                           'longest': 128}
    limits = json.loads((ROOT / 'bench' / 'limits' / f'{CELL}.json')
                        .read_text())
    return {'workload': {'chips': 1}, 'config': config, 'mix': TINY_MIX,
            'limits': limits,
            'end_to_end': [{'name': 'rounds_per_s', 'unit': 'rounds/s'},
                           {'name': 'peak_hbm_gb', 'unit': 'GB'},
                           {'name': 'setup_s', 'unit': 's'}],
            'per_layer': [{'name': n, 'unit': '%'} for n in per_layer]}


def test_the_cell_finds_its_files():
    spec = harness.cell_spec(CELL)
    assert spec['config']['num_hidden_layers'] == 10
    assert spec['config']['layer_types'][:10].count('attention') == 1
    assert spec['config']['layer_types'][5] == 'attention'
    assert {m['name'] for m in spec['per_layer']} >= {'lm_flops_use', 'mfu'}


def test_sound_run_is_correct():
    result = harness.run_cell(tiny_spec(), seed=2**31 + 11, seconds=1.0,
                              trace=False, chips=1,
                              t_start=time.perf_counter(),
                              log=lambda *a, **k: None)
    assert result['correct'], result['checks']
    assert result['attempted'] >= 2


@pytest.mark.parametrize('seed', [3, 2**31 + 7])
def test_control_and_faults_fail_the_limit(seed):
    """The program's reading passes the limit; the control (the model,
    its SSD and its loss in bfloat16, the adapters' state float32) and
    the half-uploads fault each fail it, the control by 3x the
    program's reading at least."""
    r = calibrate.readings(tiny_spec(), seed)
    assert r['program']['correct'], json.dumps(r)
    assert not r['control']['correct'], json.dumps(r)
    assert not r['half_uploads']['correct'], json.dumps(r)
    assert r['control']['change_gap'] >= 3 * r['program']['change_gap']


def test_flops_use_reads_the_program_counter():
    """``lm_flops_use``: the committed clients' required FLOPs over what
    the program counted for one ``local_train`` call (all clients, with
    recomputation), from a traced tiny segment."""
    import numpy as np

    from bench import build

    spec = tiny_spec()
    cell = build.build(spec, 5)
    s = harness.set_up(cell, harness.Compiles(), time.perf_counter())
    harness.free(s)
    shape = cell.shape()
    reader = harness.metric_module('lm_flops_use')
    value = reader.read(types.SimpleNamespace(shape=shape, trace=None))
    assert value is not None and 0 < value <= 100
    from repro import obs
    counted = obs.counters()['lm_local_train']
    assert counted['clients'] == 4
    want = 100 * float(np.mean(shape.committed)) * shape.flops_per_client \
        / counted['flops']
    assert value == pytest.approx(want)
