"""Tiny cells for driving the harness on the CPU: the same tasks,
mixes and limits as the benchmark's cells, at sizes a test can hold."""
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]

#: benchmark cell -> (configuration, mix) of its tiny stand-in
TINY = {
    'xdevice_1m.safa_tier_int8': (
        {'name': 'tiny_xdevice', 'task': 'scale',
         'sizes': dict(m=2000, d=16384, lr=0.3, env_seed=0),
         'protocol': dict(fraction=10 / 2000, lag_tolerance=40)},
        {'protocol': 'safa', 'rounds': 8,
         'exec': dict(use_kernel='packed', schedule='sparse_tier',
                      wire='int8', eval_every=4)}),
    'paper_cnn.safa_dense': (
        {'name': 'tiny_cnn', 'task': 'cnn',
         'sizes': dict(m=4, dataset_size=400, batch_size=10, epochs=1,
                       lr=0.01, t_lim=5600.0, crash_prob=0.3, env_seed=0),
         'protocol': dict(fraction=0.5, lag_tolerance=2),
         'model': dict(side=28, classes=10, c1=4, c2=6, hidden=16,
                       kernel=5)},
        {'protocol': 'safa', 'rounds': 4,
         'exec': dict(use_kernel='packed', schedule='dense', wire='f32',
                      eval_every=2)}),
}


def spec(cell: str, per_layer=(), mix=None) -> dict:
    """The harness's spec of ``cell`` at its tiny size, held to the
    cell's own limits; ``mix`` in place of its own."""
    config, own = TINY[cell]
    mix = own if mix is None else mix
    limits = json.loads((ROOT / 'bench' / 'limits' / f'{cell}.json')
                        .read_text())
    return {'workload': {'chips': 1}, 'config': config, 'mix': mix,
            'limits': limits,
            'end_to_end': [{'name': 'rounds_per_s', 'unit': 'rounds/s'},
                           {'name': 'peak_hbm_gb', 'unit': 'GB'},
                           {'name': 'setup_s', 'unit': 's'}],
            'per_layer': [{'name': n, 'unit': '%'} for n in per_layer]}
