"""The program's own tracing (``repro.obs``): host spans kept only while
recording, phase scopes in the op metadata of the compiled round
programs, the tier-rows kernels' traffic counter, and the spans of
precompute, run preparation, segments and evaluation."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro import api, fedsim, obs
from repro.core import protocol
from repro.data import make_images, make_regression, partition
from repro.data.tasks import cnn_task, regression_task
from repro.kernels import safa_aggregate as sa

# -- host spans ---------------------------------------------------------------


def test_a_span_records_nothing_outside_recording():
    with obs.span('before'):
        pass
    with obs.recording() as spans:
        pass
    with obs.span('after'):
        pass
    assert spans == []


def test_spans_keep_name_parent_and_nesting():
    with obs.recording() as spans:
        with obs.span('outer'):
            with obs.span('inner'):
                pass
            with obs.span('inner2'):
                with obs.span('leaf'):
                    pass
    assert [(s.name, s.parent) for s in spans] == [
        ('inner', 'outer'), ('leaf', 'inner2'), ('inner2', 'outer'),
        ('outer', None)]
    by = {s.name: s for s in spans}
    for child, parent in (('inner', 'outer'), ('inner2', 'outer'),
                          ('leaf', 'inner2')):
        c, p = by[child], by[parent]
        assert p.start <= c.start <= c.end <= p.end
    own = obs.self_seconds(spans)
    dur = {s.name: s.end - s.start for s in spans}
    assert own['outer'] == pytest.approx(
        dur['outer'] - dur['inner'] - dur['inner2'])
    assert own['inner2'] == pytest.approx(dur['inner2'] - dur['leaf'])
    assert own['leaf'] == pytest.approx(dur['leaf'])


def test_recordings_nest_and_restore():
    with obs.recording() as outer:
        with obs.recording() as inner:
            with obs.span('a'):
                pass
        with obs.span('b'):
            pass
    assert [s.name for s in inner] == ['a']
    assert [s.name for s in outer] == ['b']


def test_a_phase_is_one_of_the_five():
    assert obs.PHASES == ('rows', 'train', 'wire', 'aggregate', 'eval')
    with pytest.raises(ValueError, match='unknown phase'):
        obs.scope('training')


# -- phase scopes in the compiled programs -----------------------------------

class _Lowered(Exception):
    pass


def _compiled_text(monkeypatch, exp, engine: str) -> str:
    """HLO of the segment program ``protocol.<engine>`` that the first
    ``run()`` segment of ``exp`` would dispatch, compiled and not run."""
    fn = getattr(protocol, engine)

    def compile_only(*args, **kw):
        raise _Lowered(fn.lower(*args, **kw).compile().as_text())

    monkeypatch.setattr(protocol, engine, compile_only)
    with pytest.raises(_Lowered) as caught:
        exp.compile().run(max_segments=1)
    return caught.value.args[0]


def _op_names(hlo: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def _cnn_experiment(**ex):
    env = fedsim.EnvSpec(m=4, crash_prob=0.3, dataset_size=64,
                         batch_size=8, epochs=1, t_lim=830.0, seed=3)
    x, y = make_images(n=64, seed=0)
    task = cnn_task(partition(x, y, env.build().partition_sizes, 8, seed=0),
                    lr=1e-3, epochs=1)
    return api.Experiment(task, env, api.SafaSpec(fraction=0.5),
                          api.ExecSpec(eval_every=2, **ex), rounds=2)


def _tier_experiment():
    from benchmarks.scale import ScaleTask, make_scale_env
    return api.Experiment(
        ScaleTask(), make_scale_env(400, 4),
        api.SafaSpec(fraction=4 / 400, lag_tolerance=20),
        api.ExecSpec(use_kernel='packed', schedule='sparse_tier',
                     wire='int8', eval_every=2), rounds=2)


CELLS = {
    'dense_packed': (lambda: _cnn_experiment(use_kernel='packed'),
                     'safa_run_scan', {'rows', 'train', 'aggregate'}),
    'tier_int8': (_tier_experiment, 'safa_run_scan_sparse_tier_packed',
                  {'rows', 'train', 'wire', 'aggregate'}),
}


@pytest.mark.parametrize('cell', sorted(CELLS))
def test_every_op_of_a_round_body_is_under_one_phase(monkeypatch, cell):
    make, engine, phases = CELLS[cell]
    names = _op_names(_compiled_text(monkeypatch, make(), engine))
    body = f'jit({engine})/while/body/closed_call/'
    in_body = [n for n in names if n.startswith(body)]
    assert in_body
    found = set()
    for n in in_body:
        scoped = re.findall(r'(?:^|/)repro\.(\w+)(?=/|$)', n)
        assert scoped, f'op outside every phase scope: {n}'
        found.add(scoped[-1])
    assert found == phases


def test_cnn_layers_are_scoped_forward_and_backward(monkeypatch):
    names = _op_names(_compiled_text(
        monkeypatch, _cnn_experiment(use_kernel='packed'), 'safa_run_scan'))
    train = [n for n in names if '/repro.train/' in n]
    for layer in ('conv1', 'pool1', 'conv2', 'pool2', 'fc'):
        assert any(f'jvp(cnn.{layer})/' in n for n in train), layer
        assert any(f'transpose(jvp(cnn.{layer}))/' in n for n in train), \
            layer
    # the layers sit inside the train phase, never in another (names
    # that do not start at the program are those of nested reducers)
    assert not [n for n in names if n.startswith('jit(') and 'cnn.' in n
                and '/repro.train/' not in n]


def test_evaluate_is_under_the_eval_phase():
    task = _cnn_experiment().task
    params = task.init_global(jax.random.PRNGKey(0))
    hlo = task._eval_jit.lower(params, task._test_x,
                               task._test_y).compile().as_text()
    names = _op_names(hlo)
    assert any('/repro.eval/' in n for n in names)
    assert any('repro.eval/cnn.conv1/' in n for n in names)


# -- the tier-rows traffic counter ---------------------------------------------

K, N, R = 3, 256, 16        # slots, packed width (two 128-lane tiles), rows


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


SLOT_IDS = (jax.ShapeDtypeStruct((K,), jnp.int32),) * 2
ROLE = jax.ShapeDtypeStruct((K,), jnp.bool_)


def _f32_call():
    return (lambda *a: sa.safa_aggregate_packed_tier_rows(*a, tile=128),
            (_f32(R, N), _f32(K, N), _f32(N), _f32(N), *SLOT_IDS,
             *(ROLE,) * 3, _f32(K)))


def _q8_call():
    return (lambda *a: sa.safa_aggregate_packed_q8_tier_rows(*a, tile=128),
            (jax.ShapeDtypeStruct((K, N), jnp.int8), _f32(K, N // 128),
             _f32(K, N), _f32(R, N), _f32(N), _f32(N), *SLOT_IDS,
             *(ROLE,) * 4, _f32(K)))


# Hand counts for K=3 slots, N=2 tiles of 128 lanes, a 16-row buffer:
# a (2, 3) grid of 6 steps, each with three explicit DMAs of one 8-row
# f32 group (4,096 bytes): 73,728 bytes.  Pipelined blocks move once
# per column tile where their index follows the tile (a slot's 8-row
# group, since K < 8; the global and agg rows in and out) and once in
# all where it does not (the [K, 1] role and weight columns).
#   f32:  trained 2 x 4,096; rows in and out 4 x 2 x 512;
#         3 roles + weights 4 x 32                      -> 86,144
#   int8: q 2 x 1,024; scales 2 x 32; base 2 x 4,096;
#         rows 4 x 2 x 512; 4 roles + weights 5 x 32     -> 88,288
COUNTS = {
    'safa_aggregate_packed_tier_rows': (_f32_call, 86_144),
    'safa_aggregate_packed_q8_tier_rows': (_q8_call, 88_288),
}


@pytest.mark.parametrize('wrapper', sorted(COUNTS))
def test_tier_counter_matches_a_hand_count(monkeypatch, wrapper):
    call, want_bytes = COUNTS[wrapper]
    fn, args = call()
    copies = []
    real_copy = sa._copy
    monkeypatch.setattr(sa, '_copy',
                        lambda *a: copies.append(1) or real_copy(*a))
    jax.clear_caches()
    jax.eval_shape(fn, *args)
    assert obs.counters()[wrapper] == {'dmas': 18, 'bytes': want_bytes}
    # the kernel body issues TIER_STEP_DMAS copies in each grid step
    assert len(copies) == sa.TIER_STEP_DMAS == 3


def test_pipelined_bytes_follow_block_index_changes():
    from jax.experimental import pallas as pl
    arr = jax.ShapeDtypeStruct((16, 512), jnp.float32)
    every_step = pl.BlockSpec((8, 128), lambda i, j: (j, i))
    per_tile = pl.BlockSpec((8, 128), lambda i, j: (0, i))
    once = pl.BlockSpec((8, 128), lambda i, j: (0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    block = 8 * 128 * 4
    assert sa.pipelined_bytes((4, 2), [every_step], [arr]) == 8 * block
    assert sa.pipelined_bytes((4, 2), [per_tile], [arr]) == 4 * block
    assert sa.pipelined_bytes((4, 2), [once, in_hbm], [arr, arr]) == block


# -- program spans --------------------------------------------------------------

@pytest.mark.parametrize('schedule,children', [
    ('dense', ['precompute.draw', 'precompute.events']),
    ('sparse_tier', ['precompute.draw', 'precompute.events',
                     'precompute.lower']),
])
def test_precompute_spans_nest_inside_precompute(schedule, children):
    env = fedsim.EnvSpec(m=50, crash_prob=0.3, dataset_size=506,
                         batch_size=5, epochs=3, t_lim=830.0, seed=3)
    exp = api.Experiment(None, env, api.SafaSpec(),
                         api.ExecSpec(schedule=schedule, numeric=False),
                         rounds=6)
    with obs.recording() as spans:
        exp.precompute()
        exp.precompute()            # cached: no second span
    assert [(s.name, s.parent) for s in spans] == \
        [(c, 'precompute') for c in children] + [('precompute', None)]
    own = obs.self_seconds(spans)
    assert all(own[name] >= 0 for name in own)


def test_a_run_records_prepare_segments_and_evaluations():
    env = fedsim.EnvSpec(m=5, crash_prob=0.3, dataset_size=506,
                         batch_size=5, epochs=3, t_lim=830.0, seed=3)
    x, y = make_regression()
    task = regression_task(partition(x, y, env.build().partition_sizes, 5,
                                     seed=1), lr=1e-3, epochs=3)
    runner = api.Experiment(task, env, api.SafaSpec(),
                            api.ExecSpec(eval_every=2), rounds=4).compile()
    with obs.recording() as spans:
        runner.run()
    top = [s.name for s in spans if s.parent is None]
    assert top == ['precompute', 'run.prepare', 'segment', 'evaluate',
                   'segment', 'evaluate']
    assert all(a.end <= b.start for a, b in zip(
        [s for s in spans if s.parent is None],
        [s for s in spans if s.parent is None][1:]))
