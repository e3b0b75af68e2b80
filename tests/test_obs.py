"""The program's own tracing (``repro.obs``): host spans kept only while
recording, phase scopes in the op metadata of the compiled round
programs, the traffic counters of the tier-rows kernels and the slab
gather, and the spans of precompute, run preparation, segments and
evaluation."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, fedsim, obs
from repro.core import protocol
from repro.data import make_images, make_regression, partition
from repro.data.tasks import cnn_task, regression_task
from repro.kernels import ops
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


# -- the tier-rows and slab gather traffic counters --------------------------

K, N, R = 3, 2048, 16       # slots, packed width (one 2048 granule), rows


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


SLOT_IDS = (jax.ShapeDtypeStruct((K,), jnp.int32),) * 2
ROLE = jax.ShapeDtypeStruct((K,), jnp.bool_)


def _slab(n):
    return _f32(R, n // 128, 128)


def _f32_args(n):
    return (_slab(n), _f32(K, n), _f32(n), _f32(n), *SLOT_IDS,
            *(ROLE,) * 3, _f32(K))


def _q8_args(n):
    return (jax.ShapeDtypeStruct((K, n), jnp.int8), _f32(K, n // 128),
            _f32(K, n), _slab(n), _f32(n), _f32(n), *SLOT_IDS,
            *(ROLE,) * 4, _f32(K))


WRAPPERS = {
    'safa_aggregate_packed_tier_rows': _f32_args,
    'safa_aggregate_packed_q8_tier_rows': _q8_args,
}


# Hand counts for K=3 slots over a 16-row slab buffer N=2048 wide, in
# column tiles of 1024 (a (2, 3) grid of 6 steps) or of 2048 (a (1, 3)
# grid of 3 steps).  Each step issues two explicit DMAs of one f32 row,
# 4,096 bytes at tile 1024 and 8,192 at 2048: 49,152 bytes either way.
# Pipelined blocks move once per column tile where their index follows
# the tile (a slot's 8-row group, since K < 8; the dense global and agg
# rows in and out) and once in all where it does not (the [K, 128] role
# and weight rows), so they too move the same bytes at either tile:
#   f32:  trained 8 x 8,192; rows in and out 4 x 8,192;
#         3 roles + weights 4 x 1,536                   -> 153,600
#   int8: q 8 x 2,048; scales 8 x 64; base 8 x 8,192;
#         rows 4 x 8,192; 4 roles + weights 5 x 1,536    -> 172,032
COUNTS = {
    'safa_aggregate_packed_tier_rows': 153_600,
    'safa_aggregate_packed_q8_tier_rows': 172_032,
}


@pytest.mark.parametrize('wrapper,tile,dmas', [
    *(pytest.param(w, 1024, 12, id=w) for w in sorted(COUNTS)),
    *(pytest.param(w, 2048, 6, id=f'{w}-tile2048') for w in sorted(COUNTS)),
])
def test_tier_counter_matches_a_hand_count(monkeypatch, wrapper, tile,
                                           dmas):
    fn = functools.partial(getattr(sa, wrapper), tile=tile)
    copies = []
    real_copy = sa._copy
    monkeypatch.setattr(sa, '_copy',
                        lambda *a: copies.append(1) or real_copy(*a))
    jax.clear_caches()
    jax.eval_shape(fn, *WRAPPERS[wrapper](N))
    assert obs.counters()[wrapper] == {'dmas': dmas,
                                       'bytes': COUNTS[wrapper]}
    # the kernel body issues TIER_STEP_DMAS copies in each grid step
    assert len(copies) == sa.TIER_STEP_DMAS == 2


@pytest.mark.parametrize('tile,dmas', [(1024, 6), (2048, 3)])
def test_gather_counter_matches_a_hand_count(monkeypatch, tile, dmas):
    """A slab gather of K=3 rows: each step copies one f32 row in by one
    DMA, 49,152 // 2 = 24,576 bytes at either tile; the [K, N] output
    moves once per column tile as the 8-row group that holds the K rows,
    8 x 8,192 = 65,536 bytes: 90,112 in all."""
    fn = functools.partial(ops.gather_rows, tile=tile)
    copies = []
    real_copy = sa._copy
    monkeypatch.setattr(sa, '_copy',
                        lambda *a: copies.append(1) or real_copy(*a))
    jax.clear_caches()
    jax.eval_shape(fn, _slab(N), SLOT_IDS[0])
    assert obs.counters()['gather_rows'] == {'dmas': dmas, 'bytes': 90_112}
    assert len(copies) == 1         # one copy a grid step


#: cache-read and cache-write slots of a round: disjoint, as the host
#: allocator makes them, but for the scratch row 15 that inert slots share
SRCS, DSTS = [0, 9, 15], [4, 10, 15]


def _values(structs, key):
    """Arrays for ``structs``: the slot ids above, then random values."""
    slots = iter((SRCS, DSTS))
    out = []
    for i, st in enumerate(structs):
        k = jax.random.fold_in(key, i)
        if st.dtype == jnp.int32:
            out.append(jnp.asarray(next(slots), jnp.int32))
        elif st.dtype == jnp.bool_:
            out.append(jax.random.bernoulli(k, 0.5, st.shape))
        elif st.dtype == jnp.int8:
            out.append(jax.random.randint(k, st.shape, -127, 128, jnp.int8))
        else:
            out.append(jax.random.uniform(k, st.shape, jnp.float32, -1, 1))
    return out


@pytest.mark.parametrize('wrapper', sorted(COUNTS))
def test_tier_default_tile_is_wide_and_bitwise_the_narrow_one(wrapper):
    """By default the tile is ``tier_tile``'s widest choice.  Every
    column's adds over the slots run in the same order at any width, so
    the outputs are bit for bit those of tile 1024; the same bytes move,
    in fewer DMAs by the ratio of the widths."""
    n = 2 * sa.DEFAULT_TILE
    args = _values(WRAPPERS[wrapper](n), jax.random.PRNGKey(15))
    fn = getattr(sa, wrapper)
    jax.clear_caches()
    narrow = fn(*args, tile=1024)
    narrow_count = obs.counters()[wrapper]
    wide = fn(*args)
    wide_count = obs.counters()[wrapper]
    for got, want in zip(wide, narrow):     # new_global, new_agg, new_buf
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert wide_count['bytes'] == narrow_count['bytes']
    assert narrow_count['dmas'] == wide_count['dmas'] * n // 1024


# xdevice_1m's packed width is 684 granules of 2048 lanes (684 = 4 x 9 x
# 19), of which the f32 kernel takes 57 and the int8 one 38 at a time;
# the paper CNN's is 167, a prime
@pytest.mark.parametrize('n,tiles', [
    (1_400_832, {'safa_aggregate_packed_tier_rows': 57 * 2048,
                 'safa_aggregate_packed_q8_tier_rows': 38 * 2048}),
    (342_016, dict.fromkeys(COUNTS, 2048))])
@pytest.mark.parametrize('wrapper', sorted(COUNTS))
def test_tier_tile_at_the_benchmark_widths(wrapper, n, tiles):
    """The tile a tier-rows call takes by default, read from its DMA
    count: two a step over an (n / tile, K) grid."""
    tile = tiles[wrapper]
    jax.clear_caches()
    jax.eval_shape(getattr(sa, wrapper), *WRAPPERS[wrapper](n))
    assert obs.counters()[wrapper]['dmas'] == \
        sa.TIER_STEP_DMAS * (n // tile) * K


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
