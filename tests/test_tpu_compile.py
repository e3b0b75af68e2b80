"""The main path's Pallas kernels, compiled for a TPU v5e that is described
but not attached.

The chip's compiler is installed with JAX: ``get_topology_desc`` describes
a v5e:2x2 slice, and each test compiles one kernel for one of its chips at
the width the paper-scale round uses, then checks that the compiled
program holds the Mosaic kernel (``tpu_custom_call``).  Nothing runs;
each compile takes a second or two.  What the compiler refuses here
(blocks not aligned to the chip's tiles, more VMEM than a kernel may
use) interpret mode on the CPU cannot show.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import obs
from repro.kernels import comm_quant, ops, safa_aggregate
from repro.models import ssm

#: PAPER_TASKS['task2_cnn']: clients, and the paper CNN's packed width
M, N = 100, 342_016
#: PAPER_TASKS['task3_svm'] clients; its 36 floats pack to one 2048 tile
M_SVM, N_SVM = 500, 2048
#: tier width of the task2 round: K active slots over a value buffer of
#: R rows (capacity + 1, padded to whole 8-row groups)
K, R = 40, 64
#: xdevice_1m's lag-tier round: K slots over a 1.4M-wide pack, and a
#: value buffer of the cell's capacity (410) + 1 rows, padded to 8
K_1M, N_1M, R_1M = 251, 1_400_832, 416
#: granite_h_micro_lora's SSD: one 2048-token row, 64 heads of 64, state
#: 128, chunks of 256
T_SSD, H_SSD, P_SSD, N_SSD, CHUNK_SSD = 2048, 64, 64, 128, 256

F32, I8, I32, BOOL, BF16 = (jnp.float32, jnp.int8, jnp.int32, jnp.bool_,
                            jnp.bfloat16)


@pytest.fixture(scope='module')
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_for_tpu(one_chip, monkeypatch):
    """Compile ``fn`` for the described chip from (shape, dtype) pairs and
    return the compiled program.  Kernels are traced for the TPU, and
    JAX's persistent compilation cache is off: a compile for a described
    chip is written to it but cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.kernels import backend
    monkeypatch.setattr(backend, 'kernel_platform', lambda: 'tpu')
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    jax.clear_caches()      # no trace made earlier in interpret mode

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile()

    yield compile_
    jax.clear_caches()      # no TPU trace left for later tests
    jax.config.update('jax_enable_compilation_cache', cache_on)


@pytest.fixture
def compile_for_tpu(compiled_for_tpu):
    """As ``compiled_for_tpu``, returning the compiled text."""
    return lambda fn, *shapes: compiled_for_tpu(fn, *shapes).as_text()


def _dense_operands(m, n):
    return [((m, n), F32), ((m, n), F32), ((n,), F32), ((m,), BOOL),
            ((m,), BOOL), ((m,), BOOL), ((m,), F32)]


def test_safa_aggregate_packed_paper_cnn(compile_for_tpu):
    text = compile_for_tpu(safa_aggregate.safa_aggregate_packed,
                           *_dense_operands(M, N))
    assert 'tpu_custom_call' in text


def test_safa_aggregate_packed_svm_m500(compile_for_tpu):
    """m=500 whole client columns: three f32 operands double-buffered at
    the default tile would pass v5e's 16 MiB scoped VMEM."""
    text = compile_for_tpu(safa_aggregate.safa_aggregate_packed,
                           *_dense_operands(M_SVM, N_SVM))
    assert 'tpu_custom_call' in text


def test_quantize_packed_paper_cnn(compile_for_tpu):
    text = compile_for_tpu(comm_quant.quantize_packed, ((M, N), F32))
    assert 'tpu_custom_call' in text


def test_safa_aggregate_packed_q8_paper_cnn(compile_for_tpu):
    text = compile_for_tpu(
        safa_aggregate.safa_aggregate_packed_q8,
        ((M, N), I8), ((M, N // comm_quant.QBLOCK), F32), ((M, N), F32),
        ((M, N), F32), ((N,), F32), ((M,), BOOL), ((M,), BOOL),
        ((M,), BOOL), ((M,), BOOL), ((M,), F32))
    assert 'tpu_custom_call' in text


@pytest.mark.parametrize('kernel,slab', [('gather_rows', False),
                                         ('scatter_rows', False),
                                         ('gather_rows', True)],
                         ids=['gather_rows', 'scatter_rows',
                              'gather_rows-slab'])
def test_rows_kernels_tier_width(compile_for_tpu, kernel, slab):
    shapes = [(_buffer(R, N, slab), F32), ((K,), I32)]
    if kernel == 'scatter_rows':
        shapes.append(((K, N), F32))
    text = compile_for_tpu(getattr(ops, kernel), *shapes)
    assert 'tpu_custom_call' in text


def _buffer(r, n, slab=True):
    """Shape of a value buffer of r rows over width n: the lag-tier row
    slab [r, n/128, 128], or the [r, n] buffer of the sparse engines."""
    return (r, n // 128, 128) if slab else (r, n)


def _tier_operands(wrapper, k, n, r):
    slots = [((k,), I32)] * 2
    if wrapper == 'safa_aggregate_packed_tier_rows':
        return [(_buffer(r, n), F32), ((k, n), F32), ((n,), F32),
                ((n,), F32), *slots, *[((k,), BOOL)] * 3, ((k,), F32)]
    return [((k, n), I8), ((k, n // comm_quant.QBLOCK), F32), ((k, n), F32),
            (_buffer(r, n), F32), ((n,), F32), ((n,), F32), *slots,
            *[((k,), BOOL)] * 4, ((k,), F32)]


TIER_WRAPPERS = ['safa_aggregate_packed_tier_rows',
                 'safa_aggregate_packed_q8_tier_rows']


@pytest.mark.parametrize('wrapper', TIER_WRAPPERS)
def test_tier_rows_tier_width(compile_for_tpu, wrapper):
    text = compile_for_tpu(getattr(safa_aggregate, wrapper),
                           *_tier_operands(wrapper, K, N, R))
    assert 'tpu_custom_call' in text


@pytest.mark.parametrize('wrapper', TIER_WRAPPERS)
def test_tier_rows_xdevice_width(compile_for_tpu, wrapper):
    """At xdevice_1m's width the default tile is wider than the pack
    granule, and the compiler takes its blocks into VMEM."""
    text = compile_for_tpu(getattr(safa_aggregate, wrapper),
                           *_tier_operands(wrapper, K_1M, N_1M, R_1M))
    assert 'tpu_custom_call' in text
    steps = obs.counters()[wrapper]['dmas'] // safa_aggregate.TIER_STEP_DMAS
    tile = N_1M * K_1M // steps
    assert tile > safa_aggregate.DEFAULT_TILE and N_1M % tile == 0


def test_gather_rows_slab_xdevice_width(compile_for_tpu):
    """The slab gather at xdevice_1m's width takes a tile wider than the
    pack granule too: one row copy a step over an (N / tile, K) grid."""
    text = compile_for_tpu(ops.gather_rows, (_buffer(R_1M, N_1M), F32),
                           ((K_1M,), I32))
    assert 'tpu_custom_call' in text
    tile = N_1M * K_1M // obs.counters()['gather_rows']['dmas']
    assert tile > safa_aggregate.DEFAULT_TILE and N_1M % tile == 0


def test_ssd_granite_width_bytes(compiled_for_tpu):
    """One SSD forward and backward at Granite's width, float32 at
    HIGHEST, reads and writes under 1.5 GB: its contractions keep the
    [b, L, h, c, c] products out of HBM (multi-operand einsums that
    materialise them read 4.0 GB)."""
    def fwd_bwd(x, dt, A, B, C):
        def loss(*a):
            y, _ = ssm.ssd_chunked(*a, chunk=CHUNK_SSD,
                                   precision=jax.lax.Precision.HIGHEST)
            return jnp.sum(y.astype(F32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)

    compiled = compiled_for_tpu(
        fwd_bwd, ((1, T_SSD, H_SSD, P_SSD), BF16), ((1, T_SSD, H_SSD), F32),
        ((H_SSD,), F32), ((1, T_SSD, N_SSD), BF16), ((1, T_SSD, N_SSD), BF16))
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost['bytes accessed'] < 1.5e9
