"""Granite 4.0-H and its federated LoRA task against the plain reference
(``bench/tasks/granite_lora.py``: float32 at ``Precision.HIGHEST``, the
SSD in its quadratic form, plain attention), on seeded random weights,
at a tiny size that keeps the published period of ten layers (9 Mamba2,
attention at index 5).
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tasks import granite_lora as ref
from repro import api, obs
from repro.configs import get_config
from repro.core import protocol
from repro.data.lm_task import LoraLMTask
from repro.fedsim import EnvSpec
from repro.models import granite, lora, ssm
from repro.models.model import build_model

PUBLISHED = get_config('granite-4.0-h-micro')
#: the published period at CPU widths; chunk 16 puts 4 chunks in a row
TINY = dict(n_layers=10, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            vocab_size=512, ssm_state=16, ssm_headdim=16, ssm_chunk=16,
            q_block=16, kv_block=16, attention_multiplier=1 / 16)
SEQ = 64


def tiny_cfg(**kw):
    return dataclasses.replace(PUBLISHED, **{**TINY, **kw})


def ref_dims(cfg):
    """The reference's view of ``cfg``, from config.json's key names."""
    return ref.dims({
        'layer_types': list(cfg.layer_types),
        'num_hidden_layers': cfg.n_layers, 'mamba_expand': 2,
        'hidden_size': cfg.d_model, 'vocab_size': cfg.vocab_size,
        'shared_intermediate_size': cfg.d_ff, 'mamba_d_state': cfg.ssm_state,
        'mamba_n_heads': cfg.ssm_heads, 'mamba_d_head': cfg.ssm_headdim,
        'mamba_d_conv': ssm.CONV_K, 'mamba_chunk_size': cfg.ssm_chunk,
        'num_attention_heads': cfg.n_heads,
        'num_key_value_heads': cfg.n_kv_heads,
        'rms_norm_eps': cfg.rms_norm_eps,
        'embedding_multiplier': cfg.embedding_multiplier,
        'residual_multiplier': cfg.residual_multiplier,
        'attention_multiplier': cfg.attention_multiplier,
        'logits_scaling': cfg.logits_scaling})


def weights(cfg, seed=0, b_std=0.05):
    """Base and flat adapters with B drawn off zero, so that every
    adapter takes part in the output."""
    base = granite.init_base(jax.random.PRNGKey(seed), cfg)
    ad = lora.flat(granite.init_adapters(jax.random.PRNGKey(seed + 1), cfg))
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), len(ad))
    ad = {k: (v if k.endswith('.a') else b_std * jax.random.normal(kk,
                                                                   v.shape))
          for kk, (k, v) in zip(keys, sorted(ad.items()))}
    return base, ad


def tokens(cfg, n=1, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (n, SEQ), 0,
                              cfg.vocab_size)


def test_published_sizes():
    """The adapter and base counts the configuration states: one period
    of 9 Mamba2 + 1 attention layer at published widths."""
    cfg = dataclasses.replace(PUBLISHED, n_layers=10)
    assert cfg.kinds.count('attention') == 1 and cfg.kinds[5] == 'attention'
    assert granite.n_adapter_params(cfg) == 9 * 726_016 + 671_744
    assert granite.n_adapter_params(cfg) == 7_205_888
    assert build_model(cfg).n_params() == 951_991_232
    assert cfg.ssm_heads == 64 and cfg.head_dim == 64
    assert granite.runs(cfg) == [('mamba', 0, 5, 0), ('attention', 5, 6, 0),
                                 ('mamba', 6, 10, 5)]


def test_ssd_chunked_matches_the_quadratic_form():
    """``ssm.ssd_chunked`` (chunks of 16 and a carried state) against the
    reference's unchunked ``y = (L o C B^T) x`` over 64 positions.  Both
    are float32; on the CPU every matmul is float32, so the two agree to
    float32 round-off of sums over 64 terms (rtol 1e-4)."""
    t, h, p, n = SEQ, 8, 16, 16
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (t, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (t, h)) - 1.0)
    a = -jax.random.uniform(k[2], (h,), minval=1.0, maxval=16.0)
    b = jax.random.normal(k[3], (t, n))
    c = jax.random.normal(k[4], (t, n))
    got, _ = ssm.ssd_chunked(x[None], dt[None], a, b[None], c[None],
                             chunk=16, precision=ref.HIGHEST)
    want = ref._ssd_quadratic(x, dt, a, b, c, ref.HIGHEST)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_the_ssd_contracts_at_highest_precision():
    """The configuration states a float32 SSD: every contraction under
    ``lm.ssd`` carries ``Precision.HIGHEST`` (on a TPU the default is one
    bfloat16 pass), and the projections keep the default."""
    cfg = tiny_cfg()
    base, ad = weights(cfg)
    text = jax.jit(lambda b, a, t: granite.loss(b, lora.nest(a), t, cfg)) \
        .lower(base, ad, tokens(cfg)).as_text(debug_info=True)
    names = dict(re.findall(r'^#loc(\d+) = loc\("([^"]*)"', text, re.M))
    ssd, other = set(), set()
    for line in text.splitlines():
        if 'stablehlo.dot_general' not in line:
            continue
        precision = re.search(r'precision = \[(\w+), \w+\]', line).group(1)
        loc = re.search(r'loc\(#loc(\d+)\)', line).group(1)
        (ssd if 'lm.ssd' in names.get(loc, '') else other).add(precision)
    assert ssd == {'HIGHEST'} and other == {'DEFAULT'}


def _ssd_multi_operand(x, dt, A, B, C, chunk, initial_state, precision):
    """The chunked SSD as multi-operand einsums, one per block of the dual
    form: the same algebra as ``ssm.ssd_chunked`` contracted in another
    order."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    pad = (-s) % chunk
    x, dt, B, C = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                   for v in (x, dt, B, C))
    L = x.shape[1] // chunk
    xb = x.reshape(b, L, chunk, h, p)
    dtb = dt.reshape(b, L, chunk, h)
    Bb, Cb = B.reshape(b, L, chunk, n), C.reshape(b, L, chunk, n)
    dA = dtb * A
    dAc = jnp.cumsum(dA, axis=2)
    Lmat = jnp.exp(ssm.segsum(dA.transpose(0, 1, 3, 2)))
    scores = jnp.einsum('blin,bljn->blij', Cb, Bb, precision=precision)
    y_diag = jnp.einsum('blij,blhij,bljh,bljhp->blihp', scores, Lmat, dtb, xb,
                        precision=precision)
    decay_states = jnp.exp(dAc[:, :, -1:, :] - dAc)
    states = jnp.einsum('blcn,blch,blch,blchp->blhpn', Bb, decay_states, dtb,
                        xb, precision=precision)

    def step(carry, inp):
        st, dec = inp
        return carry * dec[:, :, None, None] + st, carry
    final, prev = jax.lax.scan(step, initial_state,
                               (states.transpose(1, 0, 2, 3, 4),
                                jnp.exp(dAc[:, :, -1, :]).transpose(1, 0, 2)))
    y_off = jnp.einsum('blcn,blhpn,blch->blchp', Cb,
                       prev.transpose(1, 0, 2, 3, 4), jnp.exp(dAc),
                       precision=precision)
    return (y_diag + y_off).reshape(b, -1, h, p)[:, :s], final


@pytest.mark.parametrize('s', [64, 53], ids=['whole-chunks', 'ragged-tail'])
def test_ssd_chunked_gradients_match_the_multi_operand_form(s):
    """Values, final state and the gradients with respect to every input
    of ``ssm.ssd_chunked`` (chunks of 16, a carried state) against the
    multi-operand contraction order, float32 at HIGHEST: the same sums
    grouped otherwise, so they agree to float32 round-off."""
    b, h, p, n, chunk = 2, 4, 8, 16, 16
    k = jax.random.split(jax.random.PRNGKey(7), 8)
    args = (jax.random.normal(k[0], (b, s, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, s, h)) - 1.0),
            -jax.random.uniform(k[2], (h,), minval=1.0, maxval=4.0),
            jax.random.normal(k[3], (b, s, n)),
            jax.random.normal(k[4], (b, s, n)),
            jax.random.normal(k[5], (b, h, p, n)))
    cot = (jax.random.normal(k[6], (b, s, h, p)),
           jax.random.normal(k[7], (b, h, p, n)))

    def program(x, dt, A, B, C, s0):
        return ssm.ssd_chunked(x, dt, A, B, C, chunk=chunk, initial_state=s0,
                               precision=ref.HIGHEST)

    def multi(*a):
        return _ssd_multi_operand(*a[:5], chunk, a[5], ref.HIGHEST)

    got, got_vjp = jax.vjp(program, *args)
    want, want_vjp = jax.vjp(multi, *args)
    names = ['y', 'final_state'] + [f'd{v}' for v in
                                    ('x', 'dt', 'A', 'B', 'C', 'state')]
    for name, g, w in zip(names, (*got, *got_vjp(cot)),
                          (*want, *want_vjp(cot)), strict=True):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-6 * scale, err_msg=name)


@pytest.mark.parametrize('dtype,rtol', [
    # float32 base: every op float32; the program's chunked SSD and flash
    # attention against the reference's quadratic forms: round-off only
    (jnp.float32, 2e-4),
    # bfloat16 base as the configuration runs it: the program rounds each
    # matmul operand to bfloat16 (2^-8 relative), the reference computes
    # in float32 on the same bfloat16-valued weights; ten layers of such
    # rounding stay within 2% of the loss's gradient
    (jnp.bfloat16, 2e-2),
])
def test_loss_and_gradients_match_the_reference(dtype, rtol):
    cfg = tiny_cfg(dtype=dtype)
    base, ad = weights(cfg)
    toks = tokens(cfg)[0]
    d = ref_dims(cfg)

    def program(a):
        return granite.loss(base, lora.nest(a), toks[None], cfg)

    def plain(a):
        return ref.ref_loss(base, a, toks, d, lora.ALPHA / lora.RANK,
                            ref.HIGHEST)
    lp, gp = jax.value_and_grad(program)(ad)
    lr_, gr = jax.value_and_grad(plain)(ad)
    assert abs(float(lp) - float(lr_)) <= rtol * abs(float(lr_))
    for k in gr:
        num = float(jnp.linalg.norm(gp[k] - gr[k]))
        assert num <= rtol * float(jnp.linalg.norm(gr[k])) + 1e-12, k


def test_forward_logits_match_the_reference():
    """The program's logits (float32 base) against the reference's
    layers and head at HIGHEST; float32 round-off only (atol 2e-5 on
    logits of order 0.1)."""
    cfg = tiny_cfg(dtype=jnp.float32)
    base, ad = weights(cfg)
    toks = tokens(cfg)[0]
    d = ref_dims(cfg)
    got = granite.logits(base, lora.nest(ad), toks[None], cfg)[0]
    want = ref.ref_logits(base, ad, toks, d, lora.ALPHA / lora.RANK,
                          ref.HIGHEST)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-5)


def test_zero_b_adapters_reproduce_the_base_exactly():
    """A fresh adapter (B = 0) adds exactly zero to every projection."""
    cfg = tiny_cfg()
    base = granite.init_base(jax.random.PRNGKey(0), cfg)
    fresh = granite.init_adapters(jax.random.PRNGKey(1), cfg)
    toks = tokens(cfg, n=2)
    np.testing.assert_array_equal(
        np.asarray(granite.logits(base, fresh, toks, cfg)),
        np.asarray(granite.logits(base, None, toks, cfg)))


def test_decode_steps_match_the_forward_pass():
    """The registry's serving surface: token-by-token decode through the
    Mamba2 states and the KV cache gives the full pass's logits (float32;
    round-off only)."""
    cfg = tiny_cfg(dtype=jnp.float32)
    model = build_model(cfg)
    base = model.init(jax.random.PRNGKey(0))
    toks = tokens(cfg, n=2)[:, :24]
    full = granite.logits(base, None, toks, cfg)
    cache = model.init_cache(2, 24)
    step = jax.jit(model.decode_step)
    for i in range(24):
        cache, out = step(base, cache, toks[:, i:i + 1])
        np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, i]),
                                   rtol=1e-4, atol=1e-5)


# -- the federated task ------------------------------------------------------------------

def make_task(cfg, m=4, rows=(3, 5, 4, 6), lr=0.5, seed=0):
    base = granite.init_base(jax.random.PRNGKey(seed), cfg)
    counts = np.asarray(rows[:m])
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(seed + 5),
                                         (int(counts.sum()), SEQ), 0,
                                         cfg.vocab_size), np.int32)
    return LoraLMTask(cfg, base, toks, offsets, counts, toks[:2], lr=lr,
                      steps=2)


def test_local_train_rows_is_local_train_row_for_row():
    cfg = tiny_cfg()
    task = make_task(cfg)
    g = task.init_global(jax.random.PRNGKey(7))
    g = {k: v + (0.01 if k.endswith('.b') else 0.0) for k, v in g.items()}
    stacked = protocol.broadcast_global(g, task.m)
    ops = task.operands()
    full = jax.jit(task.local_train)(stacked, 3, ops)
    rows = jnp.asarray([2, 0], jnp.int32)
    part = jax.jit(task.local_train_rows)(
        jax.tree.map(lambda a: a[rows], stacked), rows, 3, ops)
    for k in full:
        np.testing.assert_array_equal(np.asarray(full[k][rows]),
                                      np.asarray(part[k]))


def test_the_counter_is_recorded_at_trace_time():
    """``lm_local_train`` counts every client's steps with recomputation,
    when ``local_train`` is traced: nothing runs."""
    cfg = tiny_cfg()
    task = make_task(cfg)
    stacked = protocol.broadcast_global(task.init_global(
        jax.random.PRNGKey(0)), task.m)
    obs.count('lm_local_train')     # forget an earlier trace
    jax.eval_shape(task.local_train, stacked, 1, task.operands())
    counted = obs.counters()['lm_local_train']
    assert counted == {'flops': 4 * 2 * granite.train_flops(cfg, SEQ),
                       'tokens': 4 * 2 * SEQ, 'clients': 4}


def test_the_layer_scopes_reach_the_compiled_program():
    cfg = tiny_cfg()
    task = make_task(cfg)
    stacked = protocol.broadcast_global(task.init_global(
        jax.random.PRNGKey(0)), task.m)
    hlo = jax.jit(task.local_train).lower(stacked, 1, task.operands()) \
        .compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ('lm.mamba', 'lm.ssd', 'lm.conv', 'lm.attn', 'lm.mlp',
                  'lm.head'):
        assert any(scope in n for n in names), scope
    # the backward pass carries them too
    assert any('transpose' in n and 'lm.mlp' in n for n in names)


def _largest_constant(text: str) -> int:
    """Bytes of the largest array constant in StableHLO text."""
    sizes = {'f32': 4, 'bf16': 2, 'i32': 4, 'i8': 1, 'i1': 1, 'f16': 2,
             'ui32': 4}
    most = 0
    for shape in re.findall(r'stablehlo\.constant dense<[^>]*> : '
                            r'tensor<([0-9x]+[a-z0-9]+)>', text):
        *dims, dtype = shape.split('x')
        most = max(most, int(np.prod([int(x) for x in dims] or [1]))
                   * sizes.get(dtype, 4))
    return most


def test_the_segment_takes_base_and_tokens_as_arguments():
    """Lowered, the dense SAFA segment holds no constant above 1 MB: the
    2 MB bfloat16 base and the token rows are arguments.  The same
    segment with training closed over them embeds them."""
    cfg = tiny_cfg(vocab_size=8192)
    task = make_task(cfg)
    assert task.operands()['base']['embed'].nbytes >= 2 ** 20
    g = task.init_global(jax.random.PRNGKey(0))
    stacked = protocol.broadcast_global(g, task.m)
    k = 2
    sched = protocol.RoundSchedule(
        *[jnp.ones((k, task.m), bool)] * 5, jnp.arange(1, k + 1))
    w = jnp.full((task.m,), 1.0 / task.m)

    def lowered(train, extra):
        return protocol.safa_run_scan.lower(
            g, stacked, stacked, sched, w, local_train_fn=train,
            use_kernel='packed', wire='f32', extra=extra).as_text()
    assert _largest_constant(lowered(task.local_train,
                                     (task.operands(),))) < 2 ** 20
    closed = functools.partial(task.local_train, operands=task.operands())
    assert _largest_constant(lowered(closed, ())) >= 2 ** 20


class _Forwarding:
    """A task held the way a harness holds one: as ``.task``, its
    training forwarded with ``(stacked, round_idx)`` alone."""

    def __init__(self, task):
        self.task = task

    def init_global(self, key):
        return self.task.init_global(key)

    def local_train(self, stacked_params, round_idx):
        with jax.named_scope('harness'):
            return self.task.local_train(stacked_params, round_idx)

    def evaluate(self, global_params):
        return self.task.evaluate(global_params)


def test_a_wrapped_task_trains_as_the_task_itself():
    """Through a wrapper that forwards ``(stacked, round_idx)`` only, the
    run finds the task's operands, passes them as arguments, and gives
    the held-out losses of the task run unwrapped, bit for bit."""
    cfg = tiny_cfg()
    task = make_task(cfg)
    env = EnvSpec(m=4, crash_prob=0.3, dataset_size=18, batch_size=1,
                  epochs=1, t_lim=1e4, model_size_mb=0.1)
    spec = api.SafaSpec(fraction=0.5, lag_tolerance=5)
    ex = api.ExecSpec(schedule='dense', use_kernel='packed', wire='f32',
                      eval_every=2)

    def losses(t):
        hist = api.Experiment(t, env, spec, ex, rounds=4).compile().run()
        return [r.eval['loss'] for r in hist.records if r.eval]
    assert losses(_Forwarding(task)) == losses(task)


def test_a_segment_through_the_experiment_api():
    """Two 2-round segments through ``api.Experiment`` with the cell's
    exec spec: finite held-out losses, and a task with operands is
    refused where the operands path does not run."""
    cfg = tiny_cfg()
    task = make_task(cfg)
    env = EnvSpec(m=4, crash_prob=0.3, dataset_size=18, batch_size=1,
                  epochs=1, t_lim=1e4, model_size_mb=0.1)
    spec = api.SafaSpec(fraction=0.5, lag_tolerance=5)
    ex = api.ExecSpec(schedule='dense', use_kernel='packed', wire='f32',
                      eval_every=2)
    hist = api.Experiment(task, env, spec, ex, rounds=4).compile().run()
    losses = [r.eval['loss'] for r in hist.records if r.eval]
    assert len(losses) == 2 and all(np.isfinite(losses))
    with pytest.raises(ValueError, match='dense SAFA scan engine only'):
        api.Experiment(task, env, spec, dataclasses.replace(
            ex, schedule='sparse'), rounds=4).compile().run()
