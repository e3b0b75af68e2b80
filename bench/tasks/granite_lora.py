"""Federated LoRA instruction tuning of Granite 4.0-H Micro (FedIT,
arXiv:2305.05644; OpenFedLLM, arXiv:2402.06954): m clients train rank-16
adapters on every projection of a frozen hybrid Mamba2/attention base
and upload only the adapters, which SAFA aggregates.

Made here from ``--seed``: the token shards (documents of log-normal
length with Zipf-distributed ids, packed with an end-of-sequence token
into rows), the frozen base (bfloat16) and the adapters' start.  The
program under test is handed them through its own ``LoraLMTask``.

The reference below is written from the published ``config.json`` and
the Mamba2 and LoRA papers in plain ``jax.numpy``, in float32 at
``Precision.HIGHEST`` on the bfloat16-valued base (cast to float32 where
a layer uses it).  Its SSD is the quadratic (dual) form
``y = (L o C B^T) x + D x`` over the whole sequence, not the program's
chunks; its attention is the plain masked softmax; each layer is one
Python step.  Layers, groups of SSD heads and pieces of the loss head
are rematerialised, so the reference fits the chip beside the program's
base.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic
from bench.cell import TaskParts

HIGHEST = jax.lax.Precision.HIGHEST
EOS = 0
#: heads of one piece of the reference's quadratic SSD
SSD_HEADS = 8
#: positions of one piece of the reference's loss head
HEAD_POSITIONS = 512


# -- the model's shapes, from the configuration's own keys -------------------------

def dims(config: dict) -> dict:
    c = config
    kinds = c['layer_types'][:c['num_hidden_layers']]
    inner = c['mamba_expand'] * c['hidden_size']
    return dict(
        kinds=kinds, D=c['hidden_size'], V=c['vocab_size'],
        F=c['shared_intermediate_size'], inner=inner,
        N=c['mamba_d_state'], H=c['mamba_n_heads'], P=c['mamba_d_head'],
        K=c['mamba_d_conv'], chunk=c['mamba_chunk_size'],
        Hq=c['num_attention_heads'], Hkv=c['num_key_value_heads'],
        hd=c['hidden_size'] // c['num_attention_heads'],
        Lm=kinds.count('mamba'), La=kinds.count('attention'), L=len(kinds),
        eps=c['rms_norm_eps'], emb=c['embedding_multiplier'],
        res=c['residual_multiplier'], att=c['attention_multiplier'],
        logit_div=c['logits_scaling'])


def base_shapes(d: dict) -> dict:
    """The frozen base, in the layout ``repro.models.granite`` reads."""
    conv = d['inner'] + 2 * d['N']
    Lm, La, L, D = d['Lm'], d['La'], d['L'], d['D']
    return {
        'embed': (d['V'], D), 'final_norm': (D,),
        'mamba': {'norm': (Lm, D),
                  'in_proj': (Lm, D, 2 * d['inner'] + 2 * d['N'] + d['H']),
                  'conv_w': (Lm, d['K'], conv), 'conv_b': (Lm, conv),
                  'dt_bias': (Lm, d['H']), 'A_log': (Lm, d['H']),
                  'D': (Lm, d['H']), 'norm_scale': (Lm, d['inner']),
                  'out_proj': (Lm, d['inner'], D)},
        'attn': {'norm': (La, D), 'q': (La, D, d['Hq'] * d['hd']),
                 'k': (La, D, d['Hkv'] * d['hd']),
                 'v': (La, D, d['Hkv'] * d['hd']),
                 'o': (La, d['Hq'] * d['hd'], D)},
        'mlp': {'norm': (L, D), 'input_linear': (L, D, 2 * d['F']),
                'output_linear': (L, d['F'], D)},
    }


def lora_shapes(d: dict) -> dict:
    """{group: {projection: (layers, d_in, d_out)}}: every projection."""
    s = base_shapes(d)
    return {'mamba': {n: s['mamba'][n] for n in ('in_proj', 'out_proj')},
            'attn': {n: s['attn'][n] for n in 'qkvo'},
            'mlp': {n: s['mlp'][n] for n in ('input_linear',
                                             'output_linear')}}


def make_base(key, d: dict, std: float = 0.02):
    """Seeded random base, bfloat16: matrices normal(0, std), norm gains 1
    (stored as 0: a gain is 1 + stored), conv weights normal(0, 0.1),
    conv bias 0, A in [1, 16], dt in [1e-3, 0.1] after softplus, D 1."""
    shapes = base_shapes(d)
    flat, tree = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(
        x, tuple))
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0]]
    keys = jax.random.split(key, len(flat))
    out = []
    for k, shape, path in zip(keys, flat, paths):
        name = path[-1].key
        if name in ('norm', 'final_norm', 'norm_scale', 'conv_b'):
            v = jnp.zeros(shape)
        elif name == 'D':
            v = jnp.ones(shape)
        elif name == 'conv_w':
            v = 0.1 * jax.random.normal(k, shape)
        elif name == 'A_log':
            v = jnp.log(jax.random.uniform(k, shape, minval=1.0, maxval=16.0))
        elif name == 'dt_bias':
            v = jnp.log(jnp.expm1(jax.random.uniform(k, shape, minval=1e-3,
                                                     maxval=0.1)))
        else:
            v = std * jax.random.normal(k, shape)
        out.append(v.astype(jnp.bfloat16))
    return jax.tree.unflatten(tree, out)


def make_adapters(key, d: dict, rank: int):
    """``{'<group>.<projection>.a' | '.b': [layers, ...]}``: A normal with
    standard deviation 1/sqrt(d_in), B zero (LoRA)."""
    out = {}
    shapes = lora_shapes(d)
    for gk, g in zip(jax.random.split(key, len(shapes)), sorted(shapes)):
        names = sorted(shapes[g])
        for k, n in zip(jax.random.split(gk, len(names)), names):
            layers, d_in, d_out = shapes[g][n]
            out[f'{g}.{n}.a'] = jax.random.normal(
                k, (layers, d_in, rank), jnp.float32) * d_in ** -0.5
            out[f'{g}.{n}.b'] = jnp.zeros((layers, rank, d_out), jnp.float32)
    return out


def _nest(adapters):
    """``{group: {projection: {'a', 'b'}}}`` of the flat adapters."""
    out = {}
    for path, v in adapters.items():
        g, n, ab = path.split('.')
        out.setdefault(g, {}).setdefault(n, {})[ab] = v
    return out


# -- the traffic: token shards, made from the seed -----------------------------------

def make_rows(rows: int, seq: int, vocab: int, seed: int, *,
              median: float, sigma: float, longest: int, zipf: float):
    """[rows, seq] int32: documents of log-normal length (``median``,
    ``sigma``, cut at ``longest``), ids 1..vocab-1 drawn Zipf(``zipf``)
    by rank, each followed by ``EOS``, packed end to end."""
    rng = np.random.default_rng([seed, 1])
    ranks = np.arange(1, vocab, dtype=np.float64)
    cdf = np.cumsum(ranks ** -zipf)
    cdf /= cdf[-1]
    need = rows * seq
    mean = median * math.exp(sigma * sigma / 2)
    lengths = np.minimum(np.maximum(np.rint(median * np.exp(
        sigma * rng.standard_normal(int(2 * need / mean) + 16))), 1),
        longest).astype(np.int64)
    lengths = lengths[:np.searchsorted(np.cumsum(lengths + 1), need) + 1]
    ids = 1 + np.searchsorted(cdf, rng.random(int(lengths.sum())),
                              side='right')
    ids = np.minimum(ids, vocab - 1)
    stream = np.full(int(lengths.sum() + len(lengths)), EOS, np.int32)
    ends = np.cumsum(lengths + 1)
    body = np.ones(len(stream), bool)
    body[ends - 1] = False
    stream[body] = ids
    return stream[:need].reshape(rows, seq)


# -- the model, written from the published configuration ------------------------------

def _rms(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1 + gain)


def _proj(x, w, ad, scale, precision):
    y = jnp.dot(x, w, precision=precision)
    low = jnp.dot(x, ad['a'], precision=precision)
    return y + scale * jnp.dot(low, ad['b'], precision=precision)


def _segsum(x):
    """[..., T] -> [..., T, T]: entry (i, j) = x[j+1] + ... + x[i] for
    i >= j, -inf above the diagonal (summed along i, not differenced)."""
    t = x.shape[-1]
    rep = jnp.broadcast_to(x[..., None, :], x.shape + (t,))
    rep = jnp.swapaxes(rep, -1, -2)                      # [..., i, j] = x[i]
    low = jnp.tril(jnp.ones((t, t), bool), -1)
    s = jnp.cumsum(jnp.where(low, rep, 0), axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)


def _ssd_quadratic(x, dt, A, B, C, precision):
    """x [T, H, P], dt [T, H], A [H], B, C [T, N] -> y [T, H, P]:
    y_i = sum_{j <= i} (C_i . B_j) exp(sum_{k=j+1..i} dt_k A) dt_j x_j,
    in pieces of ``SSD_HEADS`` heads."""
    cb = jnp.dot(C, B.T, precision=precision)                    # [T, T]
    h = x.shape[1]
    step = min(SSD_HEADS, h)

    @jax.checkpoint
    def piece(args):
        xg, dtg, ag = args                      # [T, g, P], [T, g], [g]
        decay = jnp.exp(_segsum((dtg * ag).T))                 # [g, T, T]
        mix = cb[None] * decay * dtg.T[:, None, :]
        return jnp.einsum('gij,jgp->igp', mix, xg, precision=precision)
    groups = (x.reshape(x.shape[0], h // step, step, -1).transpose(1, 0, 2, 3),
              dt.reshape(dt.shape[0], h // step, step).transpose(1, 0, 2),
              A.reshape(h // step, step))
    y = jax.lax.map(piece, groups)                        # [G, T, g, P]
    return y.transpose(1, 0, 2, 3).reshape(x.shape)


def _mamba(p, ad, x, d, scale, precision):
    t = x.shape[0]
    inner, N, H, P = d['inner'], d['N'], d['H'], d['P']
    zxbcdt = _proj(x, p['in_proj'], ad['in_proj'], scale, precision)
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:2 * inner + 2 * N]
    dt = zxbcdt[:, 2 * inner + 2 * N:]
    k = p['conv_w'].shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype),
                              xbc])
    conv = sum(padded[i:i + t] * p['conv_w'][i] for i in range(k))
    xbc = jax.nn.silu(conv + p['conv_b'])
    xs, B, C = xbc[:, :inner], xbc[:, inner:inner + N], xbc[:, inner + N:]
    dt = jax.nn.softplus(dt + p['dt_bias'])
    A = -jnp.exp(p['A_log'])
    xh = xs.reshape(t, H, P)
    y = _ssd_quadratic(xh, dt, A, B, C, precision) + xh * p['D'][:, None]
    y = _rms(y.reshape(t, inner) * jax.nn.silu(z), p['norm_scale'], d['eps'])
    return _proj(y, p['out_proj'], ad['out_proj'], scale, precision)


def _attention(p, ad, x, d, scale, precision):
    t = x.shape[0]
    q = _proj(x, p['q'], ad['q'], scale, precision).reshape(t, d['Hq'],
                                                             d['hd'])
    k = _proj(x, p['k'], ad['k'], scale, precision).reshape(t, d['Hkv'],
                                                             d['hd'])
    v = _proj(x, p['v'], ad['v'], scale, precision).reshape(t, d['Hkv'],
                                                             d['hd'])
    rep = d['Hq'] // d['Hkv']
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum('ihd,jhd->hij', q, k, precision=precision) * d['att']
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum('hij,jhd->ihd', jax.nn.softmax(s, axis=-1), v,
                   precision=precision)
    return _proj(o.reshape(t, -1), p['o'], ad['o'], scale, precision)


def _mlp(p, ad, x, d, scale, precision):
    h = _proj(x, p['input_linear'], ad['input_linear'], scale, precision)
    gate, up = h[:, :d['F']], h[:, d['F']:]
    return _proj(jax.nn.silu(gate) * up, p['output_linear'],
                 ad['output_linear'], scale, precision)


def _at(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def ref_hidden(base, adapters, tokens, d, scale, precision,
               dtype=jnp.float32):
    """The final norm's output [T, D] for one row ``tokens`` [T],
    computed in ``dtype``; the base's weights and the adapters are cast
    to it where a layer uses them."""
    adapters = jax.tree.map(lambda a: a.astype(dtype), _nest(adapters))
    h = base['embed'][tokens].astype(dtype) * d['emb']
    seen = {'mamba': 0, 'attention': 0}
    for layer, kind in enumerate(d['kinds']):
        k = seen[kind]
        seen[kind] += 1
        group = 'mamba' if kind == 'mamba' else 'attn'
        mixer = _mamba if kind == 'mamba' else _attention

        @jax.checkpoint
        def block(h, p, ad, pm, am, mixer=mixer):
            p, pm = jax.tree.map(lambda a: a.astype(dtype), (p, pm))
            h = h + d['res'] * mixer(p, ad, _rms(h, p['norm'], d['eps']), d,
                                     scale, precision)
            return h + d['res'] * _mlp(pm, am, _rms(h, pm['norm'], d['eps']),
                                       d, scale, precision)
        h = block(h, _at(base[group], k), _at(adapters[group], k),
                  _at(base['mlp'], layer), _at(adapters['mlp'], layer))
    return _rms(h, base['final_norm'].astype(dtype), d['eps'])


def ref_logits(base, adapters, tokens, d, scale, precision,
               dtype=jnp.float32):
    """Logits [T, V] of one row: the tied head over ``logits_scaling``."""
    h = ref_hidden(base, adapters, tokens, d, scale, precision, dtype)
    return jnp.dot(h, base['embed'].astype(h.dtype).T,
                   precision=precision) / d['logit_div']


def ref_loss(base, adapters, tokens, d, scale, precision,
             dtype=jnp.float32):
    """Mean next-token cross-entropy of one row ``tokens`` [T], the head
    in pieces of ``HEAD_POSITIONS`` positions, computed in ``dtype``."""
    h = ref_hidden(base, adapters, tokens, d, scale, precision, dtype)
    t = tokens.shape[0]
    pieces = t // min(HEAD_POSITIONS, t)
    targets = jnp.concatenate([tokens[1:], tokens[:1]])
    valid = jnp.arange(t) < t - 1

    @jax.checkpoint
    def piece(args):
        hc, tc, vc = args
        lg = jnp.dot(hc, base['embed'].astype(dtype).T,
                     precision=precision) / d['logit_div']
        nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, tc[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(vc, nll, 0))
    total = jax.lax.map(piece, (h.reshape(pieces, -1, h.shape[-1]),
                                targets.reshape(pieces, -1),
                                valid.reshape(pieces, -1)))
    return jnp.sum(total) / (t - 1)


def ref_train(base, t, aux, committed, *, d, lr, steps, scale, precision,
              dtype=jnp.float32):
    """Each client that commits this round: ``steps`` SGD steps, step j
    on row (steps * (t - 1) + j) mod n_k of its rows, one client after
    another, the loss and its gradient computed in ``dtype`` and the
    step taken on the adapters in their own (float32).  A client that
    crashes or misses the deadline uploads nothing, so it is not
    trained."""
    frozen, tokens, offsets, counts = aux
    rows = offsets[:, None] + (steps * (t - 1) + jnp.arange(steps))[None] \
        % counts[:, None]

    def client(ad, rows):
        def step(ad, row):
            g = jax.grad(ref_loss, argnums=1)(frozen, ad, tokens[row], d,
                                              scale, precision, dtype)
            return jax.tree.map(lambda a, b: a - jnp.asarray(lr, a.dtype) * b,
                                ad, g), None
        return jax.lax.scan(step, ad, rows)[0]

    def one(args):
        ad, rows, go = args
        return jax.lax.cond(go, client, lambda ad, _: ad, ad, rows)
    return jax.lax.map(one, (base, rows, committed))


# -- required work, from logical shapes ------------------------------------------------

def train_flops_per_token(d: dict, seq: int, rank: int) -> int:
    """FLOPs one training token requires: the forward pass; the backward
    pass's input gradients of the frozen base's matmuls (no weight
    gradient) and the head's; input and weight gradients of the
    adapters; the SSD in its chunked form and causal attention, each
    forward and twice that backward.  No recomputation; elementwise work
    left out."""
    base = lora = 0
    for group in lora_shapes(d).values():
        for layers, d_in, d_out in group.values():
            base += 2 * layers * d_in * d_out
            lora += 2 * layers * rank * (d_in + d_out)
    c, H, P, N = d['chunk'], d['H'], d['P'], d['N']
    ssd = d['Lm'] * (2 * c * N + 2 * c * P * H + 4 * P * N * H)
    attn = d['La'] * 2 * seq * d['Hq'] * d['hd']     # causal: half of 4 S
    head = 2 * d['D'] * d['V']
    return 2 * base + 3 * lora + 3 * ssd + 3 * attn + 2 * head


# -- the task ---------------------------------------------------------------------------------

def _check(config: dict):
    """The published keys the program and this reference assume."""
    want = {'mamba_n_groups': 1, 'mamba_expand': 2, 'mamba_d_conv': 4,
            'mamba_conv_bias': True, 'mamba_proj_bias': False,
            'attention_bias': False, 'tie_word_embeddings': True,
            'position_embedding_type': 'nope', 'num_local_experts': 0,
            'normalization_function': 'rmsnorm', 'hidden_act': 'silu'}
    bad = {k: config.get(k) for k, v in want.items() if config.get(k) != v}
    if bad:
        raise ValueError(f'granite_lora runs none of {bad}')


def build(config: dict, seed: int) -> TaskParts:
    from repro.data.lm_task import LoraLMTask
    from repro.fedsim import EnvSpec
    from repro.models.granite import GraniteConfig

    _check(config)
    d = dims(config)
    s, lc, doc = config['sizes'], config['lora'], config['documents']
    rank, seq, steps = lc['rank'], s['seq'], s['local_steps']
    scale = lc['alpha'] / rank
    env_spec = EnvSpec(m=s['m'], crash_prob=s['crash_prob'],
                       dataset_size=s['rows'], batch_size=s['env_batch_rows'],
                       epochs=1, t_lim=s['t_lim'],
                       model_size_mb=s['upload_mb'], seed=s['env_seed'],
                       draw_seed=seed)
    sizes = env_spec.build().partition_sizes
    counts = np.clip(np.rint(sizes / sizes.sum() * s['rows']).astype(int),
                     s['min_rows'], s['max_rows'])
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    held = s['heldout_rows']
    rows = make_rows(int(counts.sum()) + held, seq, d['V'], seed,
                     median=doc['median'], sigma=doc['sigma'],
                     longest=doc['longest'], zipf=doc['zipf'])
    base = jax.jit(functools.partial(make_base, d=d))(
        jax.random.fold_in(traffic.seed_key(seed), 1))
    program_cfg = GraniteConfig(
        arch_id='granite-4.0-h-micro', family='granite',
        n_layers=config['num_hidden_layers'], d_model=d['D'],
        n_heads=d['Hq'], n_kv_heads=d['Hkv'], d_ff=d['F'], vocab_size=d['V'],
        ssm_state=d['N'], ssm_headdim=d['P'], ssm_chunk=d['chunk'],
        layer_types=tuple(config['layer_types']),
        attention_multiplier=d['att'], embedding_multiplier=d['emb'],
        residual_multiplier=d['res'], logits_scaling=d['logit_div'],
        rms_norm_eps=d['eps'])
    task = LoraLMTask(program_cfg, base, rows[:-held],
                      offsets, counts, rows[-held:], lr=s['lr'], steps=steps)
    init = functools.partial(make_adapters, d=d, rank=rank)
    trains = {lower: functools.partial(
        ref_train, d=d, lr=s['lr'], steps=steps, scale=scale,
        precision=None if lower else HIGHEST,
        dtype=jnp.bfloat16 if lower else jnp.float32)
        for lower in (False, True)}

    def pieces(start, clients, *, lower, fault):
        """The whole adapter set in one piece.  ``lower``: the model,
        its SSD and its loss computed in bfloat16 at the default
        precision, the adapters' state and its SGD steps still float32
        from the same start."""
        del fault
        start = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), start)
        aux = (base, jnp.asarray(rows[:-held]),
               jnp.asarray(offsets[clients], jnp.int32),
               jnp.asarray(counts[clients], jnp.int32))
        return [(start, trains[lower], aux)]

    n = sum(layers * rank * (d_in + d_out)
            for g in lora_shapes(d).values()
            for layers, d_in, d_out in g.values())
    return TaskParts(
        program=task, init=init, env_spec=env_spec,
        n=n,
        flops_per_client=steps * seq * train_flops_per_token(d, seq, rank),
        bytes_per_client=steps * seq * 4,
        pieces=pieces, join=lambda outs: outs[0])
