"""IBM Granite 4.0-H (``granitemoehybrid`` without experts): a hybrid of
Mamba2 and NoPE GQA attention mixers, each followed by a SwiGLU MLP.

Layer ``i`` is a Mamba2 mixer or a grouped-query attention mixer as
``layer_types[i]`` says (the published micro model puts attention at
layers 5, 15, 25 and 35 of 40).  Every block is::

    h = h + residual_multiplier * mixer(rmsnorm(h))
    h = h + residual_multiplier * mlp(rmsnorm(h))

with ``mlp(x) = output_linear(silu(g) * u)``, ``[g, u] = input_linear(x)``.
The embedding is scaled by ``embedding_multiplier``; attention has no
positional encoding and scores scaled by ``attention_multiplier``; the
head is the tied embedding, its logits divided by ``logits_scaling``.
Every RMSNorm, the Mamba2 gated one included, takes ``rms_norm_eps``.

The base's weights are frozen and held in bfloat16; its matmuls take
bfloat16 operands and accumulate in float32, and the residual stream,
the norms, the SSD scan and the loss are float32 (the SSD's contractions
at ``Precision.HIGHEST``, so a TPU runs them in float32 too, not as one
bfloat16 pass).  Low-rank adapters
(``repro.models.lora``) may sit on every projection: Mamba ``in_proj``
and ``out_proj``, attention ``q``/``k``/``v``/``o`` and the MLP's
``input_linear``/``output_linear``.  Runs of one mixer kind go through
``lax.scan``, one rematerialised layer per step.

Weights, stacked over the layers of each kind (``mamba``, ``attn``;
``mlp`` over all layers)::

    embed [V, D]                  final_norm [D]
    mamba: norm [Lm, D], in_proj [Lm, D, 2I + 2N + H], conv_w [Lm, 4, I + 2N],
           conv_b [Lm, I + 2N], dt_bias, A_log, D [Lm, H],
           norm_scale [Lm, I], out_proj [Lm, I, D]
    attn:  norm [La, D], q [La, D, Hq*d], k, v [La, D, Hkv*d], o [La, Hq*d, D]
    mlp:   norm [L, D], input_linear [L, D, 2F], output_linear [L, F, D]

A norm's gain is ``1 + norm`` (``common.rms_norm``).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import common as cm
from repro.models import lora
from repro.models import ssm
from repro.models.config import ModelConfig

MAMBA, ATTENTION = 'mamba', 'attention'
EXPAND = 2
#: tokens of one piece of the chunked loss head
HEAD_CHUNK = 512
#: standard deviation of the random base's matrices
STD = 0.02


@dataclasses.dataclass(frozen=True)
class GraniteConfig(ModelConfig):
    """``ModelConfig`` (``n_layers``, ``d_model``, heads, ``d_ff``,
    ``vocab_size``, ``ssm_state``/``ssm_headdim``/``ssm_chunk``) plus
    the keys of the published ``config.json`` that the shared fields do
    not hold.  Layer ``i`` is ``layer_types[i]``; ``n_layers`` may cut
    the stack to a prefix of them."""
    layer_types: tuple = ()
    attention_multiplier: float = 1.0
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    rms_norm_eps: float = 1e-5

    @property
    def kinds(self) -> tuple:
        return tuple(self.layer_types[:self.n_layers])

    @property
    def n_mamba(self) -> int:
        return self.kinds.count(MAMBA)

    @property
    def n_attn(self) -> int:
        return self.kinds.count(ATTENTION)

    @property
    def d_inner(self) -> int:
        return EXPAND * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.ssm_state

    @property
    def in_proj_width(self) -> int:
        return 2 * self.d_inner + 2 * self.ssm_state + self.ssm_heads


def runs(cfg: GraniteConfig):
    """Maximal runs of one mixer kind: ``[(kind, first layer, end
    layer, index of the first among layers of its kind)]``."""
    out, seen = [], {MAMBA: 0, ATTENTION: 0}
    for i, kind in enumerate(cfg.kinds):
        if out and out[-1][0] == kind:
            out[-1][2] = i + 1
        else:
            out.append([kind, i, i + 1, seen[kind]])
        seen[kind] += 1
    return [tuple(r) for r in out]


def base_shapes(cfg: GraniteConfig) -> dict:
    """``{group: {name: shape}}`` of the base (``embed``/``final_norm``
    under group ``''``)."""
    D, Lm, La, L = cfg.d_model, cfg.n_mamba, cfg.n_attn, cfg.n_layers
    H, hd = cfg.ssm_heads, cfg.head_dim
    return {
        '': {'embed': (cfg.vocab_size, D), 'final_norm': (D,)},
        'mamba': {'norm': (Lm, D), 'in_proj': (Lm, D, cfg.in_proj_width),
                  'conv_w': (Lm, ssm.CONV_K, cfg.conv_channels),
                  'conv_b': (Lm, cfg.conv_channels), 'dt_bias': (Lm, H),
                  'A_log': (Lm, H), 'D': (Lm, H),
                  'norm_scale': (Lm, cfg.d_inner),
                  'out_proj': (Lm, cfg.d_inner, D)},
        'attn': {'norm': (La, D), 'q': (La, D, cfg.n_heads * hd),
                 'k': (La, D, cfg.n_kv_heads * hd),
                 'v': (La, D, cfg.n_kv_heads * hd),
                 'o': (La, cfg.n_heads * hd, D)},
        'mlp': {'norm': (L, D), 'input_linear': (L, D, 2 * cfg.d_ff),
                'output_linear': (L, cfg.d_ff, D)},
    }


def lora_shapes(cfg: GraniteConfig) -> dict:
    """``{group: {projection: (layers, d_in, d_out)}}`` of the adapted
    projections."""
    shapes = base_shapes(cfg)
    targets = {'mamba': ('in_proj', 'out_proj'),
               'attn': ('q', 'k', 'v', 'o'),
               'mlp': ('input_linear', 'output_linear')}
    return {g: {n: shapes[g][n] for n in names}
            for g, names in targets.items()}


def init_base(key, cfg: GraniteConfig):
    """Random base weights: matrices normal(0, ``STD``), norm gains 1,
    conv weights normal(0, 0.1), Mamba2's ``A`` in [1, 16], ``dt`` in
    [1e-3, 0.1] after softplus, ``D`` 1."""
    shapes = base_shapes(cfg)
    leaves = [(g, n, s) for g in sorted(shapes) for n, s in
              sorted(shapes[g].items())]
    keys = jax.random.split(key, len(leaves))
    special = {
        'norm': lambda k, s: jnp.zeros(s), 'final_norm': lambda k, s:
        jnp.zeros(s), 'norm_scale': lambda k, s: jnp.zeros(s),
        'conv_b': lambda k, s: jnp.zeros(s), 'D': lambda k, s: jnp.ones(s),
        'conv_w': lambda k, s: 0.1 * jax.random.normal(k, s),
        'A_log': lambda k, s: jnp.log(jax.random.uniform(k, s, minval=1.0,
                                                         maxval=16.0)),
        'dt_bias': lambda k, s: jnp.log(jnp.expm1(jax.random.uniform(
            k, s, minval=1e-3, maxval=0.1))),
    }
    out = {g: {} for g in shapes if g}
    for k, (g, n, s) in zip(keys, leaves):
        make = special.get(n, lambda k, s: STD * jax.random.normal(k, s))
        value = make(k, s).astype(cfg.dtype)
        if g:
            out[g][n] = value
        else:
            out[n] = value
    return out


def init_adapters(key, cfg: GraniteConfig):
    shapes = lora_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    return {g: lora.init(k, shapes[g]) for k, g in zip(keys, sorted(shapes))}


def n_adapter_params(cfg: GraniteConfig) -> int:
    return sum(lora.count(s) for s in lora_shapes(cfg).values())


# -- the forward pass ---------------------------------------------------------------

def _take(tree, i):
    return None if tree is None else jax.tree.map(lambda a: a[i], tree)


def _norm(h, scale, cfg):
    return cm.rms_norm(h, scale, cfg.rms_norm_eps)


def _mamba(p, ad, x, cfg):
    def dense(name, x, w):
        return lora.dense(x, w, None if ad is None else ad[name])
    with jax.named_scope('lm.mamba'):
        return ssm.apply_mamba_block(
            p, x, d_state=cfg.ssm_state, headdim=cfg.ssm_headdim,
            chunk=cfg.ssm_chunk, expand=EXPAND, eps=cfg.rms_norm_eps,
            dense=dense, ssd_precision=jax.lax.Precision.HIGHEST)


def _qkv(p, ad, x, cfg):
    def dense(name):
        return lora.dense(x, p[name], None if ad is None else ad[name])
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = dense('q').reshape(b, s, cfg.n_heads, hd)
    k = dense('k').reshape(b, s, cfg.n_kv_heads, hd)
    v = dense('v').reshape(b, s, cfg.n_kv_heads, hd)
    return q, k, v


def _attention(p, ad, x, cfg):
    with jax.named_scope('lm.attn'):
        b, s, _ = x.shape
        q, k, v = _qkv(p, ad, x, cfg)
        o = attn_mod.flash_attention(
            q.astype(cfg.dtype), k.astype(cfg.dtype), v.astype(cfg.dtype),
            causal=True, scale=cfg.attention_multiplier,
            q_block=min(cfg.q_block, s), kv_block=min(cfg.kv_block, s))
        return lora.dense(o.reshape(b, s, -1), p['o'],
                          None if ad is None else ad['o'])


def _mlp(p, ad, x, cfg):
    with jax.named_scope('lm.mlp'):
        h = lora.dense(x, p['input_linear'],
                       None if ad is None else ad['input_linear'])
        gate, up = jnp.split(h, 2, axis=-1)
        return lora.dense(jax.nn.silu(gate) * up, p['output_linear'],
                          None if ad is None else ad['output_linear'])


def _block(kind, base, adapters, h, layer, k, cfg):
    """One layer: ``layer`` counts all layers, ``k`` those of ``kind``."""
    group = 'mamba' if kind == MAMBA else 'attn'
    p = _take(base[group], k)
    ad = None if adapters is None else _take(adapters[group], k)
    mixer = _mamba if kind == MAMBA else _attention
    h = h + cfg.residual_multiplier * mixer(p, ad, _norm(h, p['norm'], cfg),
                                            cfg)
    p = _take(base['mlp'], layer)
    ad = None if adapters is None else _take(adapters['mlp'], layer)
    return h + cfg.residual_multiplier * _mlp(p, ad, _norm(h, p['norm'], cfg),
                                              cfg)


def hidden(base, adapters, tokens, cfg: GraniteConfig):
    """tokens [B, S] -> the final norm's output [B, S, D], float32."""
    h = jnp.take(base['embed'], tokens, axis=0).astype(jnp.float32) \
        * cfg.embedding_multiplier
    for kind, first, end, k0 in runs(cfg):
        def body(h, idx, kind=kind):
            return _block(kind, base, adapters, h, idx[0], idx[1], cfg), None
        if cfg.remat:
            body = jax.checkpoint(body)
        h, _ = jax.lax.scan(body, h, (jnp.arange(first, end),
                                      jnp.arange(k0, k0 + end - first)))
    return _norm(h, base['final_norm'], cfg)


def head(base, h, cfg: GraniteConfig):
    """Logits [..., V] in float32 from final hidden states."""
    logits = jnp.einsum('...d,vd->...v', h.astype(base['embed'].dtype),
                        base['embed'], preferred_element_type=jnp.float32)
    return logits / cfg.logits_scaling


def logits(base, adapters, tokens, cfg: GraniteConfig):
    return head(base, hidden(base, adapters, tokens, cfg), cfg)


def loss(base, adapters, tokens, cfg: GraniteConfig):
    """Mean next-token cross-entropy of tokens [B, S] (positions 0..S-2
    predict 1..S-1).  The head runs in rematerialised pieces of
    ``HEAD_CHUNK`` positions, so no [B, S, V] logits are held."""
    h = hidden(base, adapters, tokens, cfg)
    b, s, _ = h.shape
    chunk = min(HEAD_CHUNK, s)
    if s % chunk:
        raise ValueError(f'{s} positions do not split into pieces of {chunk}')
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    valid = jnp.arange(s) < s - 1
    pieces = s // chunk

    @jax.checkpoint
    def piece(total, xs):
        hc, tc, vc = xs
        lg = head(base, hc, cfg)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, tc[..., None], axis=-1)[..., 0]
        return total + jnp.sum(jnp.where(vc, logz - gold, 0.0)), None

    def split(a):
        return jnp.moveaxis(a.reshape(b, pieces, chunk, *a.shape[2:]), 1, 0)
    with jax.named_scope('lm.head'):
        total, _ = jax.lax.scan(
            piece, jnp.float32(0.0),
            (split(h), split(targets), split(jnp.broadcast_to(valid, (b, s)))))
    return total / (b * (s - 1))


# -- work counts -----------------------------------------------------------------------

def train_flops(cfg: GraniteConfig, seq: int) -> int:
    """FLOPs the program performs for one training step of one sequence
    of ``seq`` tokens: the forward pass, the layers' and the head's
    recomputation, and the backward pass (input gradients of the frozen
    base's matmuls, input and weight gradients of the adapters).
    Attention is counted over every block ``flash_attention`` visits,
    the SSD as ``ssd_chunked`` computes it; elementwise work is left
    out."""
    D, V, r = cfg.d_model, cfg.vocab_size, lora.RANK
    base = lora_flops = 0
    for group in lora_shapes(cfg).values():
        for layers, d_in, d_out in group.values():
            base += 2 * layers * d_in * d_out
            lora_flops += 2 * layers * r * (d_in + d_out)
    c, H, P, N = cfg.ssm_chunk, cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    ssd = cfg.n_mamba * (2 * c * N + 2 * c * P * H + 4 * P * N * H)
    blocks = math.ceil(seq / min(cfg.kv_block, seq)) * min(cfg.kv_block, seq)
    attn = cfg.n_attn * 4 * blocks * cfg.n_heads * cfg.head_dim
    layers_fwd = base + lora_flops + ssd + attn
    head_fwd = 2 * D * V
    backward = base + 2 * lora_flops + 2 * ssd + 2 * attn + head_fwd
    # the head's pieces are always rematerialised, the layers under remat
    return seq * ((2 if cfg.remat else 1) * layers_fwd + 2 * head_fwd
                  + backward)


# -- serving (the registry's model facade) ----------------------------------------------

def init_cache(cfg: GraniteConfig, batch: int, max_len: int, length: int = 0):
    hd = cfg.head_dim
    return {
        'length': jnp.asarray(length, jnp.int32),
        'conv': jnp.zeros((cfg.n_mamba, batch, ssm.CONV_K - 1,
                           cfg.conv_channels), cfg.dtype),
        'ssm': jnp.zeros((cfg.n_mamba, batch, cfg.ssm_heads, cfg.ssm_headdim,
                          cfg.ssm_state), jnp.float32),
        'k': jnp.zeros((cfg.n_attn, batch, max_len, cfg.n_kv_heads, hd),
                       cfg.dtype),
        'v': jnp.zeros((cfg.n_attn, batch, max_len, cfg.n_kv_heads, hd),
                       cfg.dtype),
    }


def decode_step(base, cache, tokens, cfg: GraniteConfig):
    """tokens [B, 1] -> (the cache one token longer, logits [B, V])."""
    length = cache['length']
    h = jnp.take(base['embed'], tokens, axis=0).astype(jnp.float32) \
        * cfg.embedding_multiplier
    conv, state, kc, vc = (list(cache[k]) for k in ('conv', 'ssm', 'k', 'v'))
    counts = {MAMBA: 0, ATTENTION: 0}
    for layer, kind in enumerate(cfg.kinds):
        k = counts[kind]
        counts[kind] += 1
        if kind == MAMBA:
            p = _take(base['mamba'], k)
            x = _norm(h, p['norm'], cfg).astype(cfg.dtype)
            new, y = ssm.step_mamba_block(
                p, {'conv': conv[k], 'ssm': state[k]}, x,
                d_state=cfg.ssm_state, headdim=cfg.ssm_headdim,
                expand=EXPAND, eps=cfg.rms_norm_eps)
            conv[k], state[k] = new['conv'], new['ssm']
        else:
            p = _take(base['attn'], k)
            x = _norm(h, p['norm'], cfg)
            q, kk, vv = _qkv(p, None, x, cfg)
            kc[k] = jax.lax.dynamic_update_slice(
                kc[k], kk.astype(cfg.dtype), (0, length, 0, 0))
            vc[k] = jax.lax.dynamic_update_slice(
                vc[k], vv.astype(cfg.dtype), (0, length, 0, 0))
            o = attn_mod.decode_attention(q, kc[k], vc[k], length + 1,
                                          scale=cfg.attention_multiplier)
            y = lora.dense(o.reshape(h.shape[0], 1, -1), p['o'])
        h = h + cfg.residual_multiplier * y.astype(jnp.float32)
        p = _take(base['mlp'], layer)
        h = h + cfg.residual_multiplier * _mlp(p, None,
                                               _norm(h, p['norm'], cfg), cfg)
    out = head(base, _norm(h, base['final_norm'], cfg), cfg)[:, 0]
    new = {'length': length + 1}
    for key, layers in (('conv', conv), ('ssm', state), ('k', kc),
                        ('v', vc)):
        new[key] = jnp.stack(layers) if layers else cache[key]
    return new, out


class Model:
    """``repro.models.model.Model``'s surface for a Granite config: the
    base alone, no adapters."""

    def __init__(self, cfg: GraniteConfig):
        self.cfg = cfg

    def init(self, key):
        return init_base(key, self.cfg)

    def param_shapes(self):
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    def n_params(self) -> int:
        return sum(math.prod(s.shape)
                   for s in jax.tree.leaves(self.param_shapes()))

    def loss(self, params, batch):
        lg = logits(params, None, batch['tokens'], self.cfg)
        return cm.cross_entropy_loss(lg, batch['labels'], self.cfg.vocab_size,
                                     batch.get('loss_mask'))

    def init_cache(self, batch: int, max_len: int, length: int = 0):
        return init_cache(self.cfg, batch, max_len, length)

    def decode_step(self, params, cache, tokens):
        return decode_step(params, cache, tokens, self.cfg)
