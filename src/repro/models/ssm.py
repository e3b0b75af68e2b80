"""Mamba2 / SSD (state-space duality) block [arXiv:2405.21060].

Chunked "dual form": quadratic attention-like computation inside chunks plus
a linear recurrence across chunk boundary states.  Decode is an O(1)
single-step state update, which is what makes the ssm/hybrid architectures
eligible for the 524k-token ``long_500k`` shape.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import common as cm


def segsum(x):
    """x: [..., T] -> cumulative segment sums [..., T, T]; entry (i, j) =
    sum_{k=j+1..i} x_k for i >= j, -inf above the diagonal."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    d = cs[..., :, None] - cs[..., None, :]
    i = jnp.arange(T)
    mask = i[:, None] >= i[None, :]
    return jnp.where(mask, d, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, chunk=128, initial_state=None,
                precision=None):
    """SSD scan in chunked dual form.

    x: [b, s, h, p]   inputs per head
    dt: [b, s, h]     softplus'd step sizes
    A: [h]            negative per-head decay rates (A = -exp(A_log))
    B, C: [b, s, n]   (single group, broadcast over heads)
    precision: of the float32 contractions (``jax.lax.Precision``); the
    backend's default unless given, which on a TPU is one bfloat16 pass.
    Returns (y [b, s, h, p], final_state [b, h, p, n]).
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    L = x.shape[1] // chunk

    xb = x.reshape(b, L, chunk, h, p).astype(jnp.float32)
    dtb = dt.reshape(b, L, chunk, h).astype(jnp.float32)
    Bb = B.reshape(b, L, chunk, n).astype(jnp.float32)
    Cb = C.reshape(b, L, chunk, n).astype(jnp.float32)

    dA = dtb * A.astype(jnp.float32)           # [b,L,c,h]
    dAc = jnp.cumsum(dA, axis=2)               # within-chunk cumsum
    # 1. intra-chunk (diagonal blocks)
    Lmat = jnp.exp(segsum(dA.transpose(0, 1, 3, 2)))        # [b,L,h,c,c]
    scores = jnp.einsum('blin,bljn->blij', Cb, Bb,
                        precision=precision)                 # [b,L,c,c]
    y_diag = jnp.einsum('blij,blhij,bljh,bljhp->blihp', scores, Lmat, dtb, xb,
                        precision=precision)
    # 2. chunk-final states
    decay_states = jnp.exp(dAc[:, :, -1:, :] - dAc)          # [b,L,c,h]
    states = jnp.einsum('blcn,blch,blch,blchp->blhpn', Bb, decay_states, dtb,
                        xb, precision=precision)
    # 3. inter-chunk recurrence
    chunk_decay = jnp.exp(dAc[:, :, -1, :])                  # [b,L,h]

    if initial_state is None:
        s0 = jnp.zeros((b, h, p, n), jnp.float32)
    else:
        s0 = initial_state.astype(jnp.float32)

    def scan_fn(carry, inp):
        st, dec = inp  # st: [b,h,p,n], dec: [b,h]
        new = carry * dec[:, :, None, None] + st
        return new, carry  # emit state *entering* this chunk

    final, prev_states = jax.lax.scan(
        scan_fn, s0, (states.transpose(1, 0, 2, 3, 4),
                      chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)       # [b,L,h,p,n]
    # 4. inter-chunk outputs
    state_decay_out = jnp.exp(dAc)                           # [b,L,c,h]
    y_off = jnp.einsum('blcn,blhpn,blch->blchp', Cb, prev_states,
                       state_decay_out, precision=precision)
    y = (y_diag + y_off).reshape(b, -1, h, p)[:, :s]
    return y.astype(x.dtype), final


def ssd_step(state, x_t, dt_t, A, B_t, C_t):
    """O(1) decode step.  state: [b,h,p,n]; x_t: [b,h,p]; dt_t: [b,h];
    B_t, C_t: [b,n].  Returns (new_state, y_t [b,h,p])."""
    state = state.astype(jnp.float32)
    dA = jnp.exp(dt_t.astype(jnp.float32) * A.astype(jnp.float32))  # [b,h]
    dBx = jnp.einsum('bh,bhp,bn->bhpn', dt_t.astype(jnp.float32),
                     x_t.astype(jnp.float32), B_t.astype(jnp.float32))
    new = state * dA[:, :, None, None] + dBx
    y = jnp.einsum('bhpn,bn->bhp', new, C_t.astype(jnp.float32))
    return new, y.astype(x_t.dtype)


def ssd_ref(x, dt, A, B, C, initial_state=None):
    """Sequential oracle (step-by-step recurrence) for tests."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    st = (jnp.zeros((b, h, p, n), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    ys = []
    for t in range(s):
        st, y = ssd_step(st, x[:, t], dt[:, t], A, B[:, t], C[:, t])
        ys.append(y)
    return jnp.stack(ys, axis=1), st


# ---------------------------------------------------------------------------
# Full Mamba2 block
# ---------------------------------------------------------------------------

CONV_K = 4  # depthwise causal conv kernel width


def init_mamba_block(key, d_model, d_state, headdim, dtype, expand=2):
    d_inner = expand * d_model
    n_heads = d_inner // headdim
    conv_ch = d_inner + 2 * d_state  # conv over (x, B, C)
    ks = cm.split_keys(key, 8)
    return {
        'in_proj': cm.param(ks[0], (d_model, 2 * d_inner + 2 * d_state + n_heads),
                            ('embed', 'ssm_inner'), dtype),
        'conv_w': cm.param(ks[1], (CONV_K, conv_ch), (None, 'ssm_inner'), dtype,
                           init=lambda k, s, d: (jax.random.normal(k, s) * 0.1).astype(d)),
        'conv_b': cm.param(ks[2], (conv_ch,), ('ssm_inner',), dtype, init=cm.zeros_init),
        'A_log': cm.param(ks[3], (n_heads,), (None,), jnp.float32,
                          init=lambda k, s, d: jnp.log(jax.random.uniform(k, s, minval=1.0, maxval=16.0)).astype(d)),
        'D': cm.param(ks[4], (n_heads,), (None,), jnp.float32, init=cm.ones_init),
        'dt_bias': cm.param(ks[5], (n_heads,), (None,), jnp.float32,
                            init=lambda k, s, d: jnp.log(jnp.expm1(jax.random.uniform(k, s, minval=1e-3, maxval=0.1))).astype(d)),
        'norm_scale': cm.param(ks[6], (d_inner,), ('ssm_inner',), jnp.float32, init=cm.zeros_init),
        'out_proj': cm.param(ks[7], (d_inner, d_model), ('ssm_inner', 'embed'), dtype),
    }


def _split_in_proj(zxbcdt, d_inner, d_state):
    z, x, B, C, dt = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner, 2 * d_inner + d_state,
                 2 * d_inner + 2 * d_state], axis=-1)
    return z, x, B, C, dt


def _causal_conv(xbc, w, b):
    """xbc: [batch, seq, ch]; w: [K, ch] depthwise causal conv."""
    K = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * w[i][None, None, :] for i in range(K))
    return jax.nn.silu(out + b[None, None, :])


def _einsum_dense(name, x, w):  # noqa: ARG001
    return jnp.einsum('...d,de->...e', x, w)


def apply_mamba_block(p, x, *, d_state, headdim, chunk=128, expand=2,
                      eps=1e-6, dense=_einsum_dense, ssd_precision=None):
    """The Mamba2 mixer.  ``dense(name, x, w)`` computes the projections
    ``in_proj`` and ``out_proj`` (a model may add adapters or change
    their precision); ``eps`` is the gated RMSNorm's; ``ssd_precision``
    that of ``ssd_chunked``'s contractions."""
    bsz, s, d_model = x.shape
    d_inner = expand * d_model
    n_heads = d_inner // headdim
    zxbcdt = dense('in_proj', x, p['in_proj'])
    z, xc, B, C, dt = _split_in_proj(zxbcdt, d_inner, d_state)
    with jax.named_scope('lm.conv'):
        xbc = _causal_conv(jnp.concatenate([xc, B, C], axis=-1),
                           p['conv_w'], p['conv_b'])
    xc, B, C = jnp.split(xbc, [d_inner, d_inner + d_state], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p['dt_bias'])
    A = -jnp.exp(p['A_log'].astype(jnp.float32))
    xh = xc.reshape(bsz, s, n_heads, headdim)
    with jax.named_scope('lm.ssd'):
        y, _ = ssd_chunked(xh, dt, A, B, C, chunk=chunk,
                           precision=ssd_precision)
    y = y + xh * p['D'][None, None, :, None].astype(y.dtype)
    y = y.reshape(bsz, s, d_inner)
    y = cm.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                    p['norm_scale'], eps)
    return dense('out_proj', y, p['out_proj'])


def init_mamba_cache(bsz, d_model, d_state, headdim, dtype, expand=2):
    d_inner = expand * d_model
    n_heads = d_inner // headdim
    conv_ch = d_inner + 2 * d_state
    return {
        'conv': jnp.zeros((bsz, CONV_K - 1, conv_ch), dtype),
        'ssm': jnp.zeros((bsz, n_heads, headdim, d_state), jnp.float32),
    }


def step_mamba_block(p, cache, x_t, *, d_state, headdim, expand=2, eps=1e-6):
    """x_t: [b, 1, d_model] -> (new_cache, y_t [b, 1, d_model])."""
    bsz, _, d_model = x_t.shape
    d_inner = expand * d_model
    n_heads = d_inner // headdim
    zxbcdt = jnp.einsum('bsd,de->bse', x_t, p['in_proj'])[:, 0]
    z, xc, B, C, dt = _split_in_proj(zxbcdt, d_inner, d_state)
    conv_in = jnp.concatenate([xc, B, C], axis=-1)           # [b, ch]
    conv_win = jnp.concatenate([cache['conv'], conv_in[:, None]], axis=1)  # [b,K,ch]
    conv_out = jnp.einsum('bkc,kc->bc', conv_win, p['conv_w']) + p['conv_b']
    conv_out = jax.nn.silu(conv_out)
    xc, B, C = jnp.split(conv_out, [d_inner, d_inner + d_state], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p['dt_bias'])
    A = -jnp.exp(p['A_log'].astype(jnp.float32))
    xh = xc.reshape(bsz, n_heads, headdim)
    new_ssm, y = ssd_step(cache['ssm'], xh, dt, A, B, C)
    y = y + xh * p['D'][None, :, None].astype(y.dtype)
    y = y.reshape(bsz, d_inner)
    y = cm.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                    p['norm_scale'], eps)
    y = jnp.einsum('bi,id->bd', y, p['out_proj'])
    new_cache = {'conv': conv_win[:, 1:], 'ssm': new_ssm}
    return new_cache, y[:, None, :]
