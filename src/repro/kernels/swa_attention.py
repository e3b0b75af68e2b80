"""Flash-style sliding-window attention (Pallas TPU kernel).

Causal attention with an optional window: key j is visible to query i iff
0 <= i - j < window.  Online-softmax accumulation over KV blocks keeps the
working set at [block_q, block_k] in VMEM; out-of-band blocks (fully masked
by causality or the window) are skipped via ``pl.when``, so compute is
O(S * window) instead of O(S^2) — the TPU-native realisation of the
sliding-window attention used by h2o-danube3 (and the hybrid shared-attn
block).

Grid: (batch, head, num_q_blocks, num_kv_blocks); the KV-block axis is the
innermost (sequential accumulation into VMEM scratch).  GQA is handled by
mapping query head h to KV head h // (H // KH) in the K/V index maps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

NEG_INF = -1e30

#: Static alias inventory (see ``safa_aggregate.ALIAS_CONTRACTS`` for the
#: format): the attention output is a fresh buffer — no operand aliasing.
ALIAS_CONTRACTS = {
    '_kernel': ((),),
}


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale,
            window, block_q, block_k, n_kv_blocks, seq_len):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # causal: block relevant iff k_start <= q_end; window: k_end >= q_start - window + 1
    relevant = k_start <= q_start + block_q - 1
    if window is not None:
        relevant &= (k_start + block_k - 1) >= (q_start - window + 1)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32) * scale     # [bq, D]
        k = k_ref[0, :, 0, :].astype(jnp.float32)             # [bk, D]
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = (q_pos >= k_pos) & (k_pos < seq_len)
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=1)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr[:, None] + p @ v

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=('window', 'block_q', 'block_k'))
def swa_attention(q, k, v, *, window=None, block_q: int = 128,
                  block_k: int = 128):
    """q: [B, S, H, D]; k, v: [B, S, KH, D] (H % KH == 0).  Causal, with an
    optional sliding window.  Returns [B, S, H, D]."""
    B, S, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    scale = D ** -0.5

    pad = (-S) % max(block_q, block_k)
    if pad:
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        qp, kp, vp = q, k, v
    Sp = qp.shape[1]
    nq, nk = Sp // block_q, Sp // block_k

    kernel = functools.partial(
        _kernel, scale=scale, window=window, block_q=block_q, block_k=block_k,
        n_kv_blocks=nk, seq_len=S)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, 1, D), lambda b, h, qi, ki: (b, qi, h, 0)),
            pl.BlockSpec((1, block_k, 1, D), lambda b, h, qi, ki: (b, ki, h // G, 0)),
            pl.BlockSpec((1, block_k, 1, D), lambda b, h, qi, ki: (b, ki, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, D), lambda b, h, qi, ki: (b, qi, h, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        # the KV-block axis accumulates sequentially
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            'parallel', 'parallel', 'parallel', 'arbitrary')),
        interpret=backend.interpret(),
    )(qp, kp, vp)
    return out[:, :S]
