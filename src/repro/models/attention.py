"""Attention: GQA + RoPE + optional qk-norm + optional sliding window.

The prefill/train path is a pure-jnp flash-style implementation (scan over
KV blocks with an online softmax) so peak activation memory stays bounded at
[*, q_block, kv_block] instead of [*, seq, seq].  It doubles as the oracle
for the Pallas ``swa_attention`` kernel.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _block_mask(q_pos, k_pos, causal: bool, window: Optional[int]):
    """q_pos: [qb], k_pos: [kb] -> bool [qb, kb] (True = attend)."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), jnp.bool_)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


def flash_attention(q, k, v, *, causal=True, window=None, q_positions=None,
                    k_positions=None, q_block=512, kv_block=512, kv_valid=None,
                    scale=None):
    """Online-softmax attention.

    q: [B, Sq, H, D];  k, v: [B, Sk, KH, D]  (GQA: H % KH == 0).
    window: sliding-window size (keys with q_pos - k_pos >= window masked).
    kv_valid: optional scalar/int count of valid kv entries (decode caches).
    scale: the score scale, D ** -0.5 unless given.
    Returns [B, Sq, H, D].
    """
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    assert H % KH == 0
    G = H // KH
    scale = D ** -0.5 if scale is None else scale

    if q_positions is None:
        q_positions = jnp.arange(Sq, dtype=jnp.int32)
    if k_positions is None:
        k_positions = jnp.arange(Sk, dtype=jnp.int32)

    # Pad sequence dims to block multiples.
    pq = (-Sq) % q_block
    pk = (-Sk) % kv_block
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pq), constant_values=2**30)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, (0, pk), constant_values=-(2**30))
    Sq_p, Sk_p = q.shape[1], k.shape[1]
    nq, nk = Sq_p // q_block, Sk_p // kv_block

    # [B, nq, qb, KH, G, D] — keep the input dtype here: the kv-block scan
    # body upcasts AFTER the (GSPMD-inserted) gathers, so in-loop collective
    # traffic stays in bf16 (§Perf iteration 2).
    qr = q.reshape(B, nq, q_block, KH, G, D)
    kr = k.reshape(B, nk, kv_block, KH, D)
    vr = v.reshape(B, nk, kv_block, KH, D)
    qpos = q_positions.reshape(nq, q_block)
    kpos = k_positions.reshape(nk, kv_block)

    kv_limit = None if kv_valid is None else jnp.asarray(kv_valid, jnp.int32)

    def per_qblock(qb, qp):
        # qb: [B, qblock, KH, G, D]; qp: [qblock]; scale applied to the
        # f32 scores (not to the bf16 operand) for precision
        def body(carry, inp):
            m_i, l_i, acc = carry
            kb, vb, kp = inp
            # bf16 operands, f32 accumulation (MXU-native); keeps the
            # GSPMD-inserted K/V gathers in bf16 (§Perf iteration 5)
            s = jnp.einsum('bqhgd,bkhd->bqhgk', qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = _block_mask(qp, kp, causal, window)
            mask &= (kp >= 0)[None, :]  # exclude block-padding keys
            if kv_limit is not None:
                mask &= kp[None, :] < kv_limit
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_i - m_new)
            l_new = l_i * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum('bqhgk,bkhd->bqhgd', p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, q_block, KH, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, q_block, KH, G), jnp.float32)
        a0 = jnp.zeros((B, q_block, KH, G, D), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(
            body, (m0, l0, a0),
            (kr.transpose(1, 0, 2, 3, 4), vr.transpose(1, 0, 2, 3, 4), kpos))
        return acc / jnp.maximum(l_f, 1e-30)[..., None]

    out = jax.lax.map(lambda args: per_qblock(*args),
                      (qr.transpose(1, 0, 2, 3, 4, 5), qpos))  # [nq,B,qb,KH,G,D]
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq_p, H, D)
    return out[:, :Sq].astype(q.dtype)


def attention_ref(q, k, v, *, causal=True, window=None, q_positions=None,
                  k_positions=None, kv_valid=None):
    """Naive O(S^2)-memory oracle for tests."""
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    G = H // KH
    if q_positions is None:
        q_positions = jnp.arange(Sq, dtype=jnp.int32)
    if k_positions is None:
        k_positions = jnp.arange(Sk, dtype=jnp.int32)
    qf = q.astype(jnp.float32) * (D ** -0.5)
    qf = qf.reshape(B, Sq, KH, G, D)
    s = jnp.einsum('bqhgd,bkhd->bqhgk', qf, k.astype(jnp.float32))
    mask = _block_mask(q_positions, k_positions, causal, window)
    if kv_valid is not None:
        mask &= k_positions[None, :] < jnp.asarray(kv_valid, jnp.int32)
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum('bqhgk,bkhd->bqhgd', p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, D).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, window=None,
                     cache_positions=None, scale=None):
    """Single-step decode attention.

    q: [B, 1, H, D]; caches: [B, S, KH, D]; cache_len: int32 scalar — number
    of valid entries.  ``cache_positions`` supports ring-buffer (SWA) caches
    where slot index != token position; defaults to arange.  ``scale``:
    the score scale, D ** -0.5 unless given.
    """
    B, _, H, D = q.shape
    _, S, KH, _ = k_cache.shape
    G = H // KH
    scale = D ** -0.5 if scale is None else scale
    if cache_positions is None:
        cache_positions = jnp.arange(S, dtype=jnp.int32)[None, :] * jnp.ones((B, 1), jnp.int32)
    q_pos = jnp.asarray(cache_len, jnp.int32) - 1  # position of the new token
    qf = (q.astype(jnp.float32) * scale).reshape(B, KH, G, D)
    s = jnp.einsum('bhgd,bkhd->bhgk', qf, k_cache.astype(jnp.float32))
    valid = (cache_positions >= 0) & (cache_positions < cache_len)
    if window is not None:
        valid &= (q_pos - cache_positions) < window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum('bhgk,bkhd->bhgd', p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, D).astype(q.dtype)
