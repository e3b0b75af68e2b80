"""The plain reference: SAFA's numeric rounds written straight from the
paper's Eq. 3 and 6-8, dense over every client, in plain ``jax.numpy``.

It replays the role masks of ``events.safa_masks`` and imports nothing of
the program.  Local training and the model come from the task module's
own reference (``train``), and the uplink from ``wire_roundtrip``.  Every
client that takes a part in the replayed rounds holds a local model and a
cache entry; nothing is packed, tiered, gathered or fused.  The clients
that take no part hold the initial model throughout, so they enter
Eq. 7 as one weight on it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QBLOCK = 128        # values per quantisation block of the int8 wire


def wire_roundtrip(x, levels: int):
    """Block-quantise each client row of ``x`` [m, ...] to signed integers
    of ``levels`` steps a side (127 for int8, 7 for int4), one scale per
    ``QBLOCK`` consecutive values of the row, and dequantise."""
    m = x.shape[0]
    flat = x.reshape(m, -1).astype(jnp.float32)
    n = flat.shape[1]
    pad = (-n) % QBLOCK
    blocks = jnp.pad(flat, ((0, 0), (0, pad))).reshape(m, -1, QBLOCK)
    scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=2, keepdims=True),
                        1e-30) / levels
    q = jnp.clip(jnp.round(blocks / scale), -levels, levels)
    return (q * scale).reshape(m, -1)[:, :n].reshape(x.shape).astype(x.dtype)


def _where(mask, a, b):
    return jax.tree.map(
        lambda x, y: jnp.where(mask.reshape((-1,) + (1,) * (x.ndim - 1)),
                               x, y), a, b)


def _tile(g, m):
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (m,) + x.shape),
                        g)


@functools.partial(jax.jit, static_argnames=('train', 'levels'))
def safa_round(g, local, cache, sync, committed, picked, undrafted,
               deprecated, weights, rest, start, t, aux, *, train, levels):
    """One dense SAFA round over the clients replayed.  ``train(base, t,
    aux, committed)`` trains them (it may skip those not in
    ``committed``, whose training is discarded); ``aux`` carries their
    data.  ``levels`` 0 is the f32 wire.  ``rest`` is the summed weight
    of the clients that take no part, whose cache entry is ``start``."""
    m = weights.shape[0]
    base = _where(sync, _tile(g, m), local)                       # Eq. 3
    trained = train(base, t, aux, committed)
    if levels:
        trained = jax.tree.map(lambda x: wire_roundtrip(x, levels), trained)
    # a client that crashed or missed the deadline uploads nothing
    upload = _where(committed, trained, base)
    cache = _where(deprecated & ~picked, _tile(g, m), cache)      # Eq. 6
    cache = _where(picked, upload, cache)
    new_g = jax.tree.map(                                         # Eq. 7
        lambda c, s: jnp.sum(c * weights.reshape((-1,) + (1,) * (c.ndim - 1))
                             .astype(c.dtype), axis=0)
        + rest.astype(c.dtype) * s, cache, start)
    cache = _where(undrafted, upload, cache)                      # Eq. 8
    local = _where(committed, upload, base)
    return new_g, local, cache


def replay(start, masks, weights, rounds: int, *, train, aux=None,
           levels: int = 0, rest: float = 0.0):
    """The global model after the first ``rounds`` rounds of ``masks``
    (an ``events.Masks``), every client starting from ``start``.
    ``masks`` and ``weights`` cover the clients replayed; ``rest`` is the
    summed weight of the others, which take no part in these rounds."""
    m = weights.shape[0]
    g, local, cache = start, _tile(start, m), _tile(start, m)
    w = jnp.asarray(weights, jnp.float32)
    rest = jnp.float32(rest)
    roles = [jnp.asarray(getattr(masks, k)[:rounds]) for k in
             ('sync', 'committed', 'picked', 'undrafted', 'deprecated')]
    for i in range(rounds):
        g, local, cache = safa_round(g, local, cache,
                                     *(r[i] for r in roles), w, rest, start,
                                     jnp.int32(i + 1), aux, train=train,
                                     levels=levels)
    return g


def half_weights(w):
    """The weights of the first half of the clients, scaled to the same
    total: the mean taken over half of the uploads."""
    w = np.asarray(w, np.float64)
    keep = np.arange(w.shape[0]) < w.shape[0] // 2
    kept = np.where(keep, w, 0.0)
    return kept * (w.sum() / max(kept.sum(), 1e-300))


def change_gap(got: dict, want: dict, start: dict):
    """Worst leaf of ``|(got - start) - (want - start)| / scale`` in the
    2-norm, where scale is the larger of the leaf's own reference change
    and the median leaf's.  A leaf whose reference change is under a
    thousandth of the median leaf's moved by round-off alone and is left
    out.  Returns (gap, {leaf: its gap} of the leaves compared, leaves
    left out)."""
    norms, gaps = {}, {}
    for k in want:
        w = np.asarray(want[k], np.float64) - np.asarray(start[k], np.float64)
        p = np.asarray(got[k], np.float64) - np.asarray(start[k], np.float64)
        norms[k] = float(np.linalg.norm(w))
        gaps[k] = float(np.linalg.norm(p - w))
    med = float(np.median(list(norms.values())))
    kept = [k for k in want if norms[k] >= 1e-3 * med]
    if not kept or med == 0.0:
        return float('inf'), {}, len(want)
    per_leaf = {k: gaps[k] / max(norms[k], med) for k in kept}
    return max(per_leaf.values()), per_leaf, len(want) - len(kept)
