"""Cross-device SAFA at a million clients: the server path at scale.

``ScaleTask`` and ``make_scale_env`` follow ``benchmarks/scale.py``: the
task holds no [m, ...] tensors, since client k's target is a function of
k, and local training is an elementwise pull of the model toward it; the
environment pins the deadline at the ~2.5*quota-th fastest client, so the
active set stays quota-bounded at any m.  Two changes: the targets come
from an integer hash of (client, coordinate, seed), which any backend
computes bit for bit (so the reference recomputes them exactly), and they
depend on ``--seed``.

The SAFA arithmetic of every model coordinate is independent of every
other but for the int8 wire, whose scales cover 128 consecutive values.
So the reference replays the whole model in column pieces of whole
quantisation blocks, over every client that takes a part in the
replayed rounds (a few hundred of the 10^6: the rest hold the initial
model throughout).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, traffic
from bench.cell import TaskParts

#: FLOPs per value of an upload: the pull (3) and its Eq. 7 delta (3)
FLOPS_PER_VALUE = 6
#: values of one [clients, columns] array of the reference's pieces
PIECE_VALUES = 1 << 26


def targets(rows, cols, salt):
    """[len(rows), len(cols)] targets in [-1, 1), from a 32-bit integer
    hash of (row, col, salt)."""
    h = (rows.astype(jnp.uint32)[:, None] * jnp.uint32(0x9E3779B1)
         + cols.astype(jnp.uint32)[None, :] * jnp.uint32(0x85EBCA77)
         + salt.astype(jnp.uint32))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -23) - 1.0


class ScaleTask:
    """Rows-contract task with index-derived data."""

    def __init__(self, d: int, lr: float, salt: int):
        self.d, self.lr = d, lr
        self.salt = jnp.uint32(salt)

    def init_global(self, key):
        return {'w': 0.01 * jax.random.normal(key, (self.d,), jnp.float32)}

    def local_train(self, stacked_params, round_idx):
        rows = jnp.arange(stacked_params['w'].shape[0], dtype=jnp.int32)
        return self.local_train_rows(stacked_params, rows, round_idx)

    def local_train_rows(self, params_rows, rows, round_idx):
        del round_idx
        p = params_rows['w']
        t = targets(rows, jnp.arange(self.d, dtype=jnp.int32), self.salt)
        return {'w': p + self.lr * (t - p)}

    def evaluate(self, global_params) -> dict:
        t = targets(jnp.arange(256, dtype=jnp.int32),
                    jnp.arange(self.d, dtype=jnp.int32), self.salt)
        return {'loss': float(jnp.mean((global_params['w'][None, :] - t)
                                       ** 2))}


def make_scale_env(m: int, quota: int, seed: int, draw_seed: int):
    """The quota-bounded environment: no crashes (a crashed straggler's
    carried progress would let O(crash_prob * m) clients slip under a
    later deadline), negligible communication, and the deadline at the
    ~2.5*quota-th fastest client's round time."""
    from repro.fedsim import EnvSpec
    spec = EnvSpec(m=m, crash_prob=0.0, dataset_size=20 * m, batch_size=10,
                   epochs=1, t_lim=1e9, seed=seed, draw_seed=draw_seed,
                   model_size_mb=1e-3)
    env = spec.build()
    base = env.t_updown + env.full_train_time()
    k = min(m - 1, int(round(2.5 * quota)))
    return spec.replace(t_lim=float(np.partition(base, k)[k]))


def ref_train(base, t, aux, committed, *, lr, salt):
    """The pull toward each client's targets, over the columns of a
    piece of the model."""
    del t, committed
    clients, cols = aux
    return {'w': base['w'] + lr * (targets(clients, cols, salt).astype(
        base['w'].dtype) - base['w'])}


def build(config: dict, seed: int) -> TaskParts:
    s, proto = config['sizes'], config['protocol']
    m, d = s['m'], s['d']
    salt = int(traffic.seed_words(seed, 1)[0])
    quota = max(1, int(round(proto['fraction'] * m)))
    env_spec = make_scale_env(m, quota, s['env_seed'], seed)
    task = ScaleTask(d, s['lr'], salt)
    train = functools.partial(ref_train, lr=s['lr'], salt=jnp.uint32(salt))

    def pieces(start, clients, *, lower, fault):
        """Column pieces of whole quantisation blocks, each short enough
        that a [clients, piece] array stays under ``PIECE_VALUES``
        values: a coordinate's rounds depend on no other coordinate but
        through the wire's per-block scale.  ``lower``: bfloat16 weights
        in place of float32."""
        del fault
        w = np.asarray(start['w'])
        if lower:
            w = jnp.asarray(w, jnp.bfloat16)
        step = max(reference.QBLOCK, PIECE_VALUES // max(len(clients), 1)
                   // reference.QBLOCK * reference.QBLOCK)
        ids = jnp.asarray(clients, jnp.int32)
        return [({'w': w[c:c + step]}, train,
                 (ids, jnp.arange(c, min(d, c + step), dtype=jnp.int32)))
                for c in range(0, d, step)]

    return TaskParts(
        program=task, init=task.init_global, env_spec=env_spec, n=d,
        flops_per_client=d * FLOPS_PER_VALUE, bytes_per_client=0.0,
        pieces=pieces,
        join=lambda outs: {'w': np.concatenate([np.asarray(o['w'])
                                                for o in outs])})
