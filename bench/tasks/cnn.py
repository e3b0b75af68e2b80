"""The paper CNN federation (SAFA paper, Task 2): MNIST-like images over
m clients, each training a 2x conv5x5 + fc network by mini-batch SGD.

The images, their split over clients and the initial weights are made
here from ``--seed``; the program under test is handed them through its
own ``cnn_task``.  A client's round reads its images once and trains
``epochs`` passes over its [nb, batch] batches.  The reference model below is written from the paper's
description in plain ``jax.numpy``, at ``Precision.HIGHEST``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic, work
from bench.cell import TaskParts

HIGHEST = jax.lax.Precision.HIGHEST


# -- data (the traffic of this task), made from the seed --------------------------

def make_images(n: int, side: int, classes: int, seed: int):
    """MNIST-like images: class-conditional Gaussian prototypes plus noise."""
    rng = np.random.default_rng(seed)
    protos = rng.normal(size=(classes, side * side)).astype(np.float32)
    labels = rng.integers(0, classes, size=n)
    x = protos[labels] + 1.2 * rng.normal(size=(n, side * side)).astype(
        np.float32)
    return x.reshape(n, side, side, 1).astype(np.float32), \
        labels.astype(np.int32)


def partition(x, y, partition_sizes, batch: int, seed: int,
              test_frac: float = 0.15):
    """Hold out a test split, deal the rest out in proportion to the
    clients' partition sizes, and pad each client's share (wrapping round
    its own samples) to the common batch count: x [m, nb, batch, ...]."""
    rng = np.random.default_rng(seed + 7)
    n = x.shape[0]
    n_test = int(n * test_frac)
    perm = rng.permutation(n)
    test_idx, pool = perm[:n_test], perm[n_test:]
    sizes = np.maximum(1, (partition_sizes / partition_sizes.sum()
                           * len(pool)).astype(int))
    shares = np.split(rng.permutation(pool)[:sizes.sum()],
                      np.cumsum(sizes)[:-1])
    nb = max(1, int(np.ceil(max(len(s) for s in shares) / batch)))
    idx = np.stack([np.resize(s, nb * batch) for s in shares])
    xs = x[idx].reshape((len(shares), nb, batch) + x.shape[1:])
    ys = y[idx].reshape(len(shares), nb, batch)
    return xs, ys, x[test_idx], y[test_idx], np.array([len(s) for s in shares])


# -- the model, written from the paper ------------------------------------------------

def init_params(key, *, side, classes, c1, c2, hidden, kernel):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    flat = (side // 4) ** 2 * c2

    def fan(k, shape, fan_in):
        return jax.random.normal(k, shape) / jnp.sqrt(fan_in)
    return {
        'c1': fan(k1, (kernel, kernel, 1, c1), kernel * kernel),
        'b1': jnp.zeros((c1,)),
        'c2': fan(k2, (kernel, kernel, c1, c2), kernel * kernel * c1),
        'b2': jnp.zeros((c2,)),
        'f1': fan(k3, (flat, hidden), flat), 'fb1': jnp.zeros((hidden,)),
        'f2': fan(k4, (hidden, classes), hidden),
        'fb2': jnp.zeros((classes,)),
    }


def _conv(x, w, precision):
    """SAME convolution written as one matmul over the input's patches:
    x [B, H, W, C], w [k, k, C, O]."""
    k = w.shape[0]
    b, h, wd, c = x.shape
    xp = jnp.pad(x, ((0, 0), (k // 2, k // 2), (k // 2, k // 2), (0, 0)))
    patches = jnp.concatenate([xp[:, dy:dy + h, dx:dx + wd, :]
                               for dy in range(k) for dx in range(k)], axis=-1)
    return jnp.dot(patches, w.reshape(k * k * c, -1), precision=precision)


def _pool(x):
    """2x2 max pool, stride 2."""
    b, h, w, c = x.shape
    return jnp.max(x.reshape(b, h // 2, 2, w // 2, 2, c), axis=(2, 4))


def ref_loss(p, x, y, precision):
    h = _pool(jax.nn.relu(_conv(x, p['c1'], precision) + p['b1']))
    h = _pool(jax.nn.relu(_conv(h, p['c2'], precision) + p['b2']))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(jnp.dot(h, p['f1'], precision=precision) + p['fb1'])
    logits = jnp.dot(h, p['f2'], precision=precision) + p['fb2']
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def ref_train(base, t, aux, committed, *, lr, epochs, precision):
    """Each client that commits this round: ``epochs`` passes of SGD
    over its own batches, one client after another.  A client that
    crashes or misses the deadline uploads nothing, so it is not
    trained."""
    del t
    x, y = aux

    def client(p, xs, ys):
        def step(p, batch):
            g = jax.grad(ref_loss)(p, *batch, precision)
            return jax.tree.map(lambda a, b: a - jnp.asarray(lr, a.dtype) * b,
                                p, g), None

        def epoch(p, _):
            return jax.lax.scan(step, p, (xs, ys))[0], None
        return jax.lax.scan(epoch, p, None, length=epochs)[0]

    def one(args):
        p, xs, ys, go = args
        return jax.lax.cond(go, client, lambda p, *_: p, p, xs, ys)
    return jax.lax.map(one, (base, x, y, committed))


# -- the task ----------------------------------------------------------------------------

def build(config: dict, seed: int) -> TaskParts:
    from repro.data import FederatedData
    from repro.data.tasks import cnn_task
    from repro.fedsim import EnvSpec

    s, model = config['sizes'], config['model']
    env_spec = EnvSpec(m=s['m'], crash_prob=s['crash_prob'],
                       dataset_size=s['dataset_size'],
                       batch_size=s['batch_size'], epochs=s['epochs'],
                       t_lim=s['t_lim'], seed=s['env_seed'], draw_seed=seed)
    x, y = make_images(s['dataset_size'], model['side'], model['classes'],
                       seed)
    xs, ys, tx, ty, sizes = partition(
        x, y, env_spec.build().partition_sizes, s['batch_size'], seed)
    del x, y
    task = cnn_task(FederatedData(x=xs, y=ys, test_x=tx, test_y=ty,
                                  partition_sizes=sizes),
                    lr=s['lr'], epochs=s['epochs'])
    init = functools.partial(init_params, **model)
    trains = {lower: functools.partial(
        ref_train, lr=s['lr'], epochs=s['epochs'],
        precision=None if lower else HIGHEST) for lower in (False, True)}

    def pieces(start, clients, *, lower, fault):
        """The whole model in one piece.  ``lower``: bfloat16 weights and
        images in place of float32, at the default precision;
        ``half_batch``: each step's mean taken over the first half of
        its batch."""
        data_x, data_y = xs[clients], ys[clients]
        if fault == 'half_batch':
            half = data_x.shape[2] // 2
            data_x, data_y = data_x[:, :, :half], data_y[:, :, :half]
        data_x = jnp.asarray(data_x)
        if lower:
            start = jax.tree.map(lambda a: jnp.asarray(a, jnp.bfloat16),
                                 start)
            data_x = data_x.astype(jnp.bfloat16)
        return [(start, trains[lower], (data_x, jnp.asarray(data_y)))]

    m, nb, batch = xs.shape[:3]
    n = sum(int(np.prod(v.shape)) for v in
            jax.eval_shape(init, traffic.seed_key(0)).values())
    per_image = work.cnn_train_flops_per_image(
        side=model['side'], c1=model['c1'], c2=model['c2'],
        hidden=model['hidden'], classes=model['classes'], k=model['kernel'])
    return TaskParts(
        program=task, init=init, env_spec=env_spec, n=n,
        flops_per_client=work.supervised_round_flops(
            1, nb, batch, s['epochs'], per_image),
        bytes_per_client=(xs[0].size + ys[0].size) * 4,
        pieces=pieces, join=lambda outs: outs[0], faults=('half_batch',))
