"""Concrete federated tasks mirroring the paper's three experiments.

Task 1: regression  (Boston-like,   m=5,   linear model, MSE)
Task 2: CNN         (MNIST-like,    m=100, 2x conv5x5 + fc, softmax)
Task 3: SVM         (KDD-like,      m=500, linear SVM, hinge loss)

Each implements ``repro.core.federation.Task``: ``local_train`` vmaps E
epochs of mini-batch SGD (Algorithm 2's client_update) over the stacked
clients dim.
"""
from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs, optim
from repro.core.federation import Task
from repro.data import FederatedData


class SupervisedTask(Task):
    def __init__(self, data: FederatedData, *, init_fn, loss_fn, acc_fn,
                 lr: float, epochs: int):
        self.data = data
        self.init_fn = init_fn
        self.loss_fn = loss_fn          # (params, x, y) -> scalar
        self.acc_fn = acc_fn            # (params, x, y) -> scalar
        self.epochs = epochs
        self.lr = lr
        self.opt = optim.sgd(lr)
        self._x = jnp.asarray(data.x)   # [m, nb, B, ...]
        self._y = jnp.asarray(data.y)
        self._train_jit = jax.jit(self._train_all)
        self._test_x = jnp.asarray(data.test_x)
        self._test_y = jnp.asarray(data.test_y)
        self._eval_jit = jax.jit(self._eval)

    def init_global(self, key):
        return self.init_fn(key)

    # -- client_update (Algorithm 2), vmapped over clients --------------------
    def _train_one(self, params, x, y):
        def epoch(params, _):
            def step(p, batch):
                bx, by = batch
                g = jax.grad(self.loss_fn)(p, bx, by)
                p, _ = self.opt.update(g, (), p)
                return p, None
            params, _ = jax.lax.scan(step, params, (x, y))
            return params, None
        params, _ = jax.lax.scan(epoch, params, None, length=self.epochs)
        return params

    def _train_all(self, stacked_params):
        return jax.vmap(self._train_one)(stacked_params, self._x, self._y)

    def local_train(self, stacked_params, round_idx: int):
        del round_idx  # full-pass SGD; order fixed as in the paper
        return self._train_jit(stacked_params)

    def _train_rows(self, params_rows, rows):
        return jax.vmap(self._train_one)(params_rows, self._x[rows],
                                         self._y[rows])

    def local_train_rows(self, params_rows, rows, round_idx):
        """Sparse-schedule rows-train contract: train only the K replicas
        in ``params_rows`` on clients ``rows``'s data.  Row for row this is
        the same ``_train_one`` trace ``local_train`` vmaps over all m, so
        a trained row is bit-identical to its dense counterpart (sentinel
        rows gather-clamp to real data; the engine discards their output
        via role masks)."""
        del round_idx
        if '_train_rows_jit' not in self.__dict__:
            self._train_rows_jit = jax.jit(self._train_rows)
        return self._train_rows_jit(params_rows, rows)

    def _eval(self, params, x, y):
        # inside the jitted function: a scope around the call would not
        # reach the ops of the compiled program
        with obs.scope('eval'):
            return self.loss_fn(params, x, y), self.acc_fn(params, x, y)

    def evaluate(self, global_params) -> dict:
        loss, acc = self._eval_jit(global_params, self._test_x, self._test_y)
        return {'loss': float(loss), 'acc': float(acc)}

    def fingerprint(self) -> str:
        """Identity of the training problem (client data + hypers) for
        checkpoint-resume verification — resuming a carry under different
        data would silently mix two runs."""
        if '_fingerprint' not in self.__dict__:
            h = hashlib.sha256()
            for a in (self.data.x, self.data.y, self.data.test_x,
                      self.data.test_y):
                h.update(np.ascontiguousarray(a).tobytes())
            h.update(repr((self.lr, self.epochs)).encode())
            self._fingerprint = \
                f'{type(self).__name__}:{h.hexdigest()[:16]}'
        return self._fingerprint


# ---------------------------------------------------------------------------
# Fleet-stacking: per-member Tasks for batched sweeps
# ---------------------------------------------------------------------------

class StackedSupervisedTask:
    """S ``SupervisedTask``s stacked fleet-major so a sweep whose members
    hold *different client data* (e.g. multi-``seed`` env grids with
    distinct partitions) still runs as one vmapped-scan dispatch.

    Members may disagree on batch count (partition sizes differ), so every
    member's [m, nb_s, B, ...] batch stack is zero-padded to the fleet
    maximum and a per-member [nb_max] validity mask rides along; the
    masked train step passes parameters through unchanged on padding
    batches, which keeps each member bit-identical to its own unpadded
    sequential run.  Members must share the model (leaf shapes), client
    count m, batch size and epoch count — the fleet compiles ONE program.

    This is not a ``Task`` itself: per-member init/eval stay with the
    member tasks; the fleet engines consume ``fleet_ctx()`` (a pytree of
    [S, ...] leaves vmapped alongside the carry) and ``fleet_train``.
    """

    def __init__(self, tasks):
        if not tasks:
            raise ValueError('empty task stack')
        t0 = tasks[0]
        if any(t.epochs != t0.epochs for t in tasks):
            raise ValueError('stacked tasks must share the epoch count')
        # one compiled program trains every member with t0's step, so the
        # steps must BE the same: silently training member s with member
        # 0's lr/loss would break the fleet==sequential bit-identity
        hypers = {(t.lr, t.loss_fn, t.acc_fn) for t in tasks}
        if len(hypers) != 1:
            raise ValueError(
                'stacked tasks must share lr/loss_fn/acc_fn (the fleet '
                'compiles one train step for all members); got '
                f'{len(hypers)} distinct combinations')
        shapes = {t._x.shape[:1] + t._x.shape[3:] for t in tasks}
        if len(shapes) != 1 or len({t._x.shape[2] for t in tasks}) != 1:
            raise ValueError(
                'stacked tasks must share (m, batch_size, features); got '
                f'x shapes {sorted(t._x.shape for t in tasks)}')
        self.tasks = tuple(tasks)
        self._t0 = t0
        nb = np.array([t._x.shape[1] for t in tasks])
        nb_max = int(nb.max())

        def pad(a, n):
            widths = [(0, 0), (0, n - a.shape[1])] + [(0, 0)] * (a.ndim - 2)
            return np.pad(np.asarray(a), widths)

        self._x = jnp.asarray(np.stack([pad(t._x, nb_max) for t in tasks]))
        self._y = jnp.asarray(np.stack([pad(t._y, nb_max) for t in tasks]))
        self._valid = jnp.asarray(np.arange(nb_max)[None, :] < nb[:, None])

    def fleet_ctx(self):
        """[S, ...] train context vmapped with the fleet carry."""
        return {'x': self._x, 'y': self._y, 'valid': self._valid}

    def fleet_train(self, stacked_params, round_idx, ctx):
        """One member's train call (invoked inside the fleet vmap, so
        ``stacked_params`` is [m, ...] and ``ctx`` leaves are that
        member's slices)."""
        del round_idx
        train = lambda p, x, y: self._train_one_masked(p, x, y, ctx['valid'])
        return jax.vmap(train)(stacked_params, ctx['x'], ctx['y'])

    def _train_one_masked(self, params, x, y, valid):
        """``SupervisedTask._train_one`` with a per-batch validity mask:
        padding steps compute and discard, returning the carry unchanged —
        an exact no-op, so the real steps' bits match the unpadded run."""
        t = self._t0

        def epoch(params, _):
            def step(p, batch):
                bx, by, v = batch
                g = jax.grad(t.loss_fn)(p, bx, by)
                p2, _ = t.opt.update(g, (), p)
                return jax.tree.map(lambda a, b: jnp.where(v, a, b), p2, p), \
                    None
            params, _ = jax.lax.scan(step, params, (x, y, valid))
            return params, None

        params, _ = jax.lax.scan(epoch, params, None, length=t.epochs)
        return params


def stack_tasks(tasks) -> StackedSupervisedTask:
    """Stack per-member ``SupervisedTask``s for a per-member-Task sweep
    (``repro.api.SweepSpec(tasks=...)``)."""
    return StackedSupervisedTask(list(tasks))


# ---------------------------------------------------------------------------
# Task 1: regression
# ---------------------------------------------------------------------------

def _reg_init(key, d=13):
    kw, _ = jax.random.split(key)
    return {'w': 0.01 * jax.random.normal(kw, (d,)), 'b': jnp.zeros(())}


def _reg_pred(p, x):
    # elementwise-mul + reduce rather than x @ w: dot_general's CPU lowering
    # re-tiles the contraction as batch dims fold in, so a fleet-vmapped run
    # would drift from single-run bits; this form lowers to a reduction
    # whose accumulation order is batch-size independent (test_fleet asserts
    # per-member bit-identity of safa_run_fleet vs sequential scan runs).
    return jnp.sum(x * p['w'], axis=-1) + p['b']


def _reg_loss(p, x, y):
    return jnp.mean(jnp.square(_reg_pred(p, x) - y))


def _reg_acc(p, x, y):
    """Paper Table III: acc = 1 - mean(|y - yhat| / max(y, yhat))."""
    yh = _reg_pred(p, x)
    return 1.0 - jnp.mean(jnp.abs(y - yh) / jnp.maximum(jnp.maximum(y, yh), 1e-6))


def regression_task(data: FederatedData, lr=1e-4, epochs=3) -> SupervisedTask:
    d = data.x.shape[-1]
    return SupervisedTask(data, init_fn=functools.partial(_reg_init, d=d),
                          loss_fn=_reg_loss, acc_fn=_reg_acc, lr=lr,
                          epochs=epochs)


# ---------------------------------------------------------------------------
# Task 2: CNN (2x conv 5x5 [20, 50 ch] + 2x2 maxpool + fc relu + softmax)
# ---------------------------------------------------------------------------

def _cnn_init(key, side=28, classes=10, c1=20, c2=50, hidden=128):
    ks = jax.random.split(key, 4)
    s = side // 4
    def conv_w(k, shape):
        fan_in = shape[0] * shape[1] * shape[2]
        return jax.random.normal(k, shape) / jnp.sqrt(fan_in)
    return {
        'c1': conv_w(ks[0], (5, 5, 1, c1)), 'b1': jnp.zeros((c1,)),
        'c2': conv_w(ks[1], (5, 5, c1, c2)), 'b2': jnp.zeros((c2,)),
        'f1': jax.random.normal(ks[2], (s * s * c2, hidden)) / jnp.sqrt(s * s * c2),
        'fb1': jnp.zeros((hidden,)),
        'f2': jax.random.normal(ks[3], (hidden, classes)) / jnp.sqrt(hidden),
        'fb2': jnp.zeros((classes,)),
    }


def _maxpool2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), 'VALID')


def _cnn_logits(p, x):
    # one name scope per layer; autodiff carries each into its backward
    # ops as transpose(jvp(cnn.<layer>)), so a trace can time the layers
    dims = ('NHWC', 'HWIO', 'NHWC')
    with jax.named_scope('cnn.conv1'):
        h = jax.lax.conv_general_dilated(x, p['c1'], (1, 1), 'SAME',
                                         dimension_numbers=dims)
        h = jax.nn.relu(h + p['b1'])
    with jax.named_scope('cnn.pool1'):
        h = _maxpool2(h)
    with jax.named_scope('cnn.conv2'):
        h = jax.lax.conv_general_dilated(h, p['c2'], (1, 1), 'SAME',
                                         dimension_numbers=dims)
        h = jax.nn.relu(h + p['b2'])
    with jax.named_scope('cnn.pool2'):
        h = _maxpool2(h)
    with jax.named_scope('cnn.fc'):
        h = h.reshape(h.shape[0], -1)
        h = jax.nn.relu(h @ p['f1'] + p['fb1'])
        return h @ p['f2'] + p['fb2']


def _cnn_loss(p, x, y):
    logits = _cnn_logits(p, x)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def _cnn_acc(p, x, y):
    return jnp.mean((jnp.argmax(_cnn_logits(p, x), -1) == y).astype(jnp.float32))


def cnn_task(data: FederatedData, lr=1e-3, epochs=5) -> SupervisedTask:
    side = data.x.shape[-3]
    classes = int(data.y.max()) + 1
    return SupervisedTask(
        data, init_fn=functools.partial(_cnn_init, side=side, classes=classes),
        loss_fn=_cnn_loss, acc_fn=_cnn_acc, lr=lr, epochs=epochs)


# ---------------------------------------------------------------------------
# Task 3: linear SVM, hinge loss, labels in {-1, +1}
# ---------------------------------------------------------------------------

def _svm_init(key, d=35):
    return {'w': 0.01 * jax.random.normal(key, (d,)), 'b': jnp.zeros(())}


def _svm_margin(p, x):
    # elementwise-mul + reduce for fleet-vmap bit-stability (see _reg_pred)
    return jnp.sum(x * p['w'], axis=-1) + p['b']


def _svm_loss(p, x, y, l2=1e-4):
    hinge = jnp.mean(jnp.maximum(0.0, 1.0 - y * _svm_margin(p, x)))
    return hinge + l2 * jnp.sum(jnp.square(p['w']))


def _svm_acc(p, x, y):
    """Paper Table III: mean(max(0, sign(y * yhat)))."""
    return jnp.mean(jnp.maximum(0.0, jnp.sign(y * _svm_margin(p, x))))


def svm_task(data: FederatedData, lr=1e-2, epochs=5) -> SupervisedTask:
    d = data.x.shape[-1]
    return SupervisedTask(data, init_fn=functools.partial(_svm_init, d=d),
                          loss_fn=_svm_loss, acc_fn=_svm_acc, lr=lr,
                          epochs=epochs)
