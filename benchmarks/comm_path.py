"""Compressed-wire path benchmark: rounds/sec + wire bytes for three
upload formats of the same SAFA run:

* ``f32``    — uncompressed uploads, packed aggregation (1 dispatch/round);
* ``perleaf``— int8 uplink via the per-leaf reference wrapper
  (``quantize_uploads=True``: 2 pallas dispatches per leaf per client);
* ``packed`` — the quantized-wire fast path (``wire='int8'``: one packed
  quantize + one fused dequant-aggregate, exactly 2 dispatches per round).

All three run the scan engine at quickstart scale; wire-bytes accounting
(``ops.comm_bytes``, tree vs packed layout) is also reported for the
paper-scale CNN model.  The dispatch-count invariant of the fast path is
asserted on every run — including the CI ``--smoke`` pass — so the
2-dispatch contract cannot silently regress.

    PYTHONPATH=src python -m benchmarks.comm_path
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import Timer, emit
from repro.core import federation, protocol
from repro.data import make_regression, partition
from repro.data.tasks import regression_task
from repro.fedsim import EnvSpec
from repro.kernels.ops import comm_bytes, count_pallas_calls

ROUNDS = 40

SPEC = EnvSpec(m=5, crash_prob=0.3, dataset_size=506, batch_size=5,
               epochs=3, t_lim=830.0, seed=3)


def _quickstart_setup():
    env = SPEC.build()
    x, y = make_regression()
    data = partition(x, y, env.partition_sizes, batch_size=5, seed=1)
    task = regression_task(data, lr=1e-3, epochs=3)
    return env, task


_MODES = {
    'f32': dict(use_kernel='packed'),
    'perleaf': dict(quantize_uploads=True, use_kernel='packed'),
    'packed': dict(wire='int8'),
}


def _time_mode(task, mode: str, reps: int, rounds: int) -> float:
    def once():
        h = federation.run_safa(task, SPEC.build(), fraction=0.5,
                                lag_tolerance=5,
                                rounds=rounds, eval_every=rounds,
                                engine='scan', **_MODES[mode])
        jax.block_until_ready(h.final_global)
    once()                                  # warm up compile caches
    with Timer() as t:
        for _ in range(reps):
            once()
    return t.dt / reps


def _dispatches_per_round(task, env, mode: str) -> int:
    """pallas_calls in one scanned round body for the given upload mode."""
    sched = federation.precompute_safa_schedule(env, fraction=0.5,
                                                lag_tolerance=5, rounds=2)
    ns = federation._NumericState(task, env.m, 0)
    w = jnp.asarray(env.weights)
    train_fn = task.local_train
    use_kernel, wire = 'packed', 'f32'
    if mode == 'perleaf':
        train_fn = federation._quantized_train_fn(task.local_train)
    elif mode == 'packed':
        use_kernel, wire = False, 'int8'
    jaxpr = jax.make_jaxpr(
        lambda g, l, c, s, ww: protocol.safa_run_scan(
            g, l, c, s, ww, local_train_fn=train_fn, use_kernel=use_kernel,
            wire=wire)
    )(ns.global_w, ns.local_w, ns.cache, sched.to_device(), w)
    return count_pallas_calls(jaxpr.jaxpr)


def _wire_bytes_rows(name: str, tree):
    """Uplink bytes for one client's model transfer, every format."""
    raw = comm_bytes(tree, quantized=False)
    for fmt, kw in (('f32_tree', dict(quantized=False)),
                    ('int8_tree', dict(quantized=True)),
                    ('f32_packed', dict(quantized=False, layout='packed')),
                    ('int8_packed', dict(quantized=True, layout='packed'))):
        b = comm_bytes(tree, **kw)
        emit(f'comm_path/wire_bytes/{name}/{fmt}', b,
             f'compression={raw / b:.2f}x')


def run(rounds: int = ROUNDS, reps: int = 3):
    _, task = _quickstart_setup()

    # dispatch counts first: the fast-path invariant is asserted, not just
    # reported, so the CI smoke pass guards it
    # a built env's rng is single-shot: each mode's precompute gets a
    # fresh build of the same spec
    counts = {m: _dispatches_per_round(task, SPEC.build(), m)
              for m in _MODES}
    assert counts['packed'] == 2, (
        f"compressed fast path must be exactly 2 pallas dispatches per "
        f"round, got {counts['packed']}")
    emit('comm_path/dispatches_per_round', counts['packed'],
         f"f32_packed={counts['f32']};perleaf_int8={counts['perleaf']};"
         f"packed_int8={counts['packed']}")

    secs = {m: _time_mode(task, m, reps, rounds) for m in _MODES}
    for mode, s in secs.items():
        emit(f'comm_path/{mode}/rounds_per_sec', f'{rounds / s:.1f}',
             f'sec_per_run={s:.3f};rounds={rounds};'
             f'speedup_vs_perleaf={secs["perleaf"] / s:.2f}x')

    # wire accounting: quickstart model and the paper-scale CNN
    _wire_bytes_rows('quickstart', task.init_global(jax.random.PRNGKey(0)))
    from repro.data.tasks import _cnn_init
    _wire_bytes_rows('paper_cnn', _cnn_init(jax.random.PRNGKey(0)))


if __name__ == '__main__':
    run()
