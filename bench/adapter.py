"""The harness's task adapter: the program's task, as the program sees
it, with the benchmark's weights, a named scope around local training
and a hook at every evaluation.

The arithmetic is the wrapped task's own.  ``init_global`` returns the
weights the benchmark made from ``--seed`` (one jitted call on the
device); ``local_train``/``local_train_rows`` run under
``jax.named_scope(TRAIN_SCOPE)``, which the trace reduction reads for
``train_share``; ``evaluate`` reports each eval-segment boundary to the
harness's listener (``evaluate`` syncs to the host, so the boundary is
where the segment's rounds are done).
"""
from __future__ import annotations

import time

import jax

TRAIN_SCOPE = 'bench_local_train'
#: host spans the harness writes into the profiler's trace
SPAN_WINDOW = 'bench_window'
SPAN_RUN = 'bench_run'
SPAN_SEGMENT = 'bench_segment'
SPAN_EVALUATE = 'bench_evaluate'
SPAN_PRECOMPUTE = 'bench_precompute'


class Listener:
    """Receives each boundary; the harness's window is one."""

    def boundary(self, global_params, eval_s: float) -> None:
        del global_params, eval_s


class Adapter:
    def __init__(self, task, init_fn, key):
        self.task = task
        self._init = jax.jit(init_fn)
        self._key = key
        self.listener = Listener()
        self._segment = None

    def init_global(self, key):
        del key     # the benchmark's weights come from --seed
        return self._init(self._key)

    def local_train(self, stacked_params, round_idx):
        with jax.named_scope(TRAIN_SCOPE):
            return self.task.local_train(stacked_params, round_idx)

    def local_train_rows(self, params_rows, rows, round_idx):
        with jax.named_scope(TRAIN_SCOPE):
            return self.task.local_train_rows(params_rows, rows, round_idx)

    def open_segment(self):
        """Start the host span of the next segment."""
        self.close_segment()
        self._segment = jax.profiler.TraceAnnotation(SPAN_SEGMENT)
        self._segment.__enter__()

    def close_segment(self):
        if self._segment is not None:
            self._segment.__exit__(None, None, None)
            self._segment = None

    def evaluate(self, global_params) -> dict:
        jax.block_until_ready(global_params)    # the segment's rounds
        self.close_segment()
        with jax.profiler.TraceAnnotation(SPAN_EVALUATE):
            t0 = time.perf_counter()
            out = self.task.evaluate(global_params)
            eval_s = time.perf_counter() - t0
        self.listener.boundary(global_params, eval_s)
        self.open_segment()
        return out
