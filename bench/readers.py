"""Shared arithmetic of the per-layer metric readers in ``metrics/``."""
from __future__ import annotations

import numpy as np

from bench.trace_reduce import base_name


def calls_of(names):
    """A match for the trace's ops of a kernel: the HLO custom call of a
    Pallas kernel is named after the jitted function that launches it
    (``gather_rows.6``)."""
    return lambda op: base_name(op) in names


def kernel_roofline(ctx, names, bytes_per_call: float):
    """HBM roofline share (%) of one kernel, whose calls carry one of
    ``names`` in the device trace: the bytes its calls must move, over
    its device time at the chip's peak bandwidth.  None where the trace
    holds no call of it."""
    if ctx.trace is None:
        return None
    secs, calls = ctx.trace.time_of(calls_of(names))
    if not calls or secs <= 0:
        return None
    return 100.0 * bytes_per_call * calls / (ctx.peaks.hbm_bw * secs)


def per_round(counts, fn) -> float:
    """Mean over a run's rounds of ``fn(count)``, or of ``fn(*counts)``
    for a tuple of per-round counts."""
    if not isinstance(counts, tuple):
        counts = (counts,)
    return float(np.mean([fn(*c) for c in zip(*counts)]))


def breakdown(reduced, top: int = 10) -> dict:
    ops = sorted(reduced.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {'device_ops': [[n, s] for n, s in ops],
            'idle_gaps': [[n, s] for n, s in reduced.gaps[:top]]}
