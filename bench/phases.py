"""Device time per phase of a round, and idle gaps named after the
program's own spans, from a profiler trace of a window.

The program puts every op of a round body under one phase scope
(``repro.obs.scope``): ``repro.rows``, ``repro.train``, ``repro.wire``,
``repro.aggregate`` and ``repro.eval``.  Each op's own device time (as
``trace_reduce`` counts it) goes to the innermost phase in its name
stack, and to ``unscoped`` where there is none, so the phases and
``unscoped`` add up to the busy time.  The CNN's layer scopes
(``cnn.conv1``, ``cnn.pool1``, ...) nest inside ``repro.train``; they are
summed apart, by kind, and not counted again.  The program's host spans
(``repro.segment``, ``repro.evaluate``, ...) rank inside the harness's
spans when an idle gap is named.

    python -m bench.phases --workload <name> --seed <n> --seconds <s>

runs one cell with the profiler on and the program's spans recorded
(``obs.recording()``) and prints one JSON object: the harness's result
line, the traced window's rate, the share of busy time of each phase and
layer kind, the self seconds of each program span, the program's
counters and the longest idle gaps.
"""
from __future__ import annotations

import gzip
import re

from bench import trace_reduce
from bench.adapter import SPAN_WINDOW
from bench.trace_reduce import SPAN_DEPTH, _busy, _clip, _own_times

PHASE = re.compile(r'(?:^|/)repro\.(\w+)(?=/|$)')
LAYER = re.compile(r'(?:^|[/(])cnn\.([a-z]+)\d*(?=[/)]|$)')


def _window(trace):
    windows = [s for s in trace.spans if s[0] == SPAN_WINDOW]
    if not windows:
        raise ValueError('the trace holds no bench_window span')
    return windows[0][1:]


def _own(trace):
    """(op, own device ns) of every op of every device in the window."""
    lo, hi = _window(trace)
    for ops in trace.devices.values():
        for op, own in zip(ops, _own_times(ops, lo, hi)):
            if _clip(op, lo, hi) > 0:
                yield op, own


def _phase(op) -> str:
    found = PHASE.findall(op.scope)
    return found[-1] if found else 'unscoped'


def phase_seconds(trace) -> tuple:
    """``({phase or 'unscoped': seconds}, {layer kind: seconds})``: own
    device seconds in the window, mean over devices, by the innermost
    ``repro.`` phase and by ``cnn.`` layer kind (``conv``, ``pool``,
    ``fc``) of each op's name stack."""
    phases, layers = {}, {}
    for op, own in _own(trace):
        key = _phase(op)
        phases[key] = phases.get(key, 0.0) + own * 1e-9
        kind = LAYER.findall(op.scope)
        if kind:
            layers[kind[-1]] = layers.get(kind[-1], 0.0) + own * 1e-9
    n = len(trace.devices)
    return ({k: v / n for k, v in phases.items()},
            {k: v / n for k, v in layers.items()})


def unscoped_ops(trace, top: int = 10) -> list:
    """[(op base name, seconds)] of the ops under no phase, longest
    first, mean over devices."""
    secs = {}
    for op, own in _own(trace):
        if _phase(op) == 'unscoped':
            key = trace_reduce.base_name(op.name)
            secs[key] = secs.get(key, 0.0) + own * 1e-9
    n = len(trace.devices)
    return sorted(((k, v / n) for k, v in secs.items()),
                  key=lambda kv: -kv[1])[:top]


def program_spans(path: str) -> list:
    """[(name, start ns, end ns)] of the host spans named ``repro.*`` in
    a trace (``.xplane.pb``, or gzipped)."""
    from jax.profiler import ProfileData
    with open(path, 'rb') as f:
        raw = f.read()
    if raw[:2] == b'\x1f\x8b':
        raw = gzip.decompress(raw)
    spans = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not plane.name.startswith('/host:'):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith('repro.'):
                    start = int(e.start_ns)
                    spans.append((e.name, start, start + int(e.duration_ns)))
    return sorted(spans, key=lambda s: s[1])


def named_gaps(trace, spans) -> list:
    """[(span name, seconds)] of the window's idle gaps, longest first,
    each named after the innermost span open at its midpoint: the
    latest-opened program span, else the harness's innermost."""
    lo, hi = _window(trace)
    out = []
    for ops in trace.devices.values():
        for a, b in _busy(ops, lo, hi)[1]:
            t = (a + b) // 2
            mine = [s for s in spans if s[1] <= t < s[2]]
            theirs = [s for s in trace.spans if s[1] <= t < s[2]]
            if mine:
                name = max(mine, key=lambda s: s[1])[0]
            elif theirs:
                name = min(theirs, key=lambda s: SPAN_DEPTH.get(s[0], 9))[0]
            else:
                name = 'none'
            out.append((name, (b - a) * 1e-9))
    return sorted(out, key=lambda g: -g[1])


def main(argv=None) -> int:
    import argparse
    import json
    import time

    from bench import harness
    from repro import obs

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    args = ap.parse_args(argv)
    spec = harness.cell_spec(args.workload)
    kept = harness.CHECKOUT / '.bench_out' / f'{args.workload}.xplane.pb'
    kept.parent.mkdir(parents=True, exist_ok=True)
    readings = {}

    def log(line, file=None):
        del file
        head, key, value = (line.split(' ', 2) + ['', ''])[:3]
        if head == 'reading':
            readings[key] = value

    with obs.recording() as recorded:
        result = harness.run_cell(
            spec, seed=args.seed, seconds=args.seconds, trace=True,
            chips=spec['workload']['chips'], t_start=time.perf_counter(),
            log=log, keep_trace=str(kept))
    trace = trace_reduce.load(str(kept))
    busy = trace_reduce.reduce(trace).busy_s
    phases, layers = phase_seconds(trace)
    window_s = float(readings['window_s'])
    out = {
        'workload': args.workload, 'seed': args.seed,
        'traced_rounds_per_s': int(readings['rounds']) / window_s,
        'busy_s': busy,
        'phase_share': {k: 100.0 * v / busy for k, v in phases.items()},
        'layer_share': {k: 100.0 * v / busy for k, v in layers.items()},
        'unscoped_ops': unscoped_ops(trace),
        'span_self_s': obs.self_seconds(recorded),
        'precompute_s': float(readings['precompute_s']),
        'counters': obs.counters(),
        'gaps': named_gaps(trace, program_spans(str(kept)))[:12],
        'result': result,
    }
    kept.unlink()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == '__main__':
    import os
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                           / 'src'))
    # libtpu writes its logs to a fixed path under /tmp unless told not to
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    sys.exit(main())
