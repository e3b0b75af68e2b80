"""One run of one cell: set-up, a measured window, the check, the line.

``run.py`` is the command; this module holds everything below the check
for a chip, so the tests can drive a whole run on the CPU.

The window is a closed loop of back-to-back ``CompiledRunner.run()``
calls of the cell's one experiment.  Rounds are counted at eval-segment
boundaries, where ``evaluate`` syncs to the host.  A run still going when
the window closes stops at its next boundary, and its last segment is not
counted.  ``rounds_per_s`` is the rounds counted over the time from the
window's start to the last boundary counted.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time
import types

import numpy as np

from bench import adapter as adapter_mod
from bench import build, readers
from bench.build import SpecError

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
BENCH = CHECKOUT / 'bench'
#: where a traced run writes its profile (emptied before and after)
TRACE_DIR = CHECKOUT / '.bench_out' / 'trace'


# -- finding a cell's files by name --------------------------------------------------

def load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f'no file {path.relative_to(CHECKOUT)}')
    with open(path) as f:
        return json.load(f)


def cell_spec(name: str, bench: dict | None = None) -> dict:
    """Everything ``BENCHMARK.json`` and the files it names say of one
    workload: its entry, configuration, mix, limits and metrics."""
    bench = bench if bench is not None else load_json(
        CHECKOUT / 'BENCHMARK.json')
    wl = {w['name']: w for w in bench['workloads']}.get(name)
    if wl is None:
        raise SpecError(f'no workload {name!r} in BENCHMARK.json')
    cfg = {c['name']: c for c in bench['configs']}.get(wl['config'])
    if cfg is None:
        raise SpecError(f'workload {name!r} names no configuration '
                        f'{wl["config"]!r} in BENCHMARK.json')

    def applies(metric):
        return name in metric.get('workloads', [name])
    config = load_json(CHECKOUT / cfg['file'])
    mix = load_json(BENCH / 'mixes' / f'{wl["traffic"]}.json')
    build.protocol_of(config, mix)
    build.task_module(config)
    return {
        'workload': wl,
        'config': config,
        'mix': mix,
        'limits': load_json(BENCH / 'limits' / f'{name}.json'),
        'end_to_end': [m for m in bench['end_to_end'] if applies(m)],
        'per_layer': [m for m in bench['per_layer'] if applies(m)],
    }


def metric_module(name: str):
    """The module ``bench/metrics/<name>.py``: its ``read(ctx)`` gives
    the metric, or None where the run has nothing to read it from."""
    path = BENCH / 'metrics' / f'{name}.py'
    if not path.is_file():
        raise SpecError(f'no reader bench/metrics/{name}.py')
    spec = importlib.util.spec_from_file_location(
        f'bench_metric_{name.replace(".", "_")}', path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- compile accounting --------------------------------------------------------------------

class Compiles:
    """Seconds JAX spends tracing, lowering and compiling, and how many
    programs it compiled, from its monitoring events."""

    def __init__(self):
        import jax
        self.secs, self.programs = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name.startswith('/jax/core/compile/'):
            self.secs += secs
        if name == '/jax/core/compile/backend_compile_duration':
            self.programs += 1


# -- the window ------------------------------------------------------------------------------

class WindowClosed(Exception):
    """Raised at the first boundary after the window's end."""


class Window(adapter_mod.Listener):
    """Counts rounds at boundaries until ``seconds`` after ``open``, and
    keeps the global after the first segment (set-up opens one with no
    end)."""

    def __init__(self, eval_every: int):
        self.eval_every = eval_every
        self.start = self.deadline = self.last = None
        self.rounds = 0
        self.eval_s = []
        self.boundaries = []        # seconds from the window's start
        self.capture = None         # global after the first segment

    def open(self, seconds: float = math.inf):
        self.start = self.last = time.perf_counter()
        self.deadline = self.start + seconds

    def boundary(self, global_params, eval_s: float):
        now = time.perf_counter()
        if now > self.deadline:
            raise WindowClosed
        self.rounds += self.eval_every
        self.last = now
        self.boundaries.append(now - self.start)
        self.eval_s.append(eval_s)
        if self.capture is None:
            import jax
            self.capture = jax.device_get(global_params)


def _max_abs_diff(a: dict, b: dict) -> float:
    return max(float(np.max(np.abs(np.asarray(a[k], np.float64)
                                   - np.asarray(b[k], np.float64))))
               for k in a)


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get('peak_bytes_in_use', 0)))
    return {'platform': devs[0].platform, 'kind': devs[0].device_kind,
            'count': len(devs), 'memory_peak_bytes': peak}


@dataclasses.dataclass
class SetUp:
    cell: object
    runner: object              # the CompiledRunner the window drives
    first: Window               # set-up's segment, with its global
    precompute_s: float
    compile_s: float
    setup_s: float


def set_up(cell, compiles: Compiles, t_start: float) -> SetUp:
    """Host precompute, compile, and one segment through ``run()``: what
    every run does before its window."""
    import jax
    with jax.profiler.TraceAnnotation(adapter_mod.SPAN_PRECOMPUTE):
        t0 = time.perf_counter()
        cell.experiment.precompute()
        precompute_s = time.perf_counter() - t0
    runner = cell.experiment.compile()
    first = Window(cell.eval_every)     # set-up's: keeps the first segment
    first.open()
    cell.adapter.listener = first
    runner.run(max_segments=1)
    cell.adapter.close_segment()
    return SetUp(cell=cell, runner=runner, first=first,
                 precompute_s=precompute_s, compile_s=compiles.secs,
                 setup_s=time.perf_counter() - t_start)


def free(s: SetUp):
    """Drop the program's state and compiled programs, so that the
    reference runs in the memory they held."""
    import jax
    s.cell.adapter.listener = adapter_mod.Listener()
    s.runner = None
    s.cell.experiment = None
    s.cell.adapter.task = None
    jax.clear_caches()
    gc.collect()


def judge(limits: dict, readings: dict):
    """Each compared number beside its limit, and whether all hold:
    ``({name: {'value', 'limit'}}, correct)``."""
    checks = {k: {'value': readings[k], 'limit': limit}
              for k, limit in limits.items()}
    correct = all(np.isfinite(c['value']) and c['value'] <= c['limit']
                  for c in checks.values())
    return checks, correct


def run_cell(spec: dict, *, seed: int, seconds: float,
             trace: bool, chips: int, t_start: float, log=print,
             keep_trace: str | None = None) -> dict:
    """Set up, measure, check; returns the result line as a dict.
    ``keep_trace`` names a file to copy a traced run's profile to."""
    import jax

    from bench import trace_reduce
    from bench.peaks import peaks

    compiles = Compiles()
    cell = build.build(spec, seed)
    s = set_up(cell, compiles, t_start)
    adapter = cell.adapter

    # -- the window
    win = Window(cell.eval_every)
    adapter.listener = win
    programs_before = compiles.programs
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    with jax.profiler.TraceAnnotation(adapter_mod.SPAN_WINDOW):
        win.open(seconds)
        try:
            while True:
                with jax.profiler.TraceAnnotation(adapter_mod.SPAN_RUN):
                    adapter.open_segment()
                    s.runner.run()
                adapter.close_segment()
        except WindowClosed:
            adapter.close_segment()
    if trace:
        jax.profiler.stop_trace()
    window_compiles = compiles.programs - programs_before
    window_s = win.last - win.start
    device = device_info(chips)
    free(s)

    t0 = time.perf_counter()
    readings = {'setup_s': s.setup_s, 'precompute_s': s.precompute_s,
                'compile_s': s.compile_s, 'rounds': win.rounds,
                'window_s': window_s, 'boundaries_s': win.boundaries,
                **cell.check(s.first.capture)}
    readings['reference_s'] = time.perf_counter() - t0
    readings['window_vs_setup'] = (
        _max_abs_diff(win.capture, s.first.capture)
        if win.capture is not None else float('inf'))
    readings['window_compiles'] = window_compiles
    checks, correct = judge(spec['limits'], readings)
    correct = correct and bool(win.rounds)

    result = {'correct': correct, 'attempted': win.rounds,
              'failed': 0 if correct else win.rounds, 'metrics': {},
              'device': device}
    if not trace:
        e2e = {'rounds_per_s': win.rounds / window_s if window_s > 0
               else 0.0,
               'peak_hbm_gb': device['memory_peak_bytes'] / 1e9,
               'setup_s': s.setup_s}
        for m in spec['end_to_end']:
            result['metrics'][m['name']] = {'value': e2e[m['name']],
                                            'unit': m['unit']}
    else:
        xplane = _xplane(TRACE_DIR)
        if keep_trace:
            shutil.copyfile(xplane, keep_trace)
        reduced = trace_reduce.reduce(trace_reduce.load(xplane))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = types.SimpleNamespace(
            precompute_s=s.precompute_s, compile_s=s.compile_s,
            eval_s=win.eval_s, rounds=win.rounds,
            round_s=window_s / win.rounds if win.rounds else None,
            trace=reduced, shape=cell.shape(),
            peaks=peaks(device['kind']))
        for m in spec['per_layer']:
            value = metric_module(m['name']).read(ctx)
            if value is not None:
                result['metrics'][m['name']] = {'value': value,
                                                'unit': m['unit']}
        device['busy_s'] = reduced.busy_s
        device['window_s'] = reduced.window_s
        result['breakdown'] = readers.breakdown(reduced)
    result['checks'] = checks
    for key, value in readings.items():
        if key not in checks:
            log(f'reading {key} {value!r}', file=sys.stderr)
    for key, c in checks.items():
        log(f'check {key} {c["value"]!r} limit {c["limit"]!r}',
            file=sys.stderr)
    return result


def _xplane(root: pathlib.Path) -> str:
    found = sorted(glob.glob(str(root / '**' / '*.xplane.pb'),
                             recursive=True))
    if not found:
        raise RuntimeError(f'the profiler wrote no trace under {root}')
    return found[-1]

