"""Benchmark entrypoint: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--smoke] [--only SECTION]

Prints ``name,us_per_call,derived`` CSV rows (values are seconds for the
protocol-timing tables, accuracy for the accuracy tables, us/call for the
kernel microbenches — the ``derived`` column says which).

``--smoke`` runs the engine/protocol-comparison sections with tiny
round/fleet counts — a CI guard that the benchmark scripts themselves
keep importing and running, not a measurement.
"""
from __future__ import annotations

import argparse
import importlib
import time


def _mod(name: str):
    """Sections import lazily: this process touches JAX only inside the
    sections it runs, so a section's own child processes (the scale
    sweep's per-cell subprocesses) can take the accelerator.  fleet_sweep
    forces one XLA host device per core at import, which only takes
    effect before jax initializes — run it standalone
    (python -m benchmarks.fleet_sweep) for the sharded-fleet numbers."""
    return importlib.import_module(f'benchmarks.{name}')


SECTIONS = {
    'round_length': lambda full: (_mod('round_length').run(),
                                  _mod('round_length').summarize()),
    'round_engine': lambda full: _mod('round_engine').run(),
    'comm_path': lambda full: _mod('comm_path').run(),
    'sr_futility': lambda full: _mod('sr_futility').run(),
    'accuracy': lambda full: _mod('accuracy').run(full=full),
    'lag_tolerance': lambda full: _mod('lag_tolerance').run(),
    'bias': lambda full: _mod('bias_curves').run(),
    'eur': lambda full: _mod('eur').run(),
    'selection_ablation': lambda full: _mod('selection_ablation').run(),
    'agg_schemes': lambda full: _mod('agg_schemes').run(
        json_path='BENCH_agg_schemes.json'),
    'heterogeneity': lambda full: _mod('heterogeneity').run(
        json_path='BENCH_heterogeneity.json'),
    'kernels': lambda full: _mod('kernels_bench').run(),
    'roofline': lambda full: _mod('roofline_table').run(),
    'fleet_sweep': lambda full: _mod('fleet_sweep').run(),
    # the full sweep spawns one subprocess per cell for honest per-cell
    # peak-RSS (see benchmarks/scale.py)
    'scale': lambda full: _mod('scale').run(
        smoke=not full, json_path=_JSON_PATH['path']),
}

#: ``--json FILE`` routes the scale section's cell measurements
#: (rounds/sec + peak RSS per protocol x schedule cell) into FILE.
_JSON_PATH = {'path': None}

# tiny-parameter variants for --smoke: every engine/protocol-comparison
# script executes end to end in seconds, so CI catches bitrot in the
# benchmark layer without paying for a measurement
SMOKE_SECTIONS = {
    'round_length': lambda: (_mod('round_length').run(rounds=3),
                             _mod('round_length').summarize(rounds=3)),
    'round_engine': lambda: _mod('round_engine').run(rounds=6, reps=1),
    # comm_path asserts the 2-dispatch invariant of the compressed wire
    # path on every run, so the smoke pass is also a regression guard
    'comm_path': lambda: _mod('comm_path').run(rounds=4, reps=1),
    'eur': lambda: _mod('eur').run(rounds=3),
    # one fleet dispatch over the whole aggregation family; the JSON is
    # the BENCH_agg_schemes.json CI artifact
    'agg_schemes': lambda: _mod('agg_schemes').run(
        rounds=6, reps=1, json_path='BENCH_agg_schemes.json'),
    # the trace-scenario grid (scenario x protocol x wire); the JSON is
    # the BENCH_heterogeneity.json CI artifact
    'heterogeneity': lambda: _mod('heterogeneity').run(
        rounds=6, reps=1, json_path='BENCH_heterogeneity.json'),
    'fleet_sweep': lambda: _mod('fleet_sweep').run(rounds=6, s=4, reps=1),
    'scale': lambda: _mod('scale').run(
        smoke=True, json_path=_JSON_PATH['path']),
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument('--full', action='store_true',
                    help='paper-scale numeric runs (slow on 1 CPU core)')
    ap.add_argument('--smoke', action='store_true',
                    help='tiny-parameter CI pass over the engine sections')
    ap.add_argument('--only', choices=list(SECTIONS), default=None)
    ap.add_argument('--json', default=None, metavar='FILE',
                    help='write the scale section cells as JSON '
                         '(e.g. BENCH_scale.json)')
    args = ap.parse_args(argv)
    if args.full and args.smoke:
        ap.error('--full and --smoke are mutually exclusive')
    _JSON_PATH['path'] = args.json
    # a path only: importing jax starts no backend
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.json and args.only not in (None, 'scale'):
        ap.error('--json applies to the scale section')
    sections = SMOKE_SECTIONS if args.smoke else SECTIONS
    print('name,us_per_call,derived')
    if args.only:
        if args.smoke and args.only not in sections:
            ap.error(f'--smoke has no section {args.only!r} '
                     f'(choose from {sorted(sections)})')
        todo = [args.only]
    else:
        todo = list(sections)
    for name in todo:
        t0 = time.time()
        print(f'# --- {name} ---', flush=True)
        sections[name]() if args.smoke else sections[name](args.full)
        print(f'# {name} done in {time.time() - t0:.0f}s', flush=True)


if __name__ == '__main__':
    main()
