#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (data, environment, host precompute, compile, one warm-up
segment) is timed as ``setup_s``; then back-to-back runs of the cell's
experiment are measured for ``--seconds``.  ``--trace 0`` prints the
cell's end-to-end metrics, ``--trace 1`` profiles the window and prints
its per-layer metrics.  Either way the run is checked against the plain
reference, each compared number and its limit go to standard error as
the last lines, and the last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and
``checks`` last).  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / 'src')]
# libtpu writes its logs to a fixed path under /tmp unless told not to
os.environ.setdefault('TPU_LOG_DIR', 'disabled')


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error('--seed is a whole number >= 0')

    from bench import harness
    try:
        spec = harness.cell_spec(args.workload)
        for m in spec['per_layer']:
            harness.metric_module(m['name'])
    except harness.SpecError as e:
        print(f'bench: {e}', file=sys.stderr)
        return 2
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f'bench: the program is not in this checkout ({e})',
              file=sys.stderr)
        return 4

    import jax
    chips = spec['workload']['chips']
    devices = jax.devices()
    if devices[0].platform != 'tpu' or len(devices) < chips:
        print(f'bench: {args.workload} needs {chips} TPU chip(s); JAX sees '
              f'{len(devices)} {devices[0].platform} device(s)',
              file=sys.stderr)
        return 3
    enable_compile_cache()
    lines = []
    result = harness.run_cell(
        spec, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), chips=chips, t_start=T_START,
        log=lambda s, file=None: lines.append(s))
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
