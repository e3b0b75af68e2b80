#!/usr/bin/env python3
"""Readings behind a cell's limits, on the chip, in one process.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--program 0|1] [--stand-ins control,half_uploads,...]

For each seed: the program's reading -- set-up and its first segment
through ``CompiledRunner.run``, made by the harness's own ``set_up`` --
unless ``--program 0``, and the reading of each stand-in: the plain
reference with one change, put in the program's place (``control``: the
precision below the configuration's; a fault: ``half_uploads``, and the
task's own, such as ``half_batch``).  Each is compared by the cell's
``check`` and judged by the harness's ``judge`` against the cell's
``change_gap`` limit, as a run's output is.  One JSON line per seed,
then the largest program reading and the smallest of each stand-in.
The benchmark's own runs never run a stand-in.
"""
import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / 'src')]
# libtpu writes its logs to a fixed path under /tmp unless told not to
os.environ.setdefault('TPU_LOG_DIR', 'disabled')

#: the numbers a stand-in gives: it replaces the program's output, not
#: the window
COMPARED = ('change_gap',)


def readings(spec: dict, seed: int, *, program: bool = True,
             stand_ins=None) -> dict:
    """``{name: {'change_gap', 'correct', ...}}`` of one seed: ``program``
    and each stand-in."""
    from bench import build, harness
    limits = {k: spec['limits'][k] for k in COMPARED}
    t0 = time.perf_counter()
    cell = build.build(spec, seed)
    out = {}
    got = None
    if program:
        s = harness.set_up(cell, harness.Compiles(), t0)
        got = s.first.capture
        out['setup_s'] = s.setup_s
        harness.free(s)
    rows = {}
    if got is not None:
        rows['program'] = got
    for name in (cell.stand_ins if stand_ins is None else stand_ins):
        rows[name] = cell.stand_in(name)
    for name, global_ in rows.items():
        t0 = time.perf_counter()
        r = cell.check(global_)
        _, correct = harness.judge(limits, r)
        out[name] = {**{k: r[k] for k in COMPARED}, 'correct': correct,
                     'leaf_gaps': r['leaf_gaps'],
                     'check_s': time.perf_counter() - t0}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--program', type=int, choices=(0, 1), default=1)
    ap.add_argument('--stand-ins', default=None,
                    help="comma-separated, '' for none; default: all the "
                         'cell has')
    args = ap.parse_args(argv)
    from bench import harness
    spec = harness.cell_spec(args.workload)
    import jax
    if jax.devices()[0].platform != 'tpu':
        print('calibrate: no TPU', file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    names = (None if args.stand_ins is None
             else [n for n in args.stand_ins.split(',') if n])
    rows = []
    for seed in (int(s) for s in args.seeds.split(',')):
        rows.append({'seed': seed, **readings(
            spec, seed, program=bool(args.program), stand_ins=names)})
        print(json.dumps(rows[-1]), flush=True)
    summary = {'workload': args.workload, 'seeds': len(rows),
               'limit': spec['limits']['change_gap']}
    for key in rows[0]:
        if isinstance(rows[0][key], dict):
            gaps = [r[key]['change_gap'] for r in rows]
            summary[key] = {'max' if key == 'program' else 'min':
                            max(gaps) if key == 'program' else min(gaps),
                            'all_correct': all(r[key]['correct']
                                               for r in rows)}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
