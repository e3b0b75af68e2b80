#!/usr/bin/env python3
"""Record the small traces the trace-reduction tests read, on one chip.

    python bench/tests/record_trace.py <out_dir>

Runs two tiny cells through the harness with the profiler on -- the
lag-tier int8 round (m=2,000, d=16,384) and the dense CNN round (m=4,
a narrowed CNN) -- writes each profile as ``<out_dir>/<name>.xplane.pb.gz``
(gzipped) and the numbers the harness read from it as ``<name>.json``.
"""
import gzip
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / 'src')]
# libtpu writes its logs to a fixed path under /tmp unless told not to
os.environ.setdefault('TPU_LOG_DIR', 'disabled')


def main(out: str) -> int:
    from bench import harness
    from bench.tests import tiny
    out_dir = pathlib.Path(out)
    out_dir.mkdir(parents=True, exist_ok=True)
    for cell, name in (('xdevice_1m.safa_tier_int8', 'tier_int8'),
                       ('paper_cnn.safa_dense', 'dense')):
        raw = out_dir / f'{name}.xplane.pb'
        result = harness.run_cell(
            tiny.spec(cell, ('idle_share', 'train_share')),
            seed=5, seconds=0.3, trace=True, chips=1,
            t_start=time.perf_counter(), keep_trace=str(raw))
        with open(raw, 'rb') as f, \
                gzip.open(out_dir / f'{name}.xplane.pb.gz', 'wb') as g:
            g.write(f.read())
        raw.unlink()
        with open(out_dir / f'{name}.json', 'w') as f:
            json.dump(result, f, indent=1)
        print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1]))
