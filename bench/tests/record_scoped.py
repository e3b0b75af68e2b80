#!/usr/bin/env python3
"""Record the scoped traces the phase tests read, on one chip.

    python bench/tests/record_scoped.py <out_dir>

Runs ``record_trace.py``'s two tiny cells, whose programs carry the phase
scopes of ``repro.obs``, and writes their traces and results as it does,
plus ``counters.json``: the program's counters as the tiny cells traced
them (the lag-tier cell's int8 tier-rows kernel among them).
"""
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import record_trace  # noqa: E402  (puts the checkout and src/ on the path)


def main(out: str) -> int:
    from repro import obs
    rc = record_trace.main(out)
    with open(pathlib.Path(out) / 'counters.json', 'w') as f:
        json.dump(obs.counters(), f, indent=1)
    return rc


if __name__ == '__main__':
    sys.exit(main(sys.argv[1]))
