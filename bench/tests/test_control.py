"""The control of each cell -- the plain reference computed in the
precision below the configuration's, in the program's place -- must come
out not correct by the harness's own judgement, where a sound run comes
out correct; and so must each fault planted in the reference.

This is ``bench/calibrate.py`` at the tiny sizes of ``tiny.py``:
bfloat16 in place of the CNN's float32, an int4 wire in place of the
int8 one.
"""
import json

import pytest

from bench import calibrate
from bench.tests import tiny


@pytest.mark.parametrize('cell', sorted(tiny.TINY))
def test_control_fails_the_limit(cell):
    spec = tiny.spec(cell)
    for seed in (1, 2, 2**31 + 3):
        r = calibrate.readings(spec, seed, stand_ins=('control',))
        assert r['program']['correct'], json.dumps(r)
        assert not r['control']['correct'], json.dumps(r)
        assert r['control']['change_gap'] >= \
            3 * r['program']['change_gap'], json.dumps(r)


@pytest.mark.parametrize('cell', sorted(tiny.TINY))
def test_planted_faults_fail_the_limit(cell):
    spec = tiny.spec(cell)
    r = calibrate.readings(spec, 5, program=False)
    faults = [k for k in r if k != 'control']
    assert 'half_uploads' in faults
    assert ('half_batch' in faults) == (spec['config']['task'] == 'cnn')
    for name in faults:
        assert not r[name]['correct'], json.dumps(r)
