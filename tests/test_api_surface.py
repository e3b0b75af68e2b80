"""Public-API snapshot: ``repro.api.__all__``, the spec field names and
the registry contents are a contract — a failing test here means the
public surface changed, which must be deliberate (update the snapshot in
the same commit, and the migration notes in docs/ARCHITECTURE.md).

No hypothesis dependency — this module must run in a bare environment.
"""
import dataclasses

import pytest

from repro import api

EXPECTED_ALL = {
    'CompiledRunner', 'CsaflSpec', 'ExecSpec', 'Experiment', 'FedAsyncSpec',
    'FedAvgSpec', 'FedCSSpec', 'Form', 'History', 'LocalSpec', 'PROTOCOLS',
    'ProtocolDef', 'ProtocolSpec', 'RoundRecord', 'STALENESS_FNS',
    'SafaSpec', 'SeaflSpec', 'SweepMember', 'SweepSpec', 'Task',
    'WEIGHTED_SCHEMES', 'check_compat', 'init_fleet_global',
    'precompute_weighted_schedule', 'register', 'spec',
    'staleness_discount',
}

SPEC_FIELDS = {
    'SafaSpec': ('fraction', 'lag_tolerance', 'quantize_uploads'),
    'FedAvgSpec': ('fraction', 'sampler'),
    'FedCSSpec': ('fraction',),
    'LocalSpec': ('fraction',),
    'FedAsyncSpec': ('alpha', 'staleness_exp', 'staleness_fn', 'hinge_a',
                     'hinge_b'),
    'SeaflSpec': ('alpha', 'staleness_fn', 'staleness_exp', 'hinge_a',
                  'hinge_b', 'use_loss', 'loss_coef'),
    'CsaflSpec': ('clusters', 'alpha', 'staleness_fn', 'staleness_exp',
                  'hinge_a', 'hinge_b'),
    'ExecSpec': ('engine', 'wire', 'use_kernel', 'schedule', 'shard',
                 'eval_every', 'numeric'),
    'SweepSpec': ('members', 'tasks'),
    'SweepMember': ('env', 'fraction', 'lag_tolerance', 'seed', 'alpha',
                    'staleness_exp', 'overrides'),
}


def test_all_snapshot():
    assert set(api.__all__) == EXPECTED_ALL
    for name in api.__all__:
        assert hasattr(api, name), name


def test_spec_field_snapshot():
    for cls_name, fields in SPEC_FIELDS.items():
        cls = getattr(api, cls_name)
        assert tuple(f.name for f in dataclasses.fields(cls)) == fields, \
            cls_name


def test_protocol_specs_are_frozen():
    for cls_name in ('SafaSpec', 'FedAvgSpec', 'FedCSSpec', 'LocalSpec',
                     'FedAsyncSpec', 'SeaflSpec', 'CsaflSpec', 'ExecSpec',
                     'SweepSpec'):
        inst = getattr(api, cls_name)() if cls_name != 'SweepSpec' \
            else api.SweepSpec(members=())
        with pytest.raises(dataclasses.FrozenInstanceError):
            inst.some_field = 1


def test_registry_snapshot():
    assert {d.name for d in api.PROTOCOLS.values()} == \
        {'safa', 'fedavg', 'fedcs', 'local', 'fedasync', 'seafl', 'csafl'}
    assert set(api.PROTOCOLS) == {api.SafaSpec, api.FedAvgSpec,
                                  api.FedCSSpec, api.LocalSpec,
                                  api.FedAsyncSpec, api.SeaflSpec,
                                  api.CsaflSpec}
    for pdef in api.PROTOCOLS.values():
        for fn in ('precompute', 'fleet_precompute', 'form'):
            assert callable(getattr(pdef, fn)), (pdef.name, fn)


def test_exec_spec_defaults():
    ex = api.ExecSpec()
    assert (ex.engine, ex.wire, ex.use_kernel, ex.shard, ex.eval_every,
            ex.numeric) == (None, 'f32', False, True, 10, True)
