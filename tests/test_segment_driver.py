"""The one segment driver (``protocol._scan`` and its engines, run by
``api._run_segment``), over every state form the registry admits: the
second scan segment compiles nothing, and the scan, loop and fleet
engines agree on the first segment within ``conformance.ENGINE_TOL``.

No hypothesis dependency — this module must run in a bare environment.
"""
import jax
import pytest

import conformance as C
from repro import api
from repro.core import protocol

SCHEDULES = ('dense', 'sparse', 'sparse_delta', 'sparse_tier')


def _forms() -> dict:
    """test id -> (spec class, exec fields), one per distinct form of
    each registered protocol."""
    out, seen = {}, set()
    for pdef in api.PROTOCOLS.values():
        for schedule in SCHEDULES:
            for use_kernel in (False, 'packed'):
                ex = dict(schedule=schedule, use_kernel=use_kernel)
                try:
                    api.check_compat(pdef.spec_cls(), api.ExecSpec(**ex))
                except ValueError:
                    continue
                form = pdef.form(api.ExecSpec(**ex))
                if (pdef.name, form) in seen:
                    continue
                seen.add((pdef.name, form))
                fid = f'{pdef.name}-{schedule}' + '-packed' * form.packed
                out[fid] = (pdef.spec_cls, ex)
    return out


FORMS = _forms()


@pytest.fixture
def compiles():
    """A running count of the backend compiles made during the test."""
    count = [0]

    def on(name, secs, **kw):
        del secs, kw
        if name == '/jax/core/compile/backend_compile_duration':
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    yield count
    jax.monitoring.unregister_event_duration_listener(on)


def test_every_protocol_has_a_form():
    assert {fid.split('-')[0] for fid in FORMS} == \
        {p.name for p in api.PROTOCOLS.values()}
    assert len({api.PROTOCOLS[cls].form(api.ExecSpec(**ex)).engine
                for cls, ex in FORMS.values()}) == len(protocol.ENGINES)


@pytest.mark.parametrize('fid', sorted(FORMS))
def test_scan_loop_and_fleet_agree(fid, monkeypatch, compiles):
    spec_cls, ex = FORMS[fid]
    spec = spec_cls()
    name = api.PROTOCOLS[spec_cls].form(api.ExecSpec(**ex)).engine
    engine = getattr(protocol, name)
    made = []       # compiles during each engine call

    def counted(*args, **kw):
        before = compiles[0]
        out = engine(*args, **kw)
        made.append(compiles[0] - before)
        return out

    monkeypatch.setattr(protocol, name, counted)
    C.run_single(spec, exec_kw=ex, max_segments=2)
    monkeypatch.undo()
    assert len(made) == 2
    assert made[1] == 0, f'{fid}: the second segment compiled'

    scan = C.run_single(spec, exec_kw=ex, max_segments=1)
    loop = C.run_single(spec, engine='loop', exec_kw=ex, max_segments=1)
    members = [C.member_for(spec, C.fresh_env(3), seed=0),
               C.member_for(spec, C.fresh_env(4), seed=1)]
    fleet = C.run_sweep(spec, members, exec_kw=ex, max_segments=1)
    C.assert_tree_close(scan.final_global, loop.final_global,
                        f'{fid}: scan vs loop')
    C.assert_tree_close(scan.final_global, fleet[0].final_global,
                        f'{fid}: scan vs fleet member 0')
