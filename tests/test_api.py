"""Unified experiment API: golden equivalence with the legacy runners,
checkpoint/resume bit-identity, per-member-Task sweeps, and the protocol
registry.

No hypothesis dependency — this module must run in a bare environment.
"""
import dataclasses
import json
import warnings

import jax
import numpy as np
import pytest

from repro.core import api, federation
from repro.data import make_regression, partition
from repro.data.tasks import regression_task
from repro.fedsim import FLEnv

BASE = dict(m=5, crash_prob=0.3, dataset_size=506, batch_size=5,
            epochs=3, t_lim=830.0, seed=3)


def _env(**kw):
    base = dict(BASE)
    base.update(kw)
    return FLEnv(**base)


@pytest.fixture(scope='module')
def reg_task():
    env = _env()
    x, y = make_regression()
    data = partition(x, y, env.partition_sizes, 5, seed=1)
    return regression_task(data, lr=1e-3, epochs=3)


def _assert_tree_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _legacy(name, task, env, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter('ignore', DeprecationWarning)
        return federation.RUNNERS[name](task, env, **kw)


def _legacy_sweep(task, members, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter('ignore', DeprecationWarning)
        return federation.run_sweep(task, members, **kw)


_PROTO_KW = {
    'safa': dict(fraction=0.5, lag_tolerance=5),
    'fedavg': dict(fraction=0.5),
    'fedcs': dict(fraction=0.5),
    'local': dict(fraction=0.5),
    'fedasync': {},
}
_WIRES = {'safa': ('f32', 'int8'), 'fedavg': ('f32', 'int8'),
          'fedcs': ('f32', 'int8'), 'local': ('f32',),
          'fedasync': ('f32',)}


class TestGoldenEquivalence:
    """Acceptance criterion: every legacy ``run_*`` call is bit-identical
    to its ``Experiment`` spelling, across all five protocols x
    {scan, loop} x {f32, int8-where-supported}."""

    @pytest.mark.parametrize('proto,engine,wire', [
        (p, e, w)
        for p in ('safa', 'fedavg', 'fedcs', 'local', 'fedasync')
        for e in ('scan', 'loop')
        for w in _WIRES[p]])
    def test_legacy_matches_experiment(self, reg_task, proto, engine, wire):
        kw = dict(_PROTO_KW[proto])
        legacy_kw = dict(kw, rounds=6, eval_every=3, engine=engine)
        if wire != 'f32':
            legacy_kw['wire'] = wire
        h_old = _legacy(proto, reg_task, _env(), **legacy_kw)
        exp = api.Experiment(
            reg_task, _env(), api.spec(proto, **kw),
            api.ExecSpec(engine=engine, wire=wire, eval_every=3),
            rounds=6)
        h_new = exp.compile().run()
        assert h_new.protocol == h_old.protocol
        _assert_tree_equal(h_new.final_global, h_old.final_global)
        assert h_new.evals() == h_old.evals()
        assert h_new.futility == h_old.futility
        assert h_new.records == h_old.records

    def test_timing_only_matches(self):
        for proto in federation.RUNNERS:
            kw = dict(_PROTO_KW[proto])
            h_old = _legacy(proto, None, _env(), rounds=10, numeric=False,
                            **kw)
            h_new = api.Experiment(
                None, _env(), api.spec(proto, **kw),
                api.ExecSpec(numeric=False), rounds=10).compile().run()
            assert h_new.records == h_old.records, proto
            assert h_new.futility == h_old.futility, proto

    def test_legacy_sweep_matches_run_sweep(self, reg_task):
        def members():
            return [api.SweepMember(env=_env(draw_seed=s), fraction=0.5,
                                    lag_tolerance=tau, seed=s)
                    for s, tau in ((0, 5), (1, 2))]
        h_old = _legacy_sweep(reg_task, members(), rounds=6, eval_every=3)
        exp = api.Experiment(reg_task, _env(),
                             api.SafaSpec(fraction=0.5, lag_tolerance=5),
                             api.ExecSpec(eval_every=3), rounds=6)
        h_new = exp.compile().run_sweep(members())
        for a, b in zip(h_new, h_old):
            _assert_tree_equal(a.final_global, b.final_global)
            assert a.evals() == b.evals()
            assert a.futility == b.futility

    def test_experiment_schedule_cached_across_runs(self, reg_task):
        """The env rng is consumed once per Experiment: repeated run()
        calls replay the same schedule and produce the same bits."""
        exp = api.Experiment(reg_task, _env(),
                             api.SafaSpec(fraction=0.5, lag_tolerance=5),
                             api.ExecSpec(eval_every=2), rounds=4)
        runner = exp.compile()
        h1, h2 = runner.run(), runner.run()
        _assert_tree_equal(h1.final_global, h2.final_global)
        assert h1.evals() == h2.evals()

    def test_repeated_runs_do_not_alias_records(self, reg_task):
        """Histories from the same (schedule-cached) Experiment must not
        share RoundRecord objects: a later partial run would otherwise
        report the earlier run's evals for rounds it never executed."""
        exp = api.Experiment(reg_task, _env(),
                             api.SafaSpec(fraction=0.5, lag_tolerance=5),
                             api.ExecSpec(eval_every=3), rounds=9)
        runner = exp.compile()
        full = runner.run()
        partial = runner.run(max_segments=1)
        assert len(full.evals()) == 3
        assert len(partial.evals()) == 1        # no stale evals leak in
        assert full.records[0] is not partial.records[0]


class TestValidation:
    def test_unknown_wire_engine_kernel(self, reg_task):
        with pytest.raises(ValueError, match='wire'):
            api.check_compat(api.SafaSpec(), api.ExecSpec(wire='int4'))
        with pytest.raises(ValueError, match='engine'):
            api.check_compat(api.SafaSpec(), api.ExecSpec(engine='warp'))
        with pytest.raises(ValueError, match='use_kernel'):
            api.check_compat(api.SafaSpec(), api.ExecSpec(use_kernel='Packed'))

    def test_quantize_uploads_wire_exclusive(self):
        with pytest.raises(ValueError, match='reference'):
            api.check_compat(api.SafaSpec(quantize_uploads=True),
                             api.ExecSpec(wire='int8'))

    def test_unregistered_spec_rejected(self):
        @dataclasses.dataclass(frozen=True)
        class GossipSpec(api.ProtocolSpec):
            fanout: int = 2
        with pytest.raises(TypeError, match='unregistered'):
            api.check_compat(GossipSpec())

    def test_unknown_proto_name(self):
        with pytest.raises(ValueError, match='proto'):
            api.spec('gossip')

    def test_wire_rejected_uniformly_for_local_and_fedasync(self, reg_task):
        """Satellite: one check_compat, one message — on the new surface
        AND through the legacy run_local/run_fedasync shims."""
        messages = set()
        for name in ('local', 'fedasync'):
            with pytest.raises(ValueError, match='upload-aggregate wire') \
                    as ei:
                api.check_compat(api.spec(name), api.ExecSpec(wire='int8'))
            messages.add(str(ei.value).replace(name, '<proto>'))
        with pytest.raises(ValueError, match='upload-aggregate wire') as e1:
            _legacy('local', reg_task, _env(), fraction=0.5, rounds=2,
                    wire='int8')
        messages.add(str(e1.value).replace('local', '<proto>'))
        with pytest.raises(ValueError, match='upload-aggregate wire') as e2:
            _legacy('fedasync', reg_task, _env(), rounds=2, wire='int8')
        messages.add(str(e2.value).replace('fedasync', '<proto>'))
        assert len(messages) == 1  # identical wording everywhere

    def test_use_kernel_rejected_for_non_safa(self, reg_task):
        for name in ('fedavg', 'local', 'fedasync'):
            with pytest.raises(ValueError, match='use_kernel'):
                api.check_compat(api.spec(name),
                                 api.ExecSpec(use_kernel='packed'))
        with pytest.raises(ValueError, match='use_kernel'):
            _legacy('local', reg_task, _env(), fraction=0.5, rounds=2,
                    use_kernel=True)

    def test_sweep_spec_length_mismatch(self, reg_task):
        with pytest.raises(ValueError, match='task'):
            api.SweepSpec(members=(api.SweepMember(env=_env()),),
                          tasks=(reg_task, reg_task))


class TestHistoryRoundTrip:
    def test_to_dict_from_dict_through_json(self, reg_task):
        h = api.Experiment(reg_task, _env(),
                           api.SafaSpec(fraction=0.5, lag_tolerance=5),
                           api.ExecSpec(eval_every=2),
                           rounds=4).compile().run()
        d = json.loads(json.dumps(h.to_dict()))
        h2 = api.History.from_dict(d)
        assert h2.protocol == h.protocol
        assert h2.futility == h.futility
        assert h2.best_eval == h.best_eval
        assert h2.records == h.records          # exact floats: json reprs
        assert h2.evals() == h.evals()
        assert h2.final_global is None          # excluded by contract

    def test_timing_only_roundtrip(self):
        h = api.Experiment(None, _env(), api.FedAvgSpec(fraction=0.3),
                           api.ExecSpec(numeric=False),
                           rounds=8).compile().run()
        h2 = api.History.from_dict(json.loads(json.dumps(h.to_dict())))
        assert h2.records == h.records


class TestCheckpointResume:
    def _exp(self, task, **kw):
        cfg = dict(rounds=9, eval_every=3)
        cfg.update(kw)
        return api.Experiment(task, _env(),
                              api.SafaSpec(fraction=0.5, lag_tolerance=5),
                              api.ExecSpec(eval_every=cfg['eval_every']),
                              rounds=cfg['rounds'])

    def test_resume_single_run_bit_identical(self, reg_task, tmp_path):
        """Acceptance criterion: a run killed mid-way resumes from its
        checkpoint to a bit-identical History."""
        golden = self._exp(reg_task).compile().run()
        path = str(tmp_path / 'run.npz')
        partial = self._exp(reg_task).compile().run(checkpoint=path,
                                                    max_segments=1)
        assert len(partial.evals()) == 1        # killed after segment 1
        resumed = self._exp(reg_task).compile().run(checkpoint=path)
        _assert_tree_equal(resumed.final_global, golden.final_global)
        assert resumed.evals() == golden.evals()
        assert resumed.best_eval == golden.best_eval
        assert resumed.futility == golden.futility

    def test_resume_loop_engine(self, reg_task, tmp_path):
        """Checkpoint boundaries are eval segments, so the reference loop
        engine resumes too."""
        mk = lambda: api.Experiment(
            reg_task, _env(), api.SafaSpec(fraction=0.5, lag_tolerance=5),
            api.ExecSpec(engine='loop', eval_every=3), rounds=6)
        golden = mk().compile().run()
        path = str(tmp_path / 'loop.npz')
        mk().compile().run(checkpoint=path, max_segments=1)
        resumed = mk().compile().run(checkpoint=path)
        _assert_tree_equal(resumed.final_global, golden.final_global)
        assert resumed.evals() == golden.evals()

    def test_resume_mid_sweep_bit_identical(self, reg_task, tmp_path):
        """Acceptance criterion: a checkpointed sweep killed mid-run
        resumes to bit-identical per-member Histories."""
        def members():
            return [api.SweepMember(env=_env(draw_seed=s), fraction=f,
                                    lag_tolerance=tau, seed=s)
                    for s, (f, tau) in enumerate(((0.5, 5), (0.3, 2),
                                                  (1.0, 10), (0.1, 1)))]
        golden = self._exp(reg_task).compile().run_sweep(members())
        path = str(tmp_path / 'sweep.npz')
        partial = self._exp(reg_task).compile().run_sweep(
            members(), checkpoint=path, max_segments=1)
        assert all(len(h.evals()) == 1 for h in partial)
        resumed = self._exp(reg_task).compile().run_sweep(members(),
                                                          checkpoint=path)
        for a, b in zip(resumed, golden):
            _assert_tree_equal(a.final_global, b.final_global)
            assert a.evals() == b.evals()
            assert a.best_eval == b.best_eval

    def test_fingerprint_mismatch_rejected(self, reg_task, tmp_path):
        path = str(tmp_path / 'fp.npz')
        self._exp(reg_task).compile().run(checkpoint=path, max_segments=1)
        other = api.Experiment(reg_task, _env(),
                               api.SafaSpec(fraction=0.5, lag_tolerance=2),
                               api.ExecSpec(eval_every=3), rounds=9)
        with pytest.raises(ValueError, match='fingerprint'):
            other.compile().run(checkpoint=path)

    def test_fingerprint_covers_task_data(self, reg_task, tmp_path):
        """Resuming a carry against different client data would silently
        mix two runs — the task participates in the fingerprint."""
        path = str(tmp_path / 'task_fp.npz')
        self._exp(reg_task).compile().run(checkpoint=path, max_segments=1)
        env = _env()
        x, y = make_regression()
        other_task = regression_task(
            partition(x, y, env.partition_sizes, 5, seed=2),  # other split
            lr=1e-3, epochs=3)
        with pytest.raises(ValueError, match='fingerprint'):
            self._exp(other_task).compile().run(checkpoint=path)

    def test_sequential_sweep_checkpoint_rejected(self, reg_task, tmp_path):
        exp = api.Experiment(reg_task, _env(),
                             api.SafaSpec(fraction=0.5, lag_tolerance=5),
                             api.ExecSpec(engine='sequential'), rounds=4)
        with pytest.raises(ValueError, match='fleet'):
            exp.compile().run_sweep([api.SweepMember(env=_env())],
                                    checkpoint=str(tmp_path / 'x.npz'))


class TestPerMemberTasks:
    """ROADMAP item: sweeps over members with *different client data*
    (padded stacking), closing the multi-seed env-sweep gap."""

    def _setup(self):
        # different env seeds => different partition sizes => different
        # batch counts: the padding path is actually exercised
        envs = [_env(seed=s) for s in (3, 4)]
        x, y = make_regression()
        tasks = [regression_task(partition(x, y, e.partition_sizes, 5,
                                           seed=1), lr=1e-3, epochs=3)
                 for e in envs]
        members = [api.SweepMember(env=e, fraction=0.5, lag_tolerance=5,
                                   seed=i) for i, e in enumerate(envs)]
        assert tasks[0]._x.shape != tasks[1]._x.shape  # ragged for real
        return members, tasks

    def _exp(self):
        return api.Experiment(None, _env(),
                              api.SafaSpec(fraction=0.5, lag_tolerance=5),
                              api.ExecSpec(eval_every=3), rounds=6)

    def test_fleet_bit_identical_to_sequential(self):
        """Acceptance criterion: per-member Tasks via padded stacking,
        fleet vs sequential bit-identity (the sequential members train on
        their own *unpadded* data — padding must be an exact no-op)."""
        members, tasks = self._setup()
        hf = self._exp().compile().run_sweep(
            api.SweepSpec(members=members, tasks=tasks))
        members2, tasks2 = self._setup()
        exp = api.Experiment(None, _env(),
                             api.SafaSpec(fraction=0.5, lag_tolerance=5),
                             api.ExecSpec(engine='sequential', eval_every=3),
                             rounds=6)
        hs = exp.compile().run_sweep(api.SweepSpec(members=members2,
                                                   tasks=tasks2))
        for a, b in zip(hf, hs):
            _assert_tree_equal(a.final_global, b.final_global)
            assert a.evals() == b.evals()

    def test_fleet_member_matches_single_run(self):
        members, tasks = self._setup()
        hf = self._exp().compile().run_sweep(
            api.SweepSpec(members=members, tasks=tasks))
        members2, tasks2 = self._setup()
        for s in range(2):
            single = api.Experiment(
                tasks2[s], members2[s].env,
                api.SafaSpec(fraction=0.5, lag_tolerance=5),
                api.ExecSpec(eval_every=3), rounds=6,
                seed=members2[s].seed).compile().run()
            _assert_tree_equal(hf[s].final_global, single.final_global)
            assert hf[s].evals() == single.evals()

    def test_legacy_run_sweep_accepts_task_list(self):
        members, tasks = self._setup()
        hl = _legacy_sweep(tasks, members, rounds=6, eval_every=3)
        members2, tasks2 = self._setup()
        hn = self._exp().compile().run_sweep(
            api.SweepSpec(members=members2, tasks=tasks2))
        for a, b in zip(hl, hn):
            _assert_tree_equal(a.final_global, b.final_global)
            assert a.evals() == b.evals()

    def test_local_per_member_tasks(self):
        """The train-context threading also covers the local fleet (no
        global carry; vmapped aggregation at eval points)."""
        members, tasks = self._setup()
        exp = api.Experiment(None, _env(), api.LocalSpec(fraction=0.5),
                             api.ExecSpec(eval_every=3), rounds=6)
        hf = exp.compile().run_sweep(api.SweepSpec(members=members,
                                                   tasks=tasks))
        members2, tasks2 = self._setup()
        exp2 = api.Experiment(None, _env(), api.LocalSpec(fraction=0.5),
                              api.ExecSpec(engine='sequential',
                                           eval_every=3), rounds=6)
        hs = exp2.compile().run_sweep(api.SweepSpec(members=members2,
                                                    tasks=tasks2))
        for a, b in zip(hf, hs):
            _assert_tree_equal(a.final_global, b.final_global)
            assert a.evals() == b.evals()

    def test_stacked_tasks_validation(self):
        from repro.data.tasks import stack_tasks
        members, tasks = self._setup()
        env = _env(seed=5)
        x, y = make_regression()
        data = partition(x, y, env.partition_sizes, 5, seed=1)
        with pytest.raises(ValueError, match='epoch'):
            stack_tasks([tasks[0], regression_task(data, lr=1e-3, epochs=2)])
        with pytest.raises(ValueError, match='lr'):
            # one compiled train step serves all members: differing lr
            # would silently train member 1 with member 0's step
            stack_tasks([tasks[0], regression_task(data, lr=1e-1, epochs=3)])
        with pytest.raises(ValueError, match='empty'):
            stack_tasks([])


class TestRegistry:
    def test_builtin_registry_contents(self):
        import repro.api  # noqa: F401 — registers the aggregation family
        assert {d.name for d in api.PROTOCOLS.values()} == \
            {'safa', 'fedavg', 'fedcs', 'local', 'fedasync', 'seafl',
             'csafl'}
        assert api.PROTOCOLS[api.SafaSpec].uses_cache
        assert not api.PROTOCOLS[api.LocalSpec].supports_wire

    def test_register_new_variant_without_touching_federation(self,
                                                              reg_task):
        """A new spec type registers with the precompute hooks and state
        forms of an existing protocol and immediately runs through
        Experiment — the extension point a SEAFL-style
        staleness-discounted variant would use."""
        @dataclasses.dataclass(frozen=True)
        class TwinSafaSpec(api.ProtocolSpec):
            fraction: float = 0.5
            lag_tolerance: int = 5

        base = api.PROTOCOLS[api.SafaSpec]
        pdef = api.ProtocolDef(
            name='safa-twin', spec_cls=TwinSafaSpec,
            precompute=lambda env, sp, *, rounds, seed: base.precompute(
                env, api.SafaSpec(fraction=sp.fraction,
                                  lag_tolerance=sp.lag_tolerance),
                rounds=rounds, seed=seed),
            fleet_precompute=base.fleet_precompute,
            form=base.form,
            uses_cache=True, supports_wire=True, supports_kernel=True)
        api.register(pdef)
        try:
            with pytest.raises(ValueError, match='registered'):
                api.register(pdef)           # duplicate names rejected
            h = api.Experiment(reg_task, _env(), TwinSafaSpec(),
                               api.ExecSpec(eval_every=2),
                               rounds=4).compile().run()
            ref = api.Experiment(reg_task, _env(), api.SafaSpec(),
                                 api.ExecSpec(eval_every=2),
                                 rounds=4).compile().run()
            _assert_tree_equal(h.final_global, ref.final_global)
            assert h.evals() == ref.evals()
            assert api.spec('safa-twin', fraction=0.3).fraction == 0.3
        finally:
            del api.PROTOCOLS[TwinSafaSpec]
            del api._BY_NAME['safa-twin']
