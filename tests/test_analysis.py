"""Mutation tests for ``repro.analysis``: every rule must FIRE on a
seeded broken fixture, and the real registry must pass CLEAN.

A static checker that never fails is indistinguishable from one that
never runs, so each rule here gets a deliberately-broken input — a
corrupted schedule, a registry def with a wrong budget, a source tree
with the exact smell the AST rule hunts — and the test asserts that rule
(and only that rule is asserted; collateral findings are fine) reports
the violation.  The clean-side tests pin the pass/fail boundary from the
other side: conventions and schedule passes green over the whole
registry, and the flagship compressed-SAFA jaxpr cells green under their
declared 2-dispatch budget.
"""
import copy
import dataclasses
import itertools
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro import analysis, api, fedsim
from repro.analysis import jaxpr_checks
from repro.analysis.conventions import check_conventions
from repro.core import agg_schemes, federation, protocol

ROUNDS = 8
ENV = dict(m=5, crash_prob=0.3, dataset_size=506, batch_size=5, epochs=3,
           t_lim=830.0)


def fresh_env(seed=3):
    return fedsim.EnvSpec(seed=seed, **ENV).build()


def safa_schedule(form='dense'):
    return federation.precompute_safa_schedule(
        fresh_env(), fraction=0.5, lag_tolerance=2, rounds=ROUNDS,
        form=form)


def failed_rules(report):
    return {f.rule for f in report.failures}


# ---------------------------------------------------------------------------
# Clean side: the real registry passes
# ---------------------------------------------------------------------------

class TestRegistryClean:
    def test_conventions_pass(self):
        rep = check_conventions()
        assert rep.ok, '\n'.join(str(f) for f in rep.failures)

    def test_schedules_pass(self):
        rep = analysis.check_schedules()
        assert rep.ok, '\n'.join(str(f) for f in rep.failures)
        # every schedule rule actually ran against some subject
        assert {'SCH001', 'SCH002', 'SCH003', 'SCH004', 'SCH005',
                'SCH006'} <= rep.rules()

    def test_flagship_compressed_cells_pass(self):
        # the "fully compressed SAFA round is exactly 2 dispatches"
        # invariant, proven on the lowered programs of both engines
        pdef = api.PROTOCOLS[api.SafaSpec]
        cells = [
            jaxpr_checks.Cell(pdef, api.SafaSpec(), api.ExecSpec(
                engine=engine, wire='int8', use_kernel='packed',
                schedule='dense', eval_every=jaxpr_checks.SEG))
            for engine in ('scan', 'fleet')]
        assert all(pdef.dispatch_budget(c.ex) == 2 for c in cells)
        rep = jaxpr_checks.check_cells(cells=cells)
        assert rep.ok, '\n'.join(str(f) for f in rep.failures)
        assert {'JAX001', 'JAX002', 'JAX003', 'JAX004', 'JAX005',
                'JAX006'} <= rep.rules()


# ---------------------------------------------------------------------------
# SCH rules: corrupted schedules
# ---------------------------------------------------------------------------

class TestScheduleMutations:
    def test_sch004_role_subset_violation_fires(self):
        sched = safa_schedule()
        t, k = next((t, k) for t in range(ROUNDS) for k in range(ENV['m'])
                    if not sched.committed[t, k])
        sched.picked[t, k] = True       # picked but never committed
        assert 'SCH004' in failed_rules(analysis.verify_schedule(sched))

    def test_sch004_lag_bound_fires(self):
        sched = safa_schedule()
        # never sync, never commit: every client's version pins at 0 and
        # staleness grows past any tau (other masks cleared so the
        # subset structure stays valid and only the lag bound trips)
        for mask in (sched.sync, sched.committed, sched.picked,
                     sched.undrafted, sched.deprecated):
            mask[:] = False
        rep = analysis.verify_schedule(sched, lag_tolerance=2)
        assert 'SCH004' in failed_rules(rep)
        assert any('staleness' in f.detail for f in rep.failures)

    def test_sch006_unsorted_indices_fire(self):
        sched = safa_schedule(form='sparse')
        t = next(t for t in range(ROUNDS)
                 if (sched.idx[t] < sched.m).sum() >= 2)
        sched.idx[t, [0, 1]] = sched.idx[t, [1, 0]]
        assert 'SCH006' in failed_rules(analysis.verify_schedule(sched))

    def test_sch003_live_sentinel_fires(self):
        sched = safa_schedule(form='sparse')
        t = next(t for t in range(ROUNDS)
                 if (sched.idx[t] >= sched.m).any())
        sched.roles[t, -1] = protocol.ROLE_PICKED   # sentinel grows a role
        assert 'SCH003' in failed_rules(analysis.verify_schedule(sched))

    def test_sch001_read_write_clash_fires(self):
        sched = safa_schedule(form='sparse_tier')
        t, j = next(
            (t, j) for t in range(ROUNDS) for j in range(sched.width)
            if sched.global_dst[t] != sched.scratch
            and sched.idx[t, j] < sched.m
            and sched.cache_src[t, j] != sched.scratch)
        # the round's global write now also feeds a cache read: in-place
        # aliasing would clobber the row mid-kernel
        sched.cache_src[t, j] = sched.global_dst[t]
        assert 'SCH001' in failed_rules(analysis.verify_schedule(sched))

    def test_sch002_padded_capacity_fires(self):
        sched = copy.deepcopy(safa_schedule(form='sparse_tier'))
        old_scratch = sched.scratch
        sched.capacity += 1             # claim one dead row
        for arr in (sched.base_src, sched.cache_src, sched.cache_dst):
            arr[arr == old_scratch] = sched.scratch
        sched.global_dst[sched.global_dst == old_scratch] = sched.scratch
        rep = analysis.verify_schedule(sched)
        assert 'SCH002' in failed_rules(rep)

    def test_sch005_negative_weight_fires(self):
        sched = agg_schemes.precompute_weighted_schedule(
            fresh_env(), rounds=ROUNDS, scheme='seafl')
        t, k = next((t, k) for t in range(ROUNDS) for k in range(ENV['m'])
                    if sched.committed[t, k])
        sched.wrow[t, k] = -0.1
        assert 'SCH005' in failed_rules(analysis.verify_schedule(sched))

    def test_sch005_async_order_fires(self):
        sched = federation.precompute_fedasync_schedule(
            fresh_env(), rounds=ROUNDS)
        sched.order[0, 0] = sched.order[0, 1]   # no longer a permutation
        assert 'SCH005' in failed_rules(analysis.verify_schedule(sched))


# ---------------------------------------------------------------------------
# REP rules: seeded source trees (and a poisoned registry for REP003)
# ---------------------------------------------------------------------------

def fixture_root(tmp_path, files=None):
    """Minimal tree ``check_conventions`` can walk: the paths REP002
    scans unconditionally, plus the seeded broken ``files``."""
    (tmp_path / 'tests').mkdir()
    (tmp_path / 'src/repro/kernels').mkdir(parents=True)
    (tmp_path / 'src/repro/core').mkdir(parents=True)
    (tmp_path / 'src/repro/core/protocol.py').write_text('')
    for rel, text in (files or {}).items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return tmp_path


class TestConventionMutations:
    def test_rep001_uncovered_spec_fires(self, tmp_path):
        # a tests tree with no pytest.raises+check_compat golden module:
        # every registered spec type is uncovered
        rep = check_conventions(fixture_root(tmp_path))
        bad = [f for f in rep.failures if f.rule == 'REP001']
        assert {f.subject for f in bad} \
            == {cls.__name__ for cls in api.PROTOCOLS}

    def test_rep002_np_random_in_round_math_fires(self, tmp_path):
        root = fixture_root(tmp_path, {
            'src/repro/core/protocol.py': '''
                import numpy as np
                noise = np.random.rand(3)
            ''',
            'src/repro/kernels/bad.py': '''
                import jax.numpy as jnp
                ACC = jnp.float64
            ''',
        })
        bad = [f for f in check_conventions(root).failures
               if f.rule == 'REP002']
        assert any('np.random' in f.detail for f in bad)
        assert any('float64' in f.detail for f in bad)

    def test_rep003_unfrozen_spec_fires(self, tmp_path):
        @dataclasses.dataclass          # NOT frozen (and can't subclass
        class MeltedSpec:               # the frozen ProtocolSpec base)
            fraction: float = 0.5

        pdef = dataclasses.replace(api.PROTOCOLS[api.SafaSpec],
                                   name='melted', spec_cls=MeltedSpec)
        api.register(pdef)
        try:
            rep = check_conventions(fixture_root(tmp_path))
            assert any(f.rule == 'REP003' and f.subject == 'MeltedSpec'
                       for f in rep.failures)
        finally:
            from repro.core import api as core_api
            del core_api.PROTOCOLS[MeltedSpec]
            del core_api._BY_NAME['melted']

    def test_rep004_silent_deprecation_fires(self, tmp_path):
        root = fixture_root(tmp_path, {
            'src/repro/shims.py': '''
                def run_old(x):
                    """Deprecated shim over run_new."""
                    return x
            ''',
        })
        bad = [f for f in check_conventions(root).failures
               if f.rule == 'REP004']
        assert any('run_old' in f.detail for f in bad)

    def test_rep004_protocol_lag_term_is_not_a_shim(self, tmp_path):
        # "deprecated" mid-docstring is SAFA's client lag state
        root = fixture_root(tmp_path, {
            'src/repro/lagmath.py': '''
                def classify(lag):
                    """Clients whose lag exceeds tau are deprecated."""
                    return lag
            ''',
        })
        assert not [f for f in check_conventions(root).failures
                    if f.rule == 'REP004']

    def test_rep005_uninventoried_kernel_fires(self, tmp_path):
        root = fixture_root(tmp_path, {
            'src/repro/kernels/rogue.py': '''
                from jax.experimental import pallas as pl

                def _rogue_kernel(x_ref, o_ref):
                    o_ref[...] = x_ref[...]

                def launch(x):
                    return pl.pallas_call(
                        _rogue_kernel,
                        input_output_aliases={0: 0},
                    )(x)
            ''',
        })
        bad = [f for f in check_conventions(root).failures
               if f.rule == 'REP005']
        assert any('ALIAS_CONTRACTS' in f.detail for f in bad)

    def test_rep005_undeclared_alias_form_fires(self, tmp_path):
        root = fixture_root(tmp_path, {
            'src/repro/kernels/sneaky.py': '''
                from jax.experimental import pallas as pl

                ALIAS_CONTRACTS = {'_sneaky_kernel': ((),)}

                def _sneaky_kernel(x_ref, o_ref):
                    o_ref[...] = x_ref[...]

                def launch(x):
                    return pl.pallas_call(
                        _sneaky_kernel,
                        input_output_aliases={0: 0},
                    )(x)
            ''',
        })
        bad = [f for f in check_conventions(root).failures
               if f.rule == 'REP005']
        assert any('not admitted' in f.detail for f in bad)

    def test_rep006_reused_built_env_fires(self, tmp_path):
        root = fixture_root(tmp_path, {
            'tests/test_reuse.py': '''
                from repro import api, fedsim

                def sweep_twice(runner, spec):
                    env = fedsim.EnvSpec(m=5).build()
                    a = runner.run_sweep(api.SweepSpec(
                        members=(api.SweepMember(env=env),)))
                    b = runner.run_sweep(api.SweepSpec(
                        members=(api.SweepMember(env=env),)))
                    return a, b
            ''',
        })
        bad = [f for f in check_conventions(root).failures
               if f.rule == 'REP006']
        assert any('single-shot' in f.detail for f in bad)


# ---------------------------------------------------------------------------
# JAX rules: wrong registrations and poisoned programs
# ---------------------------------------------------------------------------

def safa_cell(**exec_kw):
    pdef = api.PROTOCOLS[api.SafaSpec]
    kw = dict(engine='scan', schedule='dense', wire='f32',
              use_kernel=False, eval_every=jaxpr_checks.SEG)
    kw.update(exec_kw)
    return jaxpr_checks.Cell(pdef, api.SafaSpec(), api.ExecSpec(**kw))


def _stand_in_cell(monkeypatch, wrap):
    """``safa_cell()`` whose form names a stand-in engine on ``protocol``:
    ``wrap`` of the form's own engine, with its step and fleet twin."""
    cell = safa_cell()
    form = cell.pdef.form(cell.ex)
    name = 'stand_in_run_scan'
    monkeypatch.setattr(protocol, name, wrap(getattr(protocol, form.engine)),
                        raising=False)
    monkeypatch.setitem(protocol.ENGINES, name, protocol.ENGINES[form.engine])
    fake = dataclasses.replace(
        cell.pdef, form=lambda ex: form._replace(engine=name))
    return dataclasses.replace(cell, pdef=fake)


class TestJaxprMutations:
    def test_jax001_wrong_budget_fires(self):
        cell = safa_cell(wire='int8', use_kernel='packed')
        fake = dataclasses.replace(cell.pdef,
                                   dispatch_budget=lambda ex: 99)
        rep = jaxpr_checks.check_cells(
            cells=[dataclasses.replace(cell, pdef=fake)])
        bad = [f for f in rep.failures if f.rule == 'JAX001']
        assert bad and 'budget 99' in bad[0].detail

    def test_jax002_dropped_donation_fires(self):
        # donated input has no same-shape output: XLA drops the donation
        inner = jax.jit(lambda a: jnp.zeros((3, 7), jnp.float32),
                        donate_argnums=(0,))
        j = jax.make_jaxpr(lambda a: inner(a))(jnp.ones((5,), jnp.float32))
        ok, detail = jaxpr_checks._check_donations(j.jaxpr)
        assert not ok and 'donat' in detail

    def test_jax002_effective_donation_passes(self):
        inner = jax.jit(lambda a: a * 2.0, donate_argnums=(0,))
        j = jax.make_jaxpr(lambda a: inner(a))(jnp.ones((5,), jnp.float32))
        ok, _ = jaxpr_checks._check_donations(j.jaxpr)
        assert ok

    def test_jax003_phantom_claim_fires(self):
        cell = safa_cell()
        fake = dataclasses.replace(
            cell.pdef, alias_claims=lambda ex: {'_ghost_kernel': ((0, 1),)})
        rep = jaxpr_checks.check_cells(
            cells=[dataclasses.replace(cell, pdef=fake)])
        bad = [f for f in rep.failures if f.rule == 'JAX003']
        assert bad and '_ghost_kernel' in bad[0].detail

    def test_jax004_f64_promotion_fires(self):
        with jax.enable_x64(True):
            j = jax.make_jaxpr(lambda x: jnp.sin(x))(
                jnp.asarray(1.0, jnp.float64))
        f64, _ = jaxpr_checks._check_dtypes_and_callbacks(j.jaxpr)
        assert f64 is not None and 'f64' in f64

    def test_jax005_callback_in_scan_body_fires(self, monkeypatch):
        def noisy(engine):
            def run(*args, local_train_fn, **kw):
                def tf(*a, **k):
                    jax.debug.print('round')        # host sync per round
                    return local_train_fn(*a, **k)
                return engine(*args, local_train_fn=tf, **kw)
            return run

        rep = jaxpr_checks.check_cells(
            cells=[_stand_in_cell(monkeypatch, noisy)])
        assert 'JAX005' in failed_rules(rep)

    def test_jax006_baked_constant_fires(self, monkeypatch):
        counter = itertools.count()

        def drifting(engine):
            def run(*args, **kw):
                # a fresh python constant per trace: the two consecutive
                # segment traces bake different literals
                drift = float(next(counter))
                return jax.tree.map(lambda x: x + drift, engine(*args, **kw))
            return run

        rep = jaxpr_checks.check_cells(
            cells=[_stand_in_cell(monkeypatch, drifting)])
        assert 'JAX006' in failed_rules(rep)


# ---------------------------------------------------------------------------
# Env rng single-shot guard (the runtime half of REP006)
# ---------------------------------------------------------------------------

class TestEnvRngGuard:
    def test_draw_rounds_is_single_shot(self):
        env = fresh_env()
        env.draw_rounds(3)
        with pytest.raises(RuntimeError, match='already consumed'):
            env.draw_rounds(3)

    def test_fresh_env_draws_again(self):
        a = fresh_env().draw_rounds(3)
        b = fresh_env().draw_rounds(3)
        assert (a[0] == b[0]).all() and (a[1] == b[1]).all()

    def test_draw_round_stays_unrestricted(self):
        env = fresh_env()
        env.draw_round()
        env.draw_round()                # legitimate per-round stream use
