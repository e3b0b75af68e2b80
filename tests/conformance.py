"""Registry-wide protocol conformance harness (library, no tests).

``tests/test_conformance.py`` drives every spec in ``api.PROTOCOLS``
through the invariant matrix the engines promise — scan == loop, fleet ==
sequential == single run, sparse == dense, the int8 wire's engine parity
(plus the per-leaf ``quantize_uploads`` reference where the spec has
one), checkpoint/resume bit-identity, and the ``History`` dict
round-trip.  The case list is **auto-discovered** from the registry: a
protocol registered via ``api.register`` is conformance-tested with zero
test edits, and a failure names the offending spec in the test id.

Everything here is deliberately tiny (m=5 regression task, 6 rounds) so
the whole matrix stays tier-1 fast; the point is engine *identity*, not
learning quality.

Environments are consumed: every precompute advances its ``FLEnv`` rng,
so each run (and each sweep member) gets a ``fresh_env`` — two runs that
must replay the same event stream get two envs built with the same seed.
"""
import dataclasses

import jax
import numpy as np

from repro import api
from repro.data import make_regression, partition
from repro.data.tasks import regression_task
from repro.fedsim import FLEnv

ROUNDS = 6
EVAL_EVERY = 3
M = 5
ENV_SEED = 3
BASE_ENV = dict(m=M, crash_prob=0.3, dataset_size=506, batch_size=5,
                epochs=3, t_lim=830.0)

#: named non-default field variants ridden through the same matrix; keys
#: must not collide with registry names.
VARIANTS = {
    'fedasync-constant': lambda: api.FedAsyncSpec(staleness_fn='constant'),
    'fedasync-hinge': lambda: api.FedAsyncSpec(staleness_fn='hinge',
                                               hinge_b=1),
    'seafl-hinge': lambda: api.SeaflSpec(staleness_fn='hinge', hinge_b=1),
    'seafl-loss': lambda: api.SeaflSpec(use_loss=True),
    'csafl-3': lambda: api.CsaflSpec(clusters=3),
}


def fresh_env(seed: int = ENV_SEED) -> FLEnv:
    return FLEnv(seed=seed, **BASE_ENV)


_TASK = None


def shared_task():
    """One tiny regression task shared by every case (module-cached so
    its jitted train steps compile once per test session)."""
    global _TASK
    if _TASK is None:
        env = fresh_env()
        x, y = make_regression()
        data = partition(x, y, env.partition_sizes, M, seed=1)
        _TASK = regression_task(data, lr=1e-3, epochs=3)
    return _TASK


def cases() -> dict:
    """case id -> spec factory.  One default-spec case per registered
    protocol (auto-discovery) plus the named ``VARIANTS``."""
    out = {p.name: p.spec_cls for p in api.PROTOCOLS.values()}
    overlap = set(out) & set(VARIANTS)
    assert not overlap, f'variant ids shadow registry names: {overlap}'
    out.update(VARIANTS)
    return out


def pdef_of(spec) -> api.ProtocolDef:
    return api.PROTOCOLS[type(spec)]


def member_for(spec, env, seed: int = 0) -> api.SweepMember:
    """A SweepMember replaying exactly ``spec`` on ``env``: the member
    hyper columns mirror the spec's, and — for the staleness-adaptive
    family — the remaining spec fields ride in ``overrides`` so the fleet
    precompute reproduces the single-run schedule bit-for-bit."""
    kw = dict(seed=seed)
    for f in ('fraction', 'lag_tolerance', 'alpha', 'staleness_exp'):
        if hasattr(spec, f):
            kw[f] = getattr(spec, f)
    if hasattr(spec, 'staleness_fn'):
        kw['overrides'] = {
            f.name: getattr(spec, f.name)
            for f in dataclasses.fields(spec)
            if f.name not in ('fraction', 'lag_tolerance', 'alpha',
                              'staleness_exp')}
    return api.SweepMember(env=env, **kw)


def run_single(spec, *, engine=None, exec_kw=None, env_seed: int = ENV_SEED,
               seed: int = 0, checkpoint=None, max_segments=None):
    ex = api.ExecSpec(engine=engine, eval_every=EVAL_EVERY,
                      **(exec_kw or {}))
    exp = api.Experiment(shared_task(), fresh_env(env_seed), spec, ex,
                         rounds=ROUNDS, seed=seed)
    return exp.compile().run(checkpoint=checkpoint,
                             max_segments=max_segments)


def run_sweep(spec, members, *, engine='fleet', exec_kw=None,
              max_segments=None):
    ex = api.ExecSpec(engine=engine, eval_every=EVAL_EVERY,
                      **(exec_kw or {}))
    exp = api.Experiment(shared_task(), fresh_env(), spec, ex,
                         rounds=ROUNDS)
    return exp.compile().run_sweep(members, max_segments=max_segments)


def assert_tree_equal(a, b, context: str = ''):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), f'{context}: tree structures differ'
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f'{context}: leaf {i} differs')


#: The tolerance of scan == loop where a round aggregates weighted sums:
#: the loop engine dispatches each round op by op, while the scan
#: compiles the round into one fused program.  Both compute f32 weighted
#: sums, which the fused program reassociates (measured on the CPU over
#: 6-12 rounds of the regression task: at most 9.5e-7 absolute, 2.1e-6
#: relative).  Where the order of arithmetic is the repo's own —
#: checkpoint/resume, fleet == sequential — the contract stays bitwise.
ENGINE_TOL = dict(rtol=1e-5, atol=1e-6)


def assert_tree_close(a, b, context: str = ''):
    """Leaf-for-leaf agreement within ``ENGINE_TOL``."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), f'{context}: tree structures differ'
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), **ENGINE_TOL,
            err_msg=f'{context}: leaf {i} differs')


def assert_evals_close(ha, hb, context: str = ''):
    """The same eval rounds, each metric within ``ENGINE_TOL``."""
    ea, eb = ha.evals(), hb.evals()
    assert [t for t, _ in ea] == [t for t, _ in eb], \
        f'{context}: eval rounds differ'
    for (t, a), (_, b) in zip(ea, eb):
        assert a.keys() == b.keys(), f'{context}: round {t} metrics differ'
        for k in a:
            np.testing.assert_allclose(
                a[k], b[k], **ENGINE_TOL,
                err_msg=f'{context}: round {t} {k} differs')


def assert_history_equal(ha, hb, context: str = ''):
    """Full-run identity: final model bit-equality, identical eval
    trajectories, and identical host event records."""
    assert_tree_equal(ha.final_global, hb.final_global,
                      f'{context}: final_global')
    assert ha.evals() == hb.evals(), f'{context}: eval trajectories differ'
    ra = [dataclasses.asdict(r) for r in ha.records]
    rb = [dataclasses.asdict(r) for r in hb.records]
    assert ra == rb, f'{context}: round records differ'
