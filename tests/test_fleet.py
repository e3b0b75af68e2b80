"""Fleet engine regression tests: batched sweeps must be a pure perf
change — bit-identical per member to the single-run scan engine, with the
fleet-major schedule precompute bit-identical to per-member precomputes.

No hypothesis dependency — this module must run in a bare environment.
"""
import itertools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import federation, selection
from repro.data import make_regression, partition
from repro.data.tasks import regression_task
from repro.fedsim import FLEnv, env_grid
from repro.kernels import ops as kops

BASE = dict(m=5, crash_prob=0.3, dataset_size=506, batch_size=5,
            epochs=3, t_lim=830.0, seed=3)


def _members(s=8):
    """S heterogeneous fleet members sharing one client population."""
    envs = env_grid(BASE, crash_prob=(0.3, 0.7),
                    draw_seed=tuple(range((s + 1) // 2)))[:s]
    hyper = itertools.cycle(zip((0.5, 0.3, 1.0, 0.1), (5, 2, 10, 1)))
    return [federation.SweepMember(env=e, fraction=f, lag_tolerance=tau)
            for e, (f, tau) in zip(envs, hyper)]


@pytest.fixture(scope='module')
def reg_task():
    env = FLEnv(**BASE)
    x, y = make_regression()
    data = partition(x, y, env.partition_sizes, 5, seed=1)
    return regression_task(data, lr=1e-3, epochs=3)


def _assert_tree_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


class TestFleetEngine:
    def test_safa_fleet_bit_identical_to_sequential_scans(self, reg_task):
        """S=8 configs in one vmapped-scan dispatch == 8 sequential
        engine='scan' runs, bit for bit (acceptance criterion)."""
        hf = federation.run_sweep(reg_task, _members(8), rounds=12,
                                  eval_every=6, engine='fleet')
        hs = federation.run_sweep(reg_task, _members(8), rounds=12,
                                  eval_every=6, engine='sequential')
        assert len(hf) == len(hs) == 8
        for a, b in zip(hf, hs):
            _assert_tree_equal(a.final_global, b.final_global)
            assert a.evals() == b.evals()
            assert a.futility == b.futility

    def test_fleet_matches_run_safa(self, reg_task):
        """The fleet member result equals the standalone single-run API."""
        hf = federation.run_sweep(reg_task, _members(8), rounds=12,
                                  eval_every=6)
        for s in (0, 5):
            mem = _members(8)[s]
            h = federation.run_safa(reg_task, mem.env, fraction=mem.fraction,
                                    lag_tolerance=mem.lag_tolerance,
                                    rounds=12, eval_every=6, engine='scan')
            _assert_tree_equal(hf[s].final_global, h.final_global)
            assert hf[s].evals() == h.evals()

    def test_fedavg_fleet_bit_identical(self, reg_task):
        kw = dict(rounds=10, eval_every=5, proto='fedavg')
        hf = federation.run_sweep(reg_task, _members(4), engine='fleet', **kw)
        hs = federation.run_sweep(reg_task, _members(4),
                                  engine='sequential', **kw)
        for a, b in zip(hf, hs):
            _assert_tree_equal(a.final_global, b.final_global)
            assert a.evals() == b.evals()

    @pytest.mark.parametrize('proto', ['fedcs', 'local', 'fedasync'])
    def test_every_proto_fleet_bit_identical(self, reg_task, proto):
        """Acceptance criterion: run_sweep(engine='fleet') takes members of
        every protocol, bit-identical per member to sequential scans."""
        kw = dict(rounds=8, eval_every=4, proto=proto)
        hf = federation.run_sweep(reg_task, _members(4), engine='fleet', **kw)
        hs = federation.run_sweep(reg_task, _members(4),
                                  engine='sequential', **kw)
        for a, b in zip(hf, hs):
            _assert_tree_equal(a.final_global, b.final_global)
            assert a.evals() == b.evals()

    def test_local_fleet_matches_run_local(self, reg_task):
        """The fleet member result equals the standalone single-run API
        (including the vmapped eval-point aggregation)."""
        hf = federation.run_sweep(reg_task, _members(4), rounds=8,
                                  eval_every=4, proto='local')
        mem = _members(4)[2]
        h = federation.run_local(reg_task, mem.env, fraction=mem.fraction,
                                 rounds=8, eval_every=4, engine='scan')
        _assert_tree_equal(hf[2].final_global, h.final_global)
        assert hf[2].evals() == h.evals()

    def test_fedasync_fleet_matches_run_fedasync(self, reg_task):
        hf = federation.run_sweep(reg_task, _members(4), rounds=8,
                                  eval_every=4, proto='fedasync')
        mem = _members(4)[1]
        h = federation.run_fedasync(reg_task, mem.env, rounds=8,
                                    eval_every=4, engine='scan')
        _assert_tree_equal(hf[1].final_global, h.final_global)
        assert hf[1].evals() == h.evals()

    @pytest.mark.parametrize('proto', ['fedavg', 'fedcs', 'local',
                                       'fedasync'])
    def test_timing_only_sweep_matches_single_runs_every_proto(self, proto):
        hists = federation.run_sweep(None, _members(4), rounds=12,
                                     proto=proto, numeric=False)
        fn = federation.RUNNERS[proto]
        for mem, h in zip(_members(4), hists):
            single = fn(None, mem.env, fraction=mem.fraction, rounds=12,
                        numeric=False, seed=mem.seed)
            assert [r.round_len for r in h.records] == \
                [r.round_len for r in single.records]
            assert h.futility == single.futility

    def test_fleet_packed_kernel_matches_reference(self, reg_task):
        """use_kernel='packed' under the fleet vmap (batched-grid pallas
        dispatch) stays numerically on the reference trajectory."""
        hk = federation.run_sweep(reg_task, _members(4), rounds=6,
                                  eval_every=6, use_kernel='packed')
        hr = federation.run_sweep(reg_task, _members(4), rounds=6,
                                  eval_every=6)
        for a, b in zip(hk, hr):
            for la, lb in zip(jax.tree.leaves(a.final_global),
                              jax.tree.leaves(b.final_global)):
                np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                           atol=1e-5)

    def test_cnn_fleet_tracks_sequential(self):
        """dot_general-based tasks (CNN) are not covered by the bitwise
        contract (batch-size-dependent lowering), but the fleet engine
        must stay numerically on the sequential trajectory."""
        from repro.data import make_images
        from repro.data.tasks import cnn_task
        base = dict(m=4, crash_prob=0.3, dataset_size=64, batch_size=8,
                    epochs=1, t_lim=830.0, seed=3)
        envs = env_grid(base, draw_seed=(0, 1))
        x, y = make_images(n=64, seed=0)
        data = partition(x, y, envs[0].partition_sizes, 8, seed=0)
        task = cnn_task(data, lr=1e-3, epochs=1)
        members = lambda: [federation.SweepMember(env=e, fraction=0.5)
                           for e in env_grid(base, draw_seed=(0, 1))]
        hf = federation.run_sweep(task, members(), rounds=3, eval_every=3)
        hs = federation.run_sweep(task, members(), rounds=3, eval_every=3,
                                  engine='sequential')
        for a, b in zip(hf, hs):
            for la, lb in zip(jax.tree.leaves(a.final_global),
                              jax.tree.leaves(b.final_global)):
                np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                           rtol=1e-5, atol=1e-6)

    def test_timing_only_sweep_matches_single_runs(self):
        hists = federation.run_sweep(None, _members(4), rounds=15,
                                     numeric=False)
        for mem, h in zip(_members(4), hists):
            single = federation.run_safa(None, mem.env, fraction=mem.fraction,
                                         lag_tolerance=mem.lag_tolerance,
                                         rounds=15, numeric=False)
            assert [r.round_len for r in h.records] == \
                [r.round_len for r in single.records]
            assert h.futility == single.futility

    def test_sweep_validation(self, reg_task):
        with pytest.raises(ValueError, match='proto'):
            federation.run_sweep(reg_task, _members(2), rounds=2,
                                 proto='gossip')
        with pytest.raises(ValueError, match='engine'):
            federation.run_sweep(reg_task, _members(2), rounds=2,
                                 engine='warp')
        with pytest.raises(ValueError, match='empty'):
            federation.run_sweep(reg_task, [], rounds=2)
        bad = _members(2)
        bad[1] = federation.SweepMember(
            env=FLEnv(**{**BASE, 'm': 7, 'dataset_size': 700}))
        with pytest.raises(ValueError, match='client count'):
            federation.run_sweep(reg_task, bad, rounds=2)

    def test_sharded_fleet_bit_identical(self):
        """With the fleet axis sharded over 2 forced host devices the
        per-member bits must not change (subprocess: device count is fixed
        at jax import)."""
        code = (
            "import itertools, jax, numpy as np\n"
            "from repro.core import federation\n"
            "from repro.data import make_regression, partition\n"
            "from repro.data.tasks import regression_task\n"
            "from repro.fedsim import FLEnv, env_grid\n"
            f"BASE = dict({', '.join(f'{k}={v!r}' for k, v in BASE.items())})\n"
            "assert len(jax.devices()) == 2, jax.devices()\n"
            "env = FLEnv(**BASE)\n"
            "x, y = make_regression()\n"
            "data = partition(x, y, env.partition_sizes, 5, seed=1)\n"
            "task = regression_task(data, lr=1e-3, epochs=3)\n"
            "def members():\n"
            "    envs = env_grid(BASE, crash_prob=(0.3, 0.7),\n"
            "                    draw_seed=(0, 1))\n"
            "    return [federation.SweepMember(env=e, fraction=f,\n"
            "                                   lag_tolerance=t)\n"
            "            for e, f, t in zip(envs, (0.5, 0.3, 1.0, 0.1),\n"
            "                               (5, 2, 10, 1))]\n"
            "hf = federation.run_sweep(task, members(), rounds=6,\n"
            "                          eval_every=6, engine='fleet')\n"
            "hs = federation.run_sweep(task, members(), rounds=6,\n"
            "                          eval_every=6, engine='sequential')\n"
            "for a, b in zip(hf, hs):\n"
            "    for la, lb in zip(jax.tree.leaves(a.final_global),\n"
            "                      jax.tree.leaves(b.final_global)):\n"
            "        np.testing.assert_array_equal(np.asarray(la),\n"
            "                                      np.asarray(lb))\n"
            "print('SHARDED_OK')\n")
        env = dict(os.environ)
        env['XLA_FLAGS'] = (env.get('XLA_FLAGS', '')
                            + ' --xla_force_host_platform_device_count=2')
        env['JAX_PLATFORMS'] = 'cpu'
        out = subprocess.run([sys.executable, '-c', code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        assert 'SHARDED_OK' in out.stdout


class TestFleetSchedule:
    def test_fleet_precompute_bit_identical_to_singles(self):
        """The vectorised [S, m] host pass == S independent
        precompute_safa_schedule calls: masks, records and futility."""
        fleet = federation.precompute_fleet_schedule(_members(8), rounds=20)
        singles = [federation.precompute_safa_schedule(
            mem.env, fraction=mem.fraction, lag_tolerance=mem.lag_tolerance,
            rounds=20) for mem in _members(8)]
        stacked = federation.FleetSchedule.stack(singles)
        for k in federation.FleetSchedule.MASKS:
            np.testing.assert_array_equal(getattr(fleet, k),
                                          getattr(stacked, k))
        np.testing.assert_array_equal(fleet.futility, stacked.futility)
        assert fleet.records == stacked.records

    def test_fleet_precompute_large_m(self):
        """Same identity on a paper-scale population (m=100), where masked
        and compressed reductions could diverge if formulated sloppily."""
        base = dict(m=100, crash_prob=0.5, dataset_size=70000, batch_size=40,
                    epochs=5, t_lim=5600.0, seed=2)
        members = [federation.SweepMember(env=e, fraction=f, lag_tolerance=t)
                   for e, f, t in zip(env_grid(base, draw_seed=(0, 1, 2)),
                                      (0.3, 0.7, 0.5), (5, 1, 10))]
        rebuild = [federation.SweepMember(env=e, fraction=f, lag_tolerance=t)
                   for e, f, t in zip(env_grid(base, draw_seed=(0, 1, 2)),
                                      (0.3, 0.7, 0.5), (5, 1, 10))]
        fleet = federation.precompute_fleet_schedule(members, rounds=12)
        singles = [federation.precompute_safa_schedule(
            mem.env, fraction=mem.fraction, lag_tolerance=mem.lag_tolerance,
            rounds=12) for mem in rebuild]
        for s, single in enumerate(singles):
            got = fleet.member(s)
            for k in federation.FleetSchedule.MASKS:
                np.testing.assert_array_equal(getattr(got, k),
                                              getattr(single, k))
            assert got.records == single.records
            assert got.futility == single.futility

    @pytest.mark.parametrize('fedcs', [False, True])
    def test_sync_fleet_precompute_bit_identical_to_singles(self, fedcs):
        """The [S, rounds, m] sync host pass (no per-member Python loop)
        == S independent precompute_sync_schedule calls: masks, records
        and futility — for both the FedCS rank-comparison selection and
        the rng-stream FedAvg selection."""
        members = _members(8)
        for s, mem in enumerate(members):   # vary the selection seeds too
            mem.seed = s % 3
        fleet = federation.precompute_sync_fleet_schedule(members, rounds=20,
                                                          fedcs=fedcs)
        singles = []
        rebuild = _members(8)
        for s, mem in enumerate(rebuild):
            mem.seed = s % 3
            singles.append(federation.precompute_sync_schedule(
                mem.env, fraction=mem.fraction, rounds=20, seed=mem.seed,
                fedcs=fedcs))
        stacked = federation.SyncFleetSchedule.stack(singles)
        for k in federation.SyncFleetSchedule.MASKS:
            np.testing.assert_array_equal(getattr(fleet, k),
                                          getattr(stacked, k))
        np.testing.assert_array_equal(fleet.futility, stacked.futility)
        assert fleet.records == stacked.records

    def test_sync_fleet_precompute_large_m(self):
        """Same identity at paper scale (m=100) where the deadline culls
        slow clients, covering the too-slow-reckoned-crashed branch."""
        base = dict(m=100, crash_prob=0.5, dataset_size=70000, batch_size=40,
                    epochs=5, t_lim=5600.0, seed=2)
        def members():
            return [federation.SweepMember(env=e, fraction=f, seed=sd)
                    for e, f, sd in zip(env_grid(base, draw_seed=(0, 1, 2)),
                                        (0.3, 0.7, 1.0), (0, 1, 2))]
        for fedcs in (False, True):
            fleet = federation.precompute_sync_fleet_schedule(
                members(), rounds=12, fedcs=fedcs)
            singles = [federation.precompute_sync_schedule(
                mem.env, fraction=mem.fraction, rounds=12, seed=mem.seed,
                fedcs=fedcs) for mem in members()]
            for s, single in enumerate(singles):
                got = fleet.member(s)
                for k in federation.SyncFleetSchedule.MASKS:
                    np.testing.assert_array_equal(getattr(got, k),
                                                  getattr(single, k))
                assert got.records == single.records
                assert got.futility == single.futility

    def test_shapes_and_round_idx(self):
        fleet = federation.precompute_fleet_schedule(_members(4), rounds=7)
        assert fleet.size == 4 and fleet.rounds == 7
        dev = fleet.to_device()
        for mask in (dev.sync, dev.completed, dev.picked, dev.undrafted,
                     dev.deprecated):
            assert mask.shape == (4, 7, 5)
        assert dev.round_idx.shape == (4, 7)
        np.testing.assert_array_equal(np.asarray(dev.round_idx[2]),
                                      np.arange(1, 8))

    def test_rng_streams_independent_per_member(self):
        """Each member consumes only its own env rng: permuting the other
        members does not change a member's schedule."""
        a = federation.precompute_fleet_schedule(_members(4), rounds=10)
        perm = list(reversed(_members(4)))
        b = federation.precompute_fleet_schedule(perm, rounds=10)
        for s in range(4):
            np.testing.assert_array_equal(a.picked[s], b.picked[3 - s])

    def test_stack_rejects_mismatched_shapes(self):
        s1 = federation.precompute_safa_schedule(FLEnv(**BASE), fraction=0.5,
                                                 lag_tolerance=5, rounds=5)
        s2 = federation.precompute_safa_schedule(FLEnv(**BASE), fraction=0.5,
                                                 lag_tolerance=5, rounds=6)
        with pytest.raises(ValueError, match='rounds'):
            federation.FleetSchedule.stack([s1, s2])


class TestCfcfmInvariants:
    def _draw(self, rng, m):
        arrival = rng.exponential(100.0, m) + 10.0
        completed = rng.random(m) < 0.7
        arrival = np.where(completed, arrival, np.inf)
        picked_prev = rng.random(m) < 0.4
        fraction = rng.choice([0.1, 0.3, 0.5, 0.9, 1.0])
        deadline = rng.choice([120.0, 200.0, 1e9])
        return arrival, completed, picked_prev, fraction, deadline

    def test_invariants_randomized(self):
        rng = np.random.default_rng(0)
        for m in (1, 3, 5, 17, 64):
            for _ in range(40):
                arrival, completed, prev, frac, deadline = self._draw(rng, m)
                sel = selection.cfcfm(arrival, completed, prev, frac,
                                      deadline)
                quota = max(1, int(round(frac * m)))
                committed = completed & (arrival <= deadline)
                # picked is a subset of committed arrivals
                assert not np.any(sel.picked & ~sel.committed)
                np.testing.assert_array_equal(sel.committed, committed)
                # quota respected, and met whenever enough clients arrived
                assert sel.picked.sum() == min(quota, committed.sum())
                # undrafted = committed leftovers
                np.testing.assert_array_equal(sel.undrafted,
                                              committed & ~sel.picked)
                # compensatory priority: a previously-picked client may only
                # be picked once every not-previously-picked arrival is
                assert not (np.any(sel.picked & prev)
                            and np.any(committed & ~prev & ~sel.picked))
                assert sel.quota_met_time <= deadline

    def test_batch_matches_scalar(self):
        """cfcfm_batch rows == independent cfcfm calls (the fleet schedule
        precompute is built on this)."""
        rng = np.random.default_rng(1)
        for m in (2, 5, 33):
            rows = [self._draw(rng, m) for _ in range(16)]
            batch = selection.cfcfm_batch(
                np.stack([r[0] for r in rows]),
                np.stack([r[1] for r in rows]),
                np.stack([r[2] for r in rows]),
                np.array([r[3] for r in rows]),
                np.array([r[4] for r in rows]))
            for s, (arrival, completed, prev, frac, deadline) in \
                    enumerate(rows):
                ref = selection.cfcfm(arrival, completed, prev, frac,
                                      deadline)
                np.testing.assert_array_equal(batch.picked[s], ref.picked)
                np.testing.assert_array_equal(batch.undrafted[s],
                                              ref.undrafted)
                np.testing.assert_array_equal(batch.committed[s],
                                              ref.committed)
                assert batch.quota_met_time[s] == ref.quota_met_time


class TestFleetKernel:
    SHAPES = ((4, 3), (64,), (8, 33))

    def _operands(self, s=3, m=6):
        def tr(key, lead):
            ks = jax.random.split(key, len(self.SHAPES))
            return {f'p{i}': jax.random.normal(k, lead + shp)
                    for i, (k, shp) in enumerate(zip(ks, self.SHAPES))}
        rng = np.random.default_rng(0)
        picked = jnp.asarray(rng.random((s, m)) < 0.4)
        masks = dict(
            picked=picked,
            undrafted=jnp.asarray(rng.random((s, m)) < 0.3) & ~picked,
            deprecated=jnp.asarray(rng.random((s, m)) < 0.3),
            weights=jnp.asarray(rng.dirichlet(np.ones(m), size=s),
                                jnp.float32))
        return (tr(jax.random.PRNGKey(0), (s, m)),
                tr(jax.random.PRNGKey(1), (s, m)),
                tr(jax.random.PRNGKey(2), (s,)), masks)

    @staticmethod
    def _fleet(cache, trained, g, masks):
        """The fleet form of the packed aggregation: one vmapped launch."""
        return jax.vmap(lambda c, t, gg, mm: kops.safa_aggregate_tree_packed(
            c, t, gg, **mm))(cache, trained, g, masks)

    def test_fleet_grid_matches_per_member_packed(self):
        cache, trained, g, masks = self._operands()
        out = self._fleet(cache, trained, g, masks)
        for s in range(3):
            ref = kops.safa_aggregate_tree_packed(
                jax.tree.map(lambda a, i=s: a[i], cache),
                jax.tree.map(lambda a, i=s: a[i], trained),
                jax.tree.map(lambda a, i=s: a[i], g),
                **{k: v[s] for k, v in masks.items()})
            for k in cache:
                np.testing.assert_allclose(
                    np.asarray(out.new_global[k][s]),
                    np.asarray(ref.new_global[k]), atol=1e-6)
                np.testing.assert_allclose(
                    np.asarray(out.new_cache[k][s]),
                    np.asarray(ref.new_cache[k]), atol=1e-6)

    def test_fleet_grid_single_dispatch(self):
        cache, trained, g, masks = self._operands()
        jaxpr = jax.make_jaxpr(
            lambda c, t, gg: self._fleet(c, t, gg, masks))(cache, trained, g)
        assert kops.count_pallas_calls(jaxpr.jaxpr) == 1

    def test_fleet_pack_roundtrip(self):
        cache, _, g, _ = self._operands()
        spec = kops.pack_spec(jax.tree.map(lambda a: a[0], g))
        back = kops.unpack_fleet(kops.pack_fleet(cache, spec), spec)
        for k in cache:
            np.testing.assert_array_equal(np.asarray(back[k]),
                                          np.asarray(cache[k]))

    def test_fleet_packed_rejects_non_f32(self):
        cache, trained, g, masks = self._operands()
        to16 = lambda t: jax.tree.map(lambda a: a.astype(jnp.bfloat16), t)
        with pytest.raises(TypeError, match='float32'):
            self._fleet(to16(cache), to16(trained), to16(g), masks)


class TestEnvGrid:
    def test_grid_order_and_size(self):
        envs = env_grid(BASE, crash_prob=(0.1, 0.9), draw_seed=(0, 1, 2))
        assert len(envs) == 6
        # row-major: last axis fastest
        assert [e.crash_prob for e in envs] == [0.1] * 3 + [0.9] * 3
        assert [e.draw_seed for e in envs] == [0, 1, 2] * 2

    def test_draw_seed_shares_population(self):
        a, b = env_grid(BASE, draw_seed=(0, 1))
        np.testing.assert_array_equal(a.partition_sizes, b.partition_sizes)
        np.testing.assert_array_equal(a.perf, b.perf)
        ca, _ = a.draw_round()
        cb, _ = b.draw_round()
        assert not np.array_equal(ca, cb)  # independent crash streams

    def test_default_draw_stream_unchanged(self):
        """draw_seed=None keeps the seed's single-stream behaviour."""
        e1 = FLEnv(**BASE)
        e2 = FLEnv(**BASE, draw_seed=None)
        for _ in range(3):
            c1, f1 = e1.draw_round()
            c2, f2 = e2.draw_round()
            np.testing.assert_array_equal(c1, c2)
            np.testing.assert_array_equal(f1, f2)
