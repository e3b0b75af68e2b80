"""Scan-compiled round engine + packed aggregation: regression tests.

No hypothesis dependency — this module must run in a bare environment.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conformance as C
from repro.core import federation, protocol, selection
from repro.data import make_regression, partition
from repro.data.tasks import regression_task
from repro.fedsim import FLEnv
from repro.kernels import ops as kops


def _env(**kw):
    base = dict(m=5, crash_prob=0.3, dataset_size=506, batch_size=5,
                epochs=3, t_lim=830.0, seed=3)
    base.update(kw)
    return FLEnv(**base)


@pytest.fixture(scope='module')
def reg_task():
    env = _env()
    x, y = make_regression()
    data = partition(x, y, env.partition_sizes, 5, seed=1)
    return regression_task(data, lr=1e-3, epochs=3)


def _tree(key, m, shapes):
    ks = jax.random.split(key, len(shapes))
    return {f'p{i}': jax.random.normal(k, (m,) + s)
            for i, (k, s) in enumerate(zip(ks, shapes))}


def _global(key, shapes):
    ks = jax.random.split(key, len(shapes))
    return {f'p{i}': jax.random.normal(k, s)
            for i, (k, s) in enumerate(zip(ks, shapes))}


class TestScanEngine:
    def test_safa_scan_matches_loop(self, reg_task):
        """The compiled engine is a pure perf change: same seed => the
        per-round loop engine's model, within ``C.ENGINE_TOL`` (the scan
        fuses the round's weighted sums, the loop runs them op by op)."""
        hists = {}
        for engine in ('loop', 'scan'):
            h = federation.run_safa(reg_task, _env(), fraction=0.5,
                                    lag_tolerance=5, rounds=12, eval_every=6,
                                    engine=engine)
            hists[engine] = h
        C.assert_tree_close(hists['loop'].final_global,
                            hists['scan'].final_global, 'safa')
        # evals run at the same rounds and agree
        C.assert_evals_close(hists['loop'], hists['scan'], 'safa')
        assert hists['loop'].futility == hists['scan'].futility

    def test_fedavg_scan_matches_loop(self, reg_task):
        hists = {}
        for engine in ('loop', 'scan'):
            hists[engine] = federation.run_fedavg(
                reg_task, _env(), fraction=0.5, rounds=10, eval_every=5,
                engine=engine)
        C.assert_tree_close(hists['loop'].final_global,
                            hists['scan'].final_global, 'fedavg')

    def test_fedcs_scan_matches_loop(self, reg_task):
        hists = {}
        for engine in ('loop', 'scan'):
            hists[engine] = federation.run_fedcs(
                reg_task, _env(), fraction=0.5, rounds=10, eval_every=5,
                engine=engine)
        C.assert_tree_close(hists['loop'].final_global,
                            hists['scan'].final_global, 'fedcs')

    def test_local_scan_bit_identical_to_loop(self, reg_task):
        """run_local rides the same scan engine contract: one donated-carry
        dispatch per eval segment, bit-identical to the per-round loop."""
        hists = {}
        for engine in ('loop', 'scan'):
            hists[engine] = federation.run_local(
                reg_task, _env(), fraction=0.5, rounds=12, eval_every=6,
                engine=engine)
        for a, b in zip(jax.tree.leaves(hists['loop'].final_global),
                        jax.tree.leaves(hists['scan'].final_global)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert hists['loop'].evals() == hists['scan'].evals()

    def test_fedasync_scan_bit_identical_to_loop(self, reg_task):
        """The arrival-ordered sequential merges compile into an inner
        lax.scan over the precomputed merge-order/alpha schedule without
        changing a bit vs the per-round loop."""
        hists = {}
        for engine in ('loop', 'scan'):
            hists[engine] = federation.run_fedasync(
                reg_task, _env(), rounds=12, eval_every=6, engine=engine)
        for a, b in zip(jax.tree.leaves(hists['loop'].final_global),
                        jax.tree.leaves(hists['scan'].final_global)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert hists['loop'].evals() == hists['scan'].evals()

    def test_every_runner_accepts_scan_engine(self, reg_task):
        """Acceptance criterion: every RUNNERS entry takes engine='scan'
        (and 'loop'), returning evals at the same rounds."""
        assert set(federation.RUNNERS) == {'safa', 'fedavg', 'fedcs',
                                           'local', 'fedasync'}
        for name, fn in federation.RUNNERS.items():
            kw = dict(fraction=0.5, rounds=4, eval_every=2, engine='scan')
            if name == 'safa':
                kw['lag_tolerance'] = 5
            h = fn(reg_task, _env(), **kw)
            assert [r for r, _ in h.evals()] == [2, 4], name

    def test_unknown_engine_rejected(self, reg_task):
        with pytest.raises(ValueError, match='engine'):
            federation.run_safa(reg_task, _env(), fraction=0.5,
                                lag_tolerance=5, rounds=2, engine='warp')
        with pytest.raises(ValueError, match='engine'):
            federation.run_local(reg_task, _env(), fraction=0.5, rounds=2,
                                 engine='warp')
        with pytest.raises(ValueError, match='engine'):
            federation.run_fedasync(reg_task, _env(), rounds=2,
                                    engine='warp')

    def test_schedule_independent_of_numeric_mode(self):
        """Timing metrics come from the precomputed schedule alone."""
        h_timing = federation.run_safa(None, _env(), fraction=0.5,
                                       lag_tolerance=5, rounds=15,
                                       numeric=False)
        sched = federation.precompute_safa_schedule(
            _env(), fraction=0.5, lag_tolerance=5, rounds=15)
        assert [r.round_len for r in h_timing.records] == \
            [r.round_len for r in sched.records]
        assert h_timing.futility == sched.futility

    def test_draw_rounds_matches_sequential_stream(self):
        e1, e2 = _env(seed=7), _env(seed=7)
        c_all, f_all = e1.draw_rounds(4)
        for t in range(4):
            c, f = e2.draw_round()
            np.testing.assert_array_equal(c_all[t], c)
            np.testing.assert_array_equal(f_all[t], f)


class TestBatchSelectors:
    def test_fedcs_select_batch_row_identity(self):
        """The rank-comparison form == the scalar greedy loop, row for
        row, over random estimate/fraction/deadline grids."""
        rng = np.random.default_rng(0)
        for m in (1, 2, 5, 33, 100):
            est = rng.exponential(100.0, (16, m)) + 5.0
            # inject duplicate estimates so stable tie-breaks are exercised
            est[:, : m // 2] = np.round(est[:, : m // 2], -1)
            fraction = rng.choice([0.1, 0.3, 0.5, 0.9, 1.0], 16)
            deadline = rng.choice([50.0, 120.0, 400.0, 1e9], 16)
            batch = selection.fedcs_select_batch(est, fraction, deadline)
            for s in range(16):
                ref = selection.fedcs_select(est[s], fraction[s], deadline[s])
                np.testing.assert_array_equal(batch[s], ref, err_msg=f'{m}/{s}')

    def test_fedcs_select_batch_degenerate_no_fit(self):
        """No client fits the deadline -> the single fastest is admitted,
        in every row (including rows where some clients do fit)."""
        est = np.array([[90.0, 50.0, 70.0],     # nothing fits deadline=10
                        [90.0, 5.0, 70.0],      # one fits
                        [50.0, 50.0, 50.0]])    # tie: stable pick of idx 0
        deadline = np.array([10.0, 10.0, 10.0])
        batch = selection.fedcs_select_batch(est, 0.7, deadline)
        for s in range(3):
            ref = selection.fedcs_select(est[s], 0.7, deadline[s])
            np.testing.assert_array_equal(batch[s], ref)
        np.testing.assert_array_equal(batch[0], [False, True, False])
        np.testing.assert_array_equal(batch[2], [True, False, False])

    def test_fedavg_select_batch_row_identity(self):
        """Batched selections == sequential scalar calls consuming
        identically-seeded generators — the sync fleet precompute's rng
        contract."""
        m, rounds = 7, 5
        fractions = np.array([0.1, 0.5, 1.0, 0.43])
        batch = selection.fedavg_select_batch(
            [np.random.default_rng(100 + s) for s in range(4)], m,
            fractions, rounds)
        assert batch.shape == (4, rounds, m)
        for s in range(4):
            rng = np.random.default_rng(100 + s)
            for t in range(rounds):
                ref = selection.fedavg_select(rng, m, fractions[s])
                np.testing.assert_array_equal(batch[s, t], ref)


class TestPackedAggregation:
    SHAPES = ((4, 3), (64,), (8, 33), (2, 5, 7))

    def _operands(self, m=6):
        cache = _tree(jax.random.PRNGKey(0), m, self.SHAPES)
        trained = _tree(jax.random.PRNGKey(1), m, self.SHAPES)
        g = _global(jax.random.PRNGKey(2), self.SHAPES)
        masks = dict(picked=jnp.array([1, 0, 0, 1, 0, 0], bool),
                     undrafted=jnp.array([0, 1, 0, 0, 1, 0], bool),
                     deprecated=jnp.array([0, 0, 1, 1, 0, 0], bool),
                     weights=jnp.asarray(
                         np.random.default_rng(0).dirichlet(np.ones(m)),
                         jnp.float32))
        return cache, trained, g, masks

    def test_packed_equals_leafwise_equals_reference(self):
        """packed kernel == leaf-wise kernel == 3-step Eq. 6-8 reference."""
        cache, trained, g, masks = self._operands()
        ref = protocol.discriminative_aggregation(
            cache, trained, g, use_kernel=False, **masks)
        leaf = protocol.discriminative_aggregation(
            cache, trained, g, use_kernel=True, **masks)
        packed = protocol.discriminative_aggregation(
            cache, trained, g, use_kernel='packed', **masks)
        for k in cache:
            for other in (leaf, packed):
                np.testing.assert_allclose(np.asarray(other.new_global[k]),
                                           np.asarray(ref.new_global[k]),
                                           atol=1e-5)
                np.testing.assert_allclose(np.asarray(other.new_cache[k]),
                                           np.asarray(ref.new_cache[k]),
                                           atol=1e-6)

    def test_packed_single_dispatch(self):
        """Exactly one pallas_call regardless of leaf count."""
        cache, trained, g, masks = self._operands()
        count = kops.count_pallas_calls

        def agg(mode, c, t, gg):
            return protocol.discriminative_aggregation(
                c, t, gg, use_kernel=mode, **masks)

        n_packed = count(jax.make_jaxpr(
            lambda c, t, gg: agg('packed', c, t, gg))(cache, trained, g).jaxpr)
        n_leaf = count(jax.make_jaxpr(
            lambda c, t, gg: agg(True, c, t, gg))(cache, trained, g).jaxpr)
        assert n_packed == 1
        assert n_leaf == len(self.SHAPES)

    def test_unknown_use_kernel_rejected(self):
        cache, trained, g, masks = self._operands()
        with pytest.raises(ValueError, match='use_kernel'):
            protocol.discriminative_aggregation(
                cache, trained, g, use_kernel='Packed', **masks)

    def test_counter_descends_into_cond_branches(self):
        """count_pallas_calls must see dispatches inside lax.cond branches
        (tuple-of-ClosedJaxpr params)."""
        from repro.kernels.comm_quant import QBLOCK, quantize
        n = 2048

        def f(x):
            return jax.lax.cond(
                x[0] > 0, lambda v: quantize(v),
                lambda v: (jnp.zeros(n, jnp.int8),
                           jnp.ones(n // QBLOCK, jnp.float32)), x)

        jaxpr = jax.make_jaxpr(f)(jnp.ones(n))
        assert kops.count_pallas_calls(jaxpr.jaxpr) == 1

    def test_packed_rejects_non_f32(self):
        """The pack buffer computes in f32 — other dtypes must fail loud,
        not silently diverge from the leaf-wise path."""
        cache, trained, g, masks = self._operands()
        g16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), g)
        c16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), cache)
        t16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), trained)
        with pytest.raises(TypeError, match='float32'):
            protocol.discriminative_aggregation(
                c16, t16, g16, use_kernel='packed', **masks)

    def test_pack_unpack_roundtrip(self):
        m = 4
        tree = _tree(jax.random.PRNGKey(3), m, self.SHAPES)
        g = _global(jax.random.PRNGKey(4), self.SHAPES)
        spec = kops.pack_spec(g)
        assert spec.n_padded % 2048 == 0
        back = kops.unpack_stacked(kops.pack_stacked(tree, spec), spec)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(back[k]),
                                          np.asarray(tree[k]))
        gback = kops.unpack_global(kops.pack_global(g, spec), spec)
        for k in g:
            np.testing.assert_array_equal(np.asarray(gback[k]),
                                          np.asarray(g[k]))


class TestQuantizeTree:
    def test_roundtrip_nested_multileaf(self):
        """dequantize(quantize(tree)) on a nested pytree with dict/list/
        tuple structure — the layout the old is_leaf-based flattening
        mishandled (a structural tuple was mistaken for a (q, scales)
        pair)."""
        key = jax.random.PRNGKey(9)
        ks = jax.random.split(key, 4)
        tree = {
            'layers': [
                {'w': jax.random.normal(ks[0], (16, 8)),
                 'b': jax.random.normal(ks[1], (8,))},
                (jax.random.normal(ks[2], (5, 3, 2)),
                 jax.random.normal(ks[3], (7,))),
            ],
        }
        out = kops.dequantize_tree(kops.quantize_tree(tree), tree)
        assert jax.tree_util.tree_structure(out) == \
            jax.tree_util.tree_structure(tree)
        for orig, deq in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            assert deq.shape == orig.shape and deq.dtype == orig.dtype
            # int8 symmetric per-block: error bounded by half a quant step
            tol = float(jnp.max(jnp.abs(orig))) / 127.0
            np.testing.assert_allclose(np.asarray(deq), np.asarray(orig),
                                       atol=tol)


class TestFedAsyncGuard:
    def test_all_crash_round_len_finite(self):
        env = _env(m=4, crash_prob=1.0, dataset_size=100, epochs=1,
                   t_lim=100.0, seed=0)
        h = federation.run_fedasync(None, env, rounds=6, numeric=False)
        lens = [r.round_len for r in h.records]
        assert all(np.isfinite(lens))
        assert all(l == env.t_lim for l in lens)
