"""Sparse active-set schedules: the sparse==dense contracts.

Four layers under test:

* event streams — ``form='sparse'`` precomputes equal the dense
  precompute's ``.to_sparse()`` exactly; ``to_dense`` round-trips every
  mask (round 1's population-wide bootstrap sync is elided by design);
* engines — ``schedule='sparse'`` is *bit-identical* to dense across
  {safa, fedavg, fedcs} x {scan, loop} x {f32, int8} x {single, fleet};
  ``schedule='sparse_delta'`` (running-aggregate / stateless forms,
  including the packed kernels) is allclose;
* kernels — gather/scatter rows and the fused rows-aggregate kernels
  against numpy oracles, including sentinel-slot semantics;
* memory — quota-bounded schedules and stateless carries at m=10_000.

The environments here must be NON-degenerate (clients actually commit):
a too-small ``t_lim`` silences every mask and turns the identity
assertions vacuous.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conformance as C
from repro.core import api, federation, protocol, selection
from repro.data import make_regression, partition
from repro.data.tasks import regression_task
from repro.fedsim import FLEnv
from repro.kernels import ops, safa_aggregate
from repro.kernels.backend import VMEM_BUDGET, row_pad

M = 24
BASE = dict(m=M, crash_prob=0.3, dataset_size=480, batch_size=10,
            epochs=1, t_lim=200.0, seed=3)


def _env(**kw):
    base = dict(BASE)
    base.update(kw)
    return FLEnv(**base)


@pytest.fixture(scope='module')
def reg_task():
    x, y = make_regression()
    data = partition(x, y, _env().partition_sizes, 5, seed=1)
    return regression_task(data, lr=1e-3, epochs=3)


def _run(task, proto, proto_kw, exec_kw, rounds=8):
    return api.Experiment(task, _env(), api.spec(proto, **proto_kw),
                          api.ExecSpec(**exec_kw), rounds=rounds,
                          seed=0).compile().run()


def _trees_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _trees_close(a, b, **kw):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), **kw)


# ---------------------------------------------------------------------------
# Event-stream equality
# ---------------------------------------------------------------------------

class TestEventStreams:
    def test_safa_sparse_form_equals_dense_to_sparse(self):
        d = federation.precompute_safa_schedule(
            _env(), fraction=0.3, lag_tolerance=2, rounds=10)
        s = federation.precompute_safa_schedule(
            _env(), fraction=0.3, lag_tolerance=2, rounds=10, form='sparse')
        t = d.to_sparse()
        np.testing.assert_array_equal(s.idx, t.idx)
        np.testing.assert_array_equal(s.roles, t.roles)
        assert s.records[-1].round_len == d.records[-1].round_len
        assert s.futility == d.futility

    def test_sync_sparse_form_equals_dense_to_sparse(self):
        for fedcs in (False, True):
            d = federation.precompute_sync_schedule(
                _env(), fraction=0.3, rounds=10, seed=0, fedcs=fedcs)
            s = federation.precompute_sync_schedule(
                _env(), fraction=0.3, rounds=10, seed=0, fedcs=fedcs,
                form='sparse')
            t = d.to_sparse()
            np.testing.assert_array_equal(s.idx, t.idx)
            np.testing.assert_array_equal(s.roles, t.roles)

    def test_safa_to_dense_roundtrip(self):
        d = federation.precompute_safa_schedule(
            _env(), fraction=0.3, lag_tolerance=2, rounds=10)
        r = d.to_sparse().to_dense()
        # round 1's bootstrap sync (everyone holds w(0)) is elided: the
        # reconstruction recovers the active clients only
        np.testing.assert_array_equal(r.sync[1:], d.sync[1:])
        assert not r.sync[0][~(d.committed[0] | d.picked[0]
                               | d.undrafted[0] | d.deprecated[0])].any()
        for f in ('committed', 'picked', 'undrafted', 'deprecated'):
            np.testing.assert_array_equal(getattr(r, f), getattr(d, f))

    def test_bootstrap_round_has_no_sync_only_rows(self):
        s = federation.precompute_safa_schedule(
            _env(), fraction=0.3, lag_tolerance=5, rounds=6, form='sparse')
        r0 = s.roles[0][s.idx[0] < M]
        assert not np.any(r0 == protocol.ROLE_SYNC)

    def test_explicit_capacity_too_small_raises(self):
        d = federation.precompute_safa_schedule(
            _env(), fraction=0.5, lag_tolerance=2, rounds=6)
        with pytest.raises(ValueError, match='capacity'):
            d.to_sparse(capacity=1)

    def test_safa_tier_form_equals_dense_to_tier(self):
        d = federation.precompute_safa_schedule(
            _env(), fraction=0.3, lag_tolerance=2, rounds=10)
        s = federation.precompute_safa_schedule(
            _env(), fraction=0.3, lag_tolerance=2, rounds=10,
            form='sparse_tier')
        t = d.to_tier()
        for f in ('idx', 'roles', 'base_src', 'cache_src', 'cache_dst',
                  'global_dst'):
            np.testing.assert_array_equal(getattr(s, f), getattr(t, f))
        assert s.capacity == t.capacity
        # the event stream is the sparse one; only the slot maps are new
        sp = d.to_sparse()
        np.testing.assert_array_equal(s.idx, sp.idx)
        np.testing.assert_array_equal(s.roles, sp.roles)

    def test_tier_to_dense_roundtrip(self):
        d = federation.precompute_safa_schedule(
            _env(), fraction=0.3, lag_tolerance=2, rounds=10)
        r = d.to_tier().to_dense()
        np.testing.assert_array_equal(r.sync[1:], d.sync[1:])
        for f in ('committed', 'picked', 'undrafted', 'deprecated'):
            np.testing.assert_array_equal(getattr(r, f), getattr(d, f))

    def test_tier_slot_maps_stay_in_buffer(self):
        s = federation.precompute_safa_schedule(
            _env(), fraction=0.5, lag_tolerance=5, rounds=12,
            form='sparse_tier')
        scr = s.scratch
        for f in ('base_src', 'cache_src', 'cache_dst'):
            a = getattr(s, f)
            assert a.min() >= 0 and a.max() <= scr
        assert s.global_dst.min() >= 0 and s.global_dst.max() <= scr
        # within a round the written slots are distinct and disjoint from
        # the read slots (what lets the fused kernel alias the buffer)
        for t in range(s.rounds):
            srcs = set(s.base_src[t]) | set(s.cache_src[t])
            dsts = [d for d in s.cache_dst[t] if d != scr]
            if s.global_dst[t] != scr:
                dsts.append(int(s.global_dst[t]))
            assert len(dsts) == len(set(dsts))
            assert not (set(dsts) & (srcs - {scr}))

    def test_tier_explicit_capacity_too_small_raises(self):
        d = federation.precompute_safa_schedule(
            _env(), fraction=0.5, lag_tolerance=2, rounds=6)
        with pytest.raises(ValueError, match='capacity'):
            d.to_tier(capacity=1)


# ---------------------------------------------------------------------------
# Engine bit-identity: sparse == dense
# ---------------------------------------------------------------------------

class TestSparseBitIdentity:
    CASES = [
        ('safa', dict(fraction=0.3, lag_tolerance=2), 'scan', 'f32'),
        ('safa', dict(fraction=0.3, lag_tolerance=2), 'loop', 'f32'),
        ('safa', dict(fraction=0.3, lag_tolerance=30), 'scan', 'int8'),
        ('fedavg', dict(fraction=0.3), 'scan', 'f32'),
        ('fedavg', dict(fraction=0.3, sampler='topk'), 'loop', 'f32'),
        ('fedavg', dict(fraction=0.3), 'scan', 'int8'),
        ('fedcs', dict(fraction=0.3), 'scan', 'f32'),
    ]

    @pytest.mark.parametrize('proto,kw,engine,wire', CASES)
    def test_single(self, reg_task, proto, kw, engine, wire):
        ex = dict(engine=engine, wire=wire, eval_every=4)
        hd = _run(reg_task, proto, kw, dict(ex, schedule='dense'))
        hs = _run(reg_task, proto, kw, dict(ex, schedule='sparse'))
        _trees_equal(hd.final_global, hs.final_global)
        assert hd.best_eval == hs.best_eval

    @pytest.mark.parametrize('proto,kw', [
        ('safa', dict(lag_tolerance=2)), ('fedavg', {})])
    def test_fleet(self, reg_task, proto, kw):
        def members():
            return [federation.SweepMember(env=_env(), fraction=f, **kw)
                    for f in (0.3, 0.5)]
        def sweep(schedule):
            exp = api.Experiment(
                reg_task, _env(), api.spec(proto, fraction=0.3, **kw),
                api.ExecSpec(engine='fleet', schedule=schedule,
                             eval_every=4), rounds=8, seed=0)
            return exp.compile().run_sweep(members())
        hd, hs = sweep('dense'), sweep('sparse')
        for a, b in zip(hd, hs):
            _trees_equal(a.final_global, b.final_global)
            assert a.best_eval == b.best_eval

    def test_sequential_sweep(self, reg_task):
        def members():
            return [federation.SweepMember(env=_env(), fraction=0.3,
                                           lag_tolerance=2)]
        def sweep(schedule):
            exp = api.Experiment(
                reg_task, _env(), api.spec('safa', fraction=0.3),
                api.ExecSpec(engine='sequential', schedule=schedule,
                             eval_every=4), rounds=8, seed=0)
            return exp.compile().run_sweep(members())
        hd, hs = sweep('dense'), sweep('sparse')
        _trees_equal(hd[0].final_global, hs[0].final_global)


# ---------------------------------------------------------------------------
# sparse_delta: allclose to dense (running-aggregate / stateless forms)
# ---------------------------------------------------------------------------

class TestSparseDelta:
    TOL = dict(rtol=2e-5, atol=2e-6)

    @pytest.mark.parametrize('proto,kw,engine', [
        ('safa', dict(fraction=0.3, lag_tolerance=2), 'scan'),
        ('safa', dict(fraction=0.3, lag_tolerance=2), 'loop'),
        ('fedavg', dict(fraction=0.3), 'scan'),
        ('fedcs', dict(fraction=0.3), 'scan'),
    ])
    def test_tree_engines(self, reg_task, proto, kw, engine):
        ex = dict(engine=engine, eval_every=4)
        hd = _run(reg_task, proto, kw, dict(ex, schedule='dense'))
        hs = _run(reg_task, proto, kw, dict(ex, schedule='sparse_delta'))
        _trees_close(hd.final_global, hs.final_global, **self.TOL)

    @pytest.mark.parametrize('wire', ['f32', 'int8'])
    def test_safa_packed(self, reg_task, wire):
        kw = dict(fraction=0.3, lag_tolerance=2)
        hd = _run(reg_task, 'safa', kw,
                  dict(engine='scan', wire=wire, eval_every=4,
                       schedule='dense'))
        hp = _run(reg_task, 'safa', kw,
                  dict(engine='scan', wire=wire, eval_every=4,
                       schedule='sparse_delta', use_kernel='packed'))
        tol = dict(rtol=2e-2, atol=2e-2) if wire == 'int8' else self.TOL
        _trees_close(hd.final_global, hp.final_global, **tol)

    def test_fedavg_stateless_carry(self, reg_task):
        """The stateless sparse_delta carry never materialises the
        [m, ...] local stack."""
        exp = api.Experiment(reg_task, _env(), api.spec('fedavg', fraction=0.3),
                             api.ExecSpec(schedule='sparse_delta'),
                             rounds=4, seed=0)
        r = exp.compile()
        from repro.core.api import _init_state
        st = _init_state(exp.task, M, 0, r._pdef.uses_cache,
                         r._stateless(exp.exec))
        assert st.local_w is None and st.cache is None
        h = r.run()
        assert np.isfinite(h.best_eval['loss'])


# ---------------------------------------------------------------------------
# sparse_tier: lag-tier compressed value buffer
# ---------------------------------------------------------------------------

class TestSparseTier:
    """``schedule='sparse_tier'``: the [m, N] stacks collapse to one
    [capacity+1, N] value buffer.  Allclose to dense (and to
    sparse_delta — same running-aggregate math over different storage);
    *bit*-identical within the form (scan == loop, fleet == sequential;
    fleet members replay the fleet-padded program, so a standalone
    single run is allclose, not bitwise)."""
    TOL = dict(rtol=2e-5, atol=2e-6)
    TOL8 = dict(rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize('engine', ['scan', 'loop'])
    def test_tree_engines_close_to_dense_and_delta(self, reg_task, engine):
        kw = dict(fraction=0.3, lag_tolerance=2)
        ex = dict(engine=engine, eval_every=4)
        hd = _run(reg_task, 'safa', kw, dict(ex, schedule='dense'))
        hs = _run(reg_task, 'safa', kw, dict(ex, schedule='sparse_delta'))
        ht = _run(reg_task, 'safa', kw, dict(ex, schedule='sparse_tier'))
        _trees_close(hd.final_global, ht.final_global, **self.TOL)
        _trees_close(hs.final_global, ht.final_global, **self.TOL)

    def test_scan_matches_loop(self, reg_task):
        """Within ``C.ENGINE_TOL``: the scan fuses the delta sums."""
        kw = dict(fraction=0.3, lag_tolerance=2)
        ex = dict(eval_every=4, schedule='sparse_tier')
        hs = _run(reg_task, 'safa', kw, dict(ex, engine='scan'))
        hl = _run(reg_task, 'safa', kw, dict(ex, engine='loop'))
        C.assert_tree_close(hs.final_global, hl.final_global, 'tier')
        C.assert_evals_close(hs, hl, 'tier')

    @pytest.mark.parametrize('wire', ['f32', 'int8'])
    def test_packed_close_to_dense(self, reg_task, wire):
        kw = dict(fraction=0.3, lag_tolerance=2)
        hd = _run(reg_task, 'safa', kw,
                  dict(engine='scan', wire=wire, eval_every=4,
                       schedule='dense'))
        hp = _run(reg_task, 'safa', kw,
                  dict(engine='scan', wire=wire, eval_every=4,
                       schedule='sparse_tier', use_kernel='packed'))
        tol = self.TOL8 if wire == 'int8' else self.TOL
        _trees_close(hd.final_global, hp.final_global, **tol)

    def test_packed_int8_scan_equals_loop_bitwise(self, reg_task):
        kw = dict(fraction=0.3, lag_tolerance=30)
        ex = dict(wire='int8', eval_every=4, schedule='sparse_tier',
                  use_kernel='packed')
        hs = _run(reg_task, 'safa', kw, dict(ex, engine='scan'))
        hl = _run(reg_task, 'safa', kw, dict(ex, engine='loop'))
        _trees_equal(hs.final_global, hl.final_global)

    @pytest.mark.parametrize('exec_kw,tol', [
        (dict(), 'TOL'),
        (dict(use_kernel='packed'), 'TOL'),
        (dict(use_kernel='packed', wire='int8'), 'TOL8'),
    ])
    def test_fleet_equals_sequential(self, reg_task, exec_kw, tol):
        # fresh members per sweep: every precompute consumes its env rng
        def members():
            return [federation.SweepMember(env=_env(), fraction=f,
                                           lag_tolerance=2)
                    for f in (0.3, 0.5)]
        def sweep(engine):
            exp = api.Experiment(
                reg_task, _env(),
                api.spec('safa', fraction=0.3, lag_tolerance=2),
                api.ExecSpec(engine=engine, schedule='sparse_tier',
                             eval_every=4, **exec_kw), rounds=8, seed=0)
            return exp.compile().run_sweep(members())
        hf, hq = sweep('fleet'), sweep('sequential')
        for a, b in zip(hf, hq):
            _trees_equal(a.final_global, b.final_global)
            assert a.best_eval == b.best_eval
        # a standalone run of member 0 replays the same events at its own
        # (unpadded) width/capacity: allclose, not bitwise
        h0 = _run(reg_task, 'safa', dict(fraction=0.3, lag_tolerance=2),
                  dict(engine='scan', schedule='sparse_tier', eval_every=4,
                       **exec_kw))
        _trees_close(hf[0].final_global, h0.final_global,
                     **getattr(self, tol))

    def test_stateless_tier_carry(self, reg_task):
        """No [m, ...] stacks: the carry is global + [capacity+1, ...]
        value buffer + running aggregate, built by prepare_state."""
        exp = api.Experiment(
            reg_task, _env(),
            api.spec('safa', fraction=0.3, lag_tolerance=2),
            api.ExecSpec(schedule='sparse_tier'), rounds=6, seed=0)
        r = exp.compile()
        from repro.core.api import _init_state
        st = _init_state(exp.task, M, 0, r._pdef.uses_cache,
                         r._stateless(exp.exec))
        assert st.local_w is None and st.cache is None
        sched = exp.precompute()
        r._pdef.prepare_state(st, jnp.asarray(exp.env.weights), exp.exec,
                              False, sched)
        assert st.local_w is None
        for leaf in jax.tree.leaves(st.cache):
            assert leaf.shape[0] == sched.capacity + 1
        h = r.run()
        assert np.isfinite(h.best_eval['loss'])


# ---------------------------------------------------------------------------
# check_compat gating
# ---------------------------------------------------------------------------

class TestCompat:
    def test_unknown_schedule(self):
        with pytest.raises(ValueError, match='schedule'):
            api.check_compat(api.SafaSpec(), api.ExecSpec(schedule='csr'))

    def test_sparse_needs_sparse_precompute(self):
        with pytest.raises(ValueError, match='sparse'):
            api.check_compat(api.LocalSpec(), api.ExecSpec(schedule='sparse'))

    def test_sparse_rejects_quantize_uploads(self):
        with pytest.raises(ValueError, match='quantize_uploads'):
            api.check_compat(api.SafaSpec(quantize_uploads=True),
                             api.ExecSpec(schedule='sparse'))

    def test_sparse_delta_rejects_plain_kernel(self):
        with pytest.raises(ValueError, match='use_kernel'):
            api.check_compat(api.SafaSpec(),
                             api.ExecSpec(schedule='sparse_delta',
                                          use_kernel=True))

    def test_unknown_schedule_names_sparse_tier(self):
        with pytest.raises(ValueError, match='sparse_tier'):
            api.check_compat(api.SafaSpec(), api.ExecSpec(schedule='csr'))

    def test_sparse_tier_needs_tier_precompute(self):
        with pytest.raises(ValueError, match='lag-tier'):
            api.check_compat(api.FedAvgSpec(),
                             api.ExecSpec(schedule='sparse_tier'))

    def test_sparse_tier_rejects_plain_kernel(self):
        with pytest.raises(ValueError, match='use_kernel'):
            api.check_compat(api.SafaSpec(),
                             api.ExecSpec(schedule='sparse_tier',
                                          use_kernel=True))

    def test_bad_sampler(self):
        with pytest.raises(ValueError, match='sampler'):
            api.check_compat(api.FedAvgSpec(sampler='bogus'), api.ExecSpec())


# ---------------------------------------------------------------------------
# Samplers
# ---------------------------------------------------------------------------

class TestTopkSampler:
    def test_shape_and_uniqueness(self):
        idx = selection.fedavg_select_topk(
            np.random.default_rng(0), 1000, 0.05, rounds=7)
        assert idx.shape == (7, 50) and idx.dtype == np.int32
        for t in range(7):
            assert len(set(idx[t].tolist())) == 50
            assert idx[t].min() >= 0 and idx[t].max() < 1000
        assert not np.array_equal(idx[0], idx[1])

    def test_chunking_keeps_stream(self):
        """Row-major draws mean the chunked implementation consumes the
        generator exactly like one bulk (rounds, m) draw."""
        rng = np.random.default_rng(7)
        u = rng.random((9, 40))
        want = np.sort(np.argpartition(u, 11, axis=-1)[:, :12], axis=-1)
        got = selection.fedavg_select_topk(
            np.random.default_rng(7), 40, 0.3, rounds=9)
        np.testing.assert_array_equal(got, want.astype(np.int32))

    def test_sampler_reaches_schedule(self):
        a = federation.precompute_sync_schedule(
            _env(), fraction=0.3, rounds=6, seed=0, fedcs=False,
            form='sparse', sampler='topk')
        b = federation.precompute_sync_schedule(
            _env(), fraction=0.3, rounds=6, seed=0, fedcs=False,
            form='sparse', sampler='choice')
        assert not np.array_equal(a.idx, b.idx)


# ---------------------------------------------------------------------------
# Kernels: gather/scatter rows + fused rows-aggregate, vs numpy oracles
# ---------------------------------------------------------------------------

class TestRowsKernels:
    def _buf(self, rng, r, n):
        return jnp.asarray(rng.standard_normal((r, n)).astype(np.float32))

    def test_gather_scatter_roundtrip(self):
        rng = np.random.default_rng(0)
        m, n, tile = 37, 512, 256
        # scatter_rows writes whole 8-row groups: the buffer holds m + 1
        # rows (trailing scratch row) padded to a multiple of 8
        buf = self._buf(rng, row_pad(m + 1), n)
        rows = jnp.asarray(np.array([3, 9, 14, m, 2], np.int32))
        got = ops.gather_rows(buf, rows, tile=tile)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(buf)[np.asarray(rows)])
        vals = self._buf(rng, 5, n)
        want = np.asarray(buf).copy()           # snapshot: buf is donated
        want[np.asarray(rows)] = np.asarray(vals)   # sentinel -> scratch row
        out = ops.scatter_rows(buf, rows, vals, tile=tile)
        np.testing.assert_array_equal(np.asarray(out), want)

    def test_gather_scatter_fleet(self):
        rng = np.random.default_rng(1)
        s, m, n, k, tile = 3, 21, 256, 4, 256
        r = row_pad(m + 1)
        buf = self._buf(rng, s * r, n).reshape(s, r, n)
        rows = jnp.asarray(rng.integers(0, m + 1, (s, k)).astype(np.int32))
        # fleets batch the rows kernels under vmap
        got = jax.vmap(lambda b, i: ops.gather_rows(b, i, tile=tile))(
            buf, rows)
        want = np.stack([np.asarray(buf)[b][np.asarray(rows)[b]]
                         for b in range(s)])
        np.testing.assert_array_equal(np.asarray(got), want)
        vals = self._buf(rng, s * k, n).reshape(s, k, n)
        want = np.asarray(buf).copy()           # snapshot: buf is donated
        for b in range(s):
            want[b][np.asarray(rows)[b]] = np.asarray(vals)[b]
        out = jax.vmap(lambda b, i, v: ops.scatter_rows(b, i, v, tile=tile))(
            buf, rows, vals)
        np.testing.assert_array_equal(np.asarray(out), want)

    def test_tile_mismatch_raises(self):
        buf = jnp.zeros((4, 300), jnp.float32)
        with pytest.raises(ValueError, match='pad_to'):
            ops.gather_rows(buf, jnp.zeros((2,), jnp.int32), tile=256)

    def test_rows_aggregate_oracle(self):
        rng = np.random.default_rng(2)
        m, n, k, tile = 13, 512, 6, 256
        cache = rng.standard_normal((m + 1, n)).astype(np.float32)
        trained = rng.standard_normal((k, n)).astype(np.float32)
        gprev = rng.standard_normal(n).astype(np.float32)
        agg = rng.standard_normal(n).astype(np.float32)
        rows = np.array([1, 5, 7, m, 2, 9], np.int32)
        pick = np.array([1, 0, 1, 0, 0, 1], bool)
        und = np.array([0, 1, 0, 0, 0, 0], bool)
        dep = np.array([0, 0, 0, 0, 1, 0], bool)
        w = np.where(rows < m, rng.random(k).astype(np.float32), 0.0)

        ng, na, c2 = ops.safa_aggregate_packed_rows(
            jnp.asarray(cache), jnp.asarray(trained), jnp.asarray(gprev),
            jnp.asarray(agg), jnp.asarray(rows), jnp.asarray(pick),
            jnp.asarray(und), jnp.asarray(dep), jnp.asarray(w), tile=tile)

        c0 = cache[rows]                       # sentinel gathers scratch row
        c1 = np.where(pick[:, None], trained,
                      np.where(dep[:, None], gprev[None], c0))
        ng_w = agg + (w[:, None] * (c1 - c0)).sum(0)
        c2_w = np.where(und[:, None], trained, c1)
        na_w = ng_w + (w[:, None] * (c2_w - c1)).sum(0)
        np.testing.assert_allclose(np.asarray(ng), ng_w, rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(na), na_w, rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(c2), c2_w, rtol=1e-6,
                                   atol=0)


class TestSlabKernels:
    """The lag-tier value buffer as a row slab [R, N/128, 128]: the
    tier-rows kernels and ``gather_rows`` copy exact rows of it."""
    R, N, K = 16, 4096, 6
    # cache-read and cache-write slots of a round: disjoint, as the host
    # allocator makes them, but for the scratch row 15 that inert slots
    # share (last-wins, like the sentinel writes of ``scatter_rows``)
    SRCS, DSTS = [0, 9, 15, 2, 15, 11], [4, 10, 15, 5, 15, 12]

    def _slab(self, rng, *lead):
        x = rng.standard_normal(lead + (self.R, self.N)).astype(np.float32)
        return jnp.asarray(x.reshape(lead + (self.R, self.N // 128, 128)))

    @staticmethod
    @jax.jit
    def _slot_math(buf, trained, gprev, agg, srcs, dsts, pick, und, dep,
                   w):
        """The tier kernels' slot math on an [R, N] buffer, one slot
        after another as their inner grid axis runs: Eq. 6 and 8 on the
        row at srcs[k], c2 written to dsts[k], both Eq. 7 deltas."""
        new_global = new_agg = agg
        for k in range(srcs.shape[0]):
            c0 = buf[srcs[k]]
            c1 = jnp.where(dep[k] & ~pick[k], gprev, c0)
            c1 = jnp.where(pick[k], trained[k], c1)
            c2 = jnp.where(und[k], trained[k], c1)
            buf = buf.at[dsts[k]].set(c2)
            new_global = new_global + w[k] * (c1 - c0)
            new_agg = new_agg + w[k] * (c2 - c0)
        return new_global, new_agg, buf

    def _round(self, rng, wire):
        k, n = self.K, self.N
        f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
        mask = lambda: jnp.asarray(rng.random(k) < 0.5)
        slab, gprev, agg = self._slab(rng), f32(n), f32(n)
        roles = (mask(), mask(), mask())
        w = jnp.asarray(rng.random(k), jnp.float32).at[
            np.array(self.SRCS) == self.R - 1].set(0.0)
        slots = (jnp.asarray(self.SRCS, jnp.int32),
                 jnp.asarray(self.DSTS, jnp.int32))
        if wire == 'f32':
            trained = f32(k, n)
            return (trained, (slab, trained, gprev, agg, *slots, *roles, w))
        q = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
        scales = jnp.asarray(rng.random((k, n // 128)) + 0.01, jnp.float32)
        base, completed = f32(k, n), mask()
        deq = (q.astype(jnp.float32).reshape(k, n // 128, 128)
               * scales[:, :, None]).reshape(k, n)
        trained = jnp.where(completed[:, None], deq, base)
        return (trained, (q, scales, base, slab, gprev, agg, *slots, *roles,
                          completed, w))

    @pytest.mark.parametrize('tile', [1024, None])
    @pytest.mark.parametrize('wire', ['f32', 'int8'])
    def test_tier_rows_bitwise_the_slot_math(self, wire, tile):
        trained, args = self._round(np.random.default_rng(7), wire)
        if wire == 'f32':
            fn, (slab, _, gprev, agg, srcs, dsts, p, u, d, w) = \
                ops.safa_aggregate_packed_tier_rows, args
        else:
            fn = ops.safa_aggregate_packed_q8_tier_rows
            _, _, _, slab, gprev, agg, srcs, dsts, p, u, d, _, w = args
        want = self._slot_math(slab.reshape(self.R, self.N), trained, gprev,
                               agg, srcs, dsts, p, u, d, w)
        got = fn(*args, tile=tile)
        assert got[2].shape == slab.shape
        got = (got[0], got[1], got[2].reshape(self.R, self.N))
        for g, x in zip(got, want):     # new_global, new_agg, new_buf
            np.testing.assert_array_equal(np.asarray(g), np.asarray(x))

    @pytest.mark.parametrize('tile', [1024, None])
    def test_gather_rows_from_a_slab(self, tile):
        rng = np.random.default_rng(8)
        slab = self._slab(rng)
        rows = jnp.asarray([3, 0, 15, 3, 7, 15, 12, 1, 9], jnp.int32)
        got = ops.gather_rows(slab, rows, tile=tile)
        want = slab.reshape(self.R, self.N)[rows]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_gather_rows_from_a_fleet_slab(self):
        rng = np.random.default_rng(9)
        s = 3
        slab = self._slab(rng, s)
        rows = jnp.asarray(rng.integers(0, self.R, (s, self.K)), jnp.int32)
        got = jax.vmap(ops.gather_rows)(slab, rows)
        want = jax.vmap(lambda b, i: b.reshape(self.R, self.N)[i])(slab,
                                                                   rows)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_a_slab_of_partial_tiles_raises(self):
        """N/128 = 12 rows of 128 lanes: a row is not whole (8, 128)
        tiles, so no DMA can move it alone."""
        slab = jnp.zeros((self.R, 12, 128), jnp.float32)
        with pytest.raises(ValueError, match='row slab'):
            ops.gather_rows(slab, jnp.zeros((2,), jnp.int32))
        k, n = 2, 12 * 128
        with pytest.raises(ValueError, match='row slab'):
            ops.safa_aggregate_packed_tier_rows(
                slab, jnp.zeros((k, n)), jnp.zeros(n), jnp.zeros(n),
                *(jnp.zeros(k, jnp.int32),) * 2,
                *(jnp.zeros(k, bool),) * 3, jnp.zeros(k), tile=1536)


GRANULE = safa_aggregate.DEFAULT_TILE


@pytest.mark.parametrize('col_bytes', [16, 112, 145, 1024])
@pytest.mark.parametrize('granules', [1, 2, 3, 167, 684, 720])
def test_tier_tile_is_the_widest_granule_multiple_that_fits(granules,
                                                            col_bytes):
    n = granules * GRANULE
    tile, params = safa_aggregate.tier_tile(n, col_bytes)
    fits = lambda t: 2 * col_bytes * t <= VMEM_BUDGET
    assert n % tile == 0 and tile % GRANULE == 0
    assert fits(tile) and params is None
    wider = range(tile + GRANULE, n + 1, GRANULE)
    assert not any(n % t == 0 and fits(t) for t in wider)


def test_tier_tile_narrows_the_granule_where_none_fits():
    col_bytes = VMEM_BUDGET // GRANULE          # the granule needs twice
    tile, params = safa_aggregate.tier_tile(167 * GRANULE, col_bytes)
    assert (tile, params) == (GRANULE // 2, None)
    with pytest.raises(ValueError, match='pad_to'):
        safa_aggregate.tier_tile(GRANULE + 128, 16)

# ---------------------------------------------------------------------------
# pack_spec validation
# ---------------------------------------------------------------------------

class TestPackSpecValidation:
    def test_rejects_non_positive(self):
        tree = {'w': jnp.zeros((8,), jnp.float32)}
        with pytest.raises(ValueError, match='pad_to'):
            ops.pack_spec(tree, pad_to=0)
        with pytest.raises(ValueError, match='align'):
            ops.pack_spec(tree, pad_to=128, align=0)

    def test_rejects_misaligned_pad(self):
        tree = {'w': jnp.zeros((8,), jnp.float32)}
        with pytest.raises(ValueError, match='multiple'):
            ops.pack_spec(tree, pad_to=100, align=64)


# ---------------------------------------------------------------------------
# Memory: quota-bounded schedules at m=10_000
# ---------------------------------------------------------------------------

class TestMemorySmoke:
    def test_quota_bounded_schedule_and_state(self):
        from benchmarks.scale import ScaleTask, make_scale_env
        m, quota, rounds = 10_000, 20, 6
        env = make_scale_env(m, quota)
        s = federation.precompute_safa_schedule(
            env, fraction=quota / m, lag_tolerance=10 * rounds,
            rounds=rounds, form='sparse')
        # active set ~2.5*quota by regime construction, never O(m)
        assert s.capacity <= 4 * quota
        assert s.nbytes <= rounds * 4 * quota * 5
        dense_bytes = rounds * m * 5    # five [rounds, m] bool masks
        assert s.nbytes < dense_bytes / 50

        # stateless fedavg sparse_delta at m=10_000: O(d) resident state
        env2 = make_scale_env(m, quota, bound_active=False)
        exp = api.Experiment(
            ScaleTask(), env2, api.spec('fedavg', fraction=quota / m,
                                        sampler='topk'),
            api.ExecSpec(schedule='sparse_delta', eval_every=rounds),
            rounds=rounds, seed=0)
        r = exp.compile()
        from repro.core.api import _init_state
        st = _init_state(exp.task, m, 0, r._pdef.uses_cache,
                         r._stateless(exp.exec))
        state_bytes = sum(getattr(l, 'nbytes', 0)
                          for l in jax.tree.leaves(st.tree()))
        assert state_bytes < 10_000          # D floats, not m*D
        h = r.run()
        assert np.isfinite(h.best_eval['loss'])

    def test_tier_state_is_quota_bounded(self):
        """SAFA sparse_tier at m=10_000: the whole carry is
        O((tau + quota) * D), independent of m."""
        from benchmarks.scale import ScaleTask, make_scale_env
        m, quota, rounds = 10_000, 20, 6
        env = make_scale_env(m, quota)
        exp = api.Experiment(
            ScaleTask(), env,
            api.spec('safa', fraction=quota / m,
                     lag_tolerance=10 * rounds),
            api.ExecSpec(schedule='sparse_tier', eval_every=rounds),
            rounds=rounds, seed=0)
        r = exp.compile()
        sched = exp.precompute()
        # slot capacity tracks the active-set bound, never O(m)
        assert sched.capacity <= 8 * quota
        from repro.core.api import _init_state
        st = _init_state(exp.task, m, 0, r._pdef.uses_cache,
                         r._stateless(exp.exec))
        r._pdef.prepare_state(st, jnp.asarray(env.weights), exp.exec,
                              False, sched)
        state_bytes = sum(getattr(l, 'nbytes', 0)
                          for l in jax.tree.leaves(st.tree()))
        d = sum(l.size for l in jax.tree.leaves(st.global_w))
        # (capacity+1 buffer rows + global + agg) * 4 bytes, with slack
        assert state_bytes <= (sched.capacity + 4) * d * 4
        assert state_bytes < m * d              # << the [m, D] stack
        h = r.run()
        assert np.isfinite(h.best_eval['loss'])
