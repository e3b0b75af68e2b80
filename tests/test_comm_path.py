"""Compressed-wire fast path: packed int8 quantize kernels, the fused
dequant-aggregate kernel, the ``wire='int8'`` protocol knob, and the
satellite helpers (backend detection, comm_bytes layouts, memoised
per-leaf reference wrapper).

No hypothesis dependency — this module must run in a bare environment.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conformance as C
from repro.core import federation, protocol
from repro.data import make_regression, partition
from repro.data.tasks import regression_task
from repro.fedsim import FLEnv
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.comm_quant import (QBLOCK, dequantize, dequantize_packed,
                                      quantize, quantize_packed)
from repro.kernels.safa_aggregate import safa_aggregate_packed_q8


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


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


class TestQuantizePacked:
    @pytest.mark.parametrize('m,n,tile', [(1, 2048, 2048), (5, 4096, 2048),
                                          (8, 1024, 512), (3, 512, 256)])
    def test_matches_per_row_kernel(self, m, n, tile):
        """The packed kernel == m per-row ``quantize`` calls, bit for bit
        (the contract that makes the wire path bit-identical to the
        per-leaf reference)."""
        x = jax.random.normal(jax.random.PRNGKey(m + n), (m, n)) * 2.0
        q, s = quantize_packed(x, tile=tile)
        for k in range(m):
            qk, sk = quantize(x[k], tile=tile)
            np.testing.assert_array_equal(np.asarray(q[k]), np.asarray(qk))
            np.testing.assert_array_equal(np.asarray(s[k]), np.asarray(sk))

    def test_matches_oracle(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 4096)) * 3.0
        q, s = quantize_packed(x)
        rq, rs = ref.quantize_packed_ref(x)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(rq))
        np.testing.assert_allclose(np.asarray(s), np.asarray(rs), rtol=1e-6)

    def test_dequantize_roundtrip(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (6, 2048)) * 2.0
        q, s = quantize_packed(x)
        xd = dequantize_packed(q, s)
        for k in range(6):
            dk = dequantize(q[k], s[k], n=2048)
            np.testing.assert_array_equal(np.asarray(xd[k]), np.asarray(dk))
        # int8 symmetric error bound: half a quant step per block
        err = np.abs(np.asarray(xd) - np.asarray(x))
        bound = np.repeat(np.asarray(s) / 2 + 1e-7, QBLOCK, axis=1)
        assert np.all(err <= bound + 1e-6)

    def test_fleet_matches_singles(self):
        xs = jax.random.normal(jax.random.PRNGKey(2), (3, 5, 2048))
        qf, sf = jax.vmap(quantize_packed)(xs)
        for i in range(3):
            q1, s1 = quantize_packed(xs[i])
            np.testing.assert_array_equal(np.asarray(qf[i]), np.asarray(q1))
            np.testing.assert_array_equal(np.asarray(sf[i]), np.asarray(s1))

    def test_rejects_unpadded_width(self):
        with pytest.raises(ValueError, match='multiple of tile'):
            quantize_packed(jnp.zeros((2, 1000)))
        with pytest.raises(ValueError, match='QBLOCK'):
            quantize_packed(jnp.zeros((2, 192)), tile=192)


class TestPackedQ8Kernel:
    def _operands(self, m=5, n=4096, key=0):
        ks = jax.random.split(jax.random.PRNGKey(key), 9)
        x = jax.random.normal(ks[8], (m, n)) * 2.0
        q, s = quantize_packed(x)
        return dict(
            q=q, scales=s,
            base=jax.random.normal(ks[0], (m, n)),
            cache=jax.random.normal(ks[1], (m, n)),
            global_prev=jax.random.normal(ks[2], (n,)),
            picked=jax.random.bernoulli(ks[3], 0.4, (m,)),
            undrafted=jax.random.bernoulli(ks[4], 0.4, (m,)),
            deprecated=jax.random.bernoulli(ks[5], 0.3, (m,)),
            completed=jax.random.bernoulli(ks[6], 0.7, (m,)),
            weights=jax.nn.softmax(jax.random.normal(ks[7], (m,))))

    def test_matches_composition_oracle(self):
        """Fused kernel == dequantise rows -> crash-substitute -> Eq. 6-8,
        bit for bit, including the new_local output."""
        ops = self._operands()
        ng, nc, nl = safa_aggregate_packed_q8(*ops.values())
        rg, rc, rl = ref.safa_aggregate_q8_ref(*ops.values())
        np.testing.assert_array_equal(np.asarray(ng), np.asarray(rg))
        np.testing.assert_array_equal(np.asarray(nc), np.asarray(rc))
        np.testing.assert_array_equal(np.asarray(nl), np.asarray(rl))

    def test_fleet_matches_singles(self):
        singles = [self._operands(key=k) for k in range(3)]
        stacked = [jnp.stack([np.asarray(s[k]) for s in singles])
                   for k in singles[0]]
        outs_f = jax.vmap(safa_aggregate_packed_q8)(*stacked)
        for i, ops in enumerate(singles):
            outs_1 = safa_aggregate_packed_q8(*ops.values())
            for a, b in zip(outs_f, outs_1):
                np.testing.assert_array_equal(np.asarray(a[i]), np.asarray(b))

    def test_single_dispatch(self):
        ops = self._operands()
        jaxpr = jax.make_jaxpr(
            lambda *a: safa_aggregate_packed_q8(*a))(*ops.values())
        assert kops.count_pallas_calls(jaxpr.jaxpr) == 1

    def test_rejects_unpadded_width(self):
        ops = self._operands(n=2048)
        with pytest.raises(ValueError, match='multiple of tile'):
            safa_aggregate_packed_q8(*ops.values(), tile=4096)


class TestWireSpecAlignment:
    SHAPES = ((4, 3), (64,), (8, 33), (2, 5, 7))

    def _global(self, key=4):
        ks = jax.random.split(jax.random.PRNGKey(key), len(self.SHAPES))
        return {f'p{i}': jax.random.normal(k, s)
                for i, (k, s) in enumerate(zip(ks, self.SHAPES))}

    def test_offsets_qblock_aligned(self):
        spec = kops.wire_spec(self._global())
        assert all(o % QBLOCK == 0 for o in spec.offsets)
        assert spec.n_total % QBLOCK == 0
        assert spec.n_padded % 2048 == 0
        for i, size in enumerate(spec.sizes):
            assert spec.slot(i) >= size

    def test_aligned_pack_roundtrip(self):
        g = self._global()
        spec = kops.wire_spec(g)
        m = 4
        stacked = jax.tree.map(lambda a: jnp.stack([a] * m), g)
        back = kops.unpack_stacked(kops.pack_stacked(stacked, spec), spec)
        _assert_trees_equal(back, stacked)
        gback = kops.unpack_global(kops.pack_global(g, spec), spec)
        _assert_trees_equal(gback, g)

    def test_wire_roundtrip_matches_per_leaf_reference(self):
        """``wire_roundtrip_packed`` (2 dispatches) == each client
        quantising each leaf independently (2 per leaf per client)."""
        g = self._global()
        m = 4
        stacked = jax.tree.map(
            lambda a: jax.random.normal(
                jax.random.PRNGKey(int(a.size)), (m,) + a.shape), g)
        rt = kops.wire_roundtrip_packed(stacked, like=g)

        def per_leaf(x):
            flat = x.reshape(m, -1)
            rows = [dequantize(*quantize(flat[k]), n=flat.shape[1])
                    for k in range(m)]
            return jnp.stack(rows).reshape(x.shape)

        _assert_trees_equal(rt, jax.tree.map(per_leaf, stacked))

    def test_non_f32_rejected(self):
        g16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), self._global())
        stacked = jax.tree.map(lambda a: jnp.stack([a] * 2), g16)
        with pytest.raises(TypeError, match='float32'):
            kops.wire_roundtrip_packed(stacked, like=g16)


class TestWireRound:
    KW = dict(fraction=0.5, lag_tolerance=5, rounds=8, eval_every=4)

    def test_scan_bit_identical_to_loop(self, reg_task):
        hists = {e: federation.run_safa(reg_task, _env(), engine=e,
                                        wire='int8', **self.KW)
                 for e in ('loop', 'scan')}
        _assert_trees_equal(hists['loop'].final_global,
                            hists['scan'].final_global)
        assert hists['loop'].evals() == hists['scan'].evals()

    def test_bit_identical_to_per_leaf_reference(self, reg_task):
        """Acceptance criterion: the packed wire path (2 dispatches per
        round) is bit-identical to the per-leaf quantize->dequantize
        reference (``quantize_uploads=True``), against both the jnp and
        the packed-kernel aggregation forms of the reference."""
        h_wire = federation.run_safa(reg_task, _env(), engine='scan',
                                     wire='int8', **self.KW)
        h_ref = federation.run_safa(reg_task, _env(), engine='scan',
                                    quantize_uploads=True, **self.KW)
        h_ref_packed = federation.run_safa(
            reg_task, _env(), engine='scan', quantize_uploads=True,
            use_kernel='packed', **self.KW)
        _assert_trees_equal(h_wire.final_global, h_ref.final_global)
        _assert_trees_equal(h_wire.final_global, h_ref_packed.final_global)
        assert h_wire.evals() == h_ref.evals()

    def test_fleet_bit_identical_to_sequential(self, reg_task):
        def members():
            return [federation.SweepMember(env=_env(), fraction=0.5,
                                           lag_tolerance=5, seed=s)
                    for s in (0, 1)]
        hf = federation.run_sweep(reg_task, members(), rounds=6,
                                  eval_every=3, wire='int8', engine='fleet')
        hs = federation.run_sweep(reg_task, members(), rounds=6,
                                  eval_every=3, wire='int8',
                                  engine='sequential')
        for a, b in zip(hf, hs):
            _assert_trees_equal(a.final_global, b.final_global)
            assert a.evals() == b.evals()

    def test_compressed_scan_round_is_two_dispatches(self, reg_task):
        """Acceptance criterion: a wire='int8' SAFA round on the packed
        path issues exactly 2 pallas_calls (quantize + fused
        dequant-aggregate), regardless of model depth."""
        env = _env()
        sched = federation.precompute_safa_schedule(
            env, fraction=0.5, lag_tolerance=5, rounds=3)
        ns = federation._NumericState(reg_task, env.m, 0)
        w = jnp.asarray(env.weights)
        jaxpr = jax.make_jaxpr(
            lambda g, l, c, s, ww: protocol.safa_run_scan(
                g, l, c, s, ww, local_train_fn=reg_task.local_train,
                use_kernel=False, wire='int8')
        )(ns.global_w, ns.local_w, ns.cache, sched.to_device(), w)
        assert kops.count_pallas_calls(jaxpr.jaxpr) == 2

    def test_fedavg_wire_scan_matches_loop(self, reg_task):
        """Within ``C.ENGINE_TOL``: the scan fuses the renormalised sum."""
        hists = {e: federation.run_fedavg(reg_task, _env(), fraction=0.5,
                                          rounds=6, eval_every=3, engine=e,
                                          wire='int8')
                 for e in ('loop', 'scan')}
        C.assert_tree_close(hists['loop'].final_global,
                            hists['scan'].final_global, 'fedavg int8')

    def test_fedavg_wire_close_to_f32(self, reg_task):
        """The int8 wire perturbs FedAvg only at quantisation-noise
        scale."""
        h_q = federation.run_fedavg(reg_task, _env(), fraction=0.5,
                                    rounds=10, eval_every=10, wire='int8')
        h_f = federation.run_fedavg(reg_task, _env(), fraction=0.5,
                                    rounds=10, eval_every=10)
        assert h_q.best_eval['loss'] < h_f.best_eval['loss'] * 1.5 + 1.0

    def test_wire_validation(self, reg_task):
        with pytest.raises(ValueError, match='wire'):
            federation.run_safa(reg_task, _env(), wire='int4', **self.KW)
        with pytest.raises(ValueError, match='wire'):
            federation.run_fedavg(reg_task, _env(), fraction=0.5, rounds=2,
                                  wire='fp8')
        with pytest.raises(ValueError, match='reference'):
            federation.run_safa(reg_task, _env(), wire='int8',
                                quantize_uploads=True, **self.KW)

    def test_sweep_rejects_wire_for_local_and_fedasync(self, reg_task):
        members = [federation.SweepMember(env=_env(), fraction=0.5)]
        for proto in ('local', 'fedasync'):
            with pytest.raises(ValueError, match='wire'):
                federation.run_sweep(reg_task, members, rounds=2,
                                     proto=proto, wire='int8')


class TestBackendHelper:
    def test_kernel_modules_share_backend_constant(self):
        """Every kernel module asks the one shared ``backend`` module when
        a kernel is traced; none keeps a mode captured at import."""
        from repro.kernels import (backend, comm_quant, ops, safa_aggregate,
                                   swa_attention)
        for mod in (comm_quant, ops, safa_aggregate, swa_attention):
            assert mod.backend is backend
            assert not hasattr(mod, 'INTERPRET')
        assert not hasattr(backend, 'INTERPRET')

    def test_env_override(self, monkeypatch):
        """The platform alone decides: no environment variable forces
        interpret mode on a TPU or compilation on a CPU, and any other
        platform is refused."""
        from repro.kernels import backend
        for value in ('1', '0', 'true', ''):
            monkeypatch.setenv('REPRO_FORCE_INTERPRET', value)
            monkeypatch.setattr(backend, 'kernel_platform', lambda: 'tpu')
            assert backend.interpret() is False
            monkeypatch.setattr(backend, 'kernel_platform', lambda: 'cpu')
            assert backend.interpret() is True
        monkeypatch.setattr(backend, 'kernel_platform', lambda: 'gpu')
        with pytest.raises(RuntimeError, match='gpu'):
            backend.interpret()


class TestQuantizedTrainFnMemo:
    def test_memoised_per_wrapped_function(self):
        class T:
            def train_a(self, x):
                return x

            def train_b(self, x):
                return x

        t = T()
        wa1 = federation._quantized_train_fn(t.train_a)
        wa2 = federation._quantized_train_fn(t.train_a)
        wb = federation._quantized_train_fn(t.train_b)
        assert wa1 is wa2          # stable static arg across runs
        assert wa1 is not wb       # no stale closure for a different method

    def test_unbound_not_cached(self):
        def free_fn(x):
            return x
        w1 = federation._quantized_train_fn(free_fn)
        w2 = federation._quantized_train_fn(free_fn)
        assert w1 is not w2


class TestCommBytesLayout:
    def test_packed_accounting(self):
        tree = {'w': jnp.zeros((100, 13)), 'b': jnp.zeros((13,))}
        spec_f = kops.pack_spec(tree)
        spec_q = kops.wire_spec(tree)
        assert kops.comm_bytes(tree, quantized=False, layout='packed') == \
            4 * spec_f.n_padded
        assert kops.comm_bytes(tree, quantized=True, layout='packed') == \
            spec_q.n_padded + 4 * (spec_q.n_padded // QBLOCK)
        # tree layout unchanged from the historical accounting
        assert kops.comm_bytes(tree, quantized=False) == 4 * 1313
        assert kops.comm_bytes(tree, quantized=True) == \
            1313 + 4 * (-(-1300 // QBLOCK) + 1)

    def test_unknown_layout_rejected(self):
        with pytest.raises(ValueError, match='layout'):
            kops.comm_bytes({'w': jnp.zeros(4)}, quantized=False,
                            layout='Packed')
