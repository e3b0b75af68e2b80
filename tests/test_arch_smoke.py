"""Per-assigned-architecture smoke tests: a REDUCED variant of each family
(2 layers, d_model<=512, <=4 experts) runs one forward/train step and one
decode step on CPU, asserting output shapes and no NaNs.

The FULL configs are exercised only via the dry-run (ShapeDtypeStruct
lowering, no allocation) — see repro.launch.dryrun and EXPERIMENTS.md.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config, shape_supported
from repro.models.model import build_model


def make_batch(cfg, key, B=2, S=16):
    kt, kl = jax.random.split(key)
    batch = {'tokens': jax.random.randint(kt, (B, S), 0, cfg.vocab_size),
             'labels': jax.random.randint(kl, (B, S), 0, cfg.vocab_size)}
    if cfg.family == 'vlm':
        batch['patch_embeds'] = 0.1 * jax.random.normal(
            kt, (B, cfg.n_patches, cfg.d_model))
    if cfg.family == 'audio':
        batch['frame_embeds'] = 0.1 * jax.random.normal(
            kt, (B, cfg.enc_seq, cfg.d_model))
    return batch


@pytest.mark.parametrize('arch_id', ARCH_IDS)
class TestArchSmoke:
    def test_reduced_train_step(self, arch_id):
        cfg = get_config(arch_id).reduced()
        assert cfg.n_layers == 2 and cfg.d_model <= 512
        if cfg.n_experts:
            assert cfg.n_experts <= 4
        model = build_model(cfg)
        key = jax.random.PRNGKey(0)
        params = model.init(key)
        batch = make_batch(cfg, key)
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
        assert np.isfinite(float(loss)), arch_id
        # one SGD step, loss decreases on the same batch
        params2 = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
        loss2 = jax.jit(model.loss)(params2, batch)
        assert np.isfinite(float(loss2))
        assert float(loss2) < float(loss) + 1e-3

    def test_reduced_decode_step(self, arch_id):
        cfg = get_config(arch_id).reduced()
        model = build_model(cfg)
        key = jax.random.PRNGKey(1)
        params = model.init(key)
        B = 2
        cache = model.init_cache(B, 24, length=0)
        tok = jax.random.randint(key, (B, 1), 0, cfg.vocab_size)
        new_cache, logits = jax.jit(model.decode_step)(params, cache, tok)
        assert logits.shape == (B, cfg.padded_vocab)
        assert bool(jnp.all(jnp.isfinite(logits))), arch_id
        assert int(new_cache['length']) == 1

    def test_full_config_shapes_only(self, arch_id):
        """The full config's parameter tree materialises as shapes without
        allocation, and the config matches its citation block."""
        cfg = get_config(arch_id)
        model = build_model(cfg)
        shapes = model.param_shapes()  # eval_shape: no allocation
        n = model.n_params()
        assert n > 1e8, (arch_id, n)
        leaves = jax.tree.leaves(shapes)
        assert all(hasattr(l, 'shape') for l in leaves)


def test_assigned_shape_matrix():
    """11 archs x 4 shapes = 44 pairs; long_500k skips documented (granite
    keeps full-attention layers, so it skips long_500k too)."""
    pairs = [(a, s) for a in ARCH_IDS for s in INPUT_SHAPES]
    assert len(pairs) == 44
    supported = [p for p in pairs if shape_supported(*p)]
    skipped = [p for p in pairs if not shape_supported(*p)]
    assert len(supported) == 36
    assert all(s == 'long_500k' for _, s in skipped)
    # sub-quadratic-capable archs run long_500k
    for a in ('mamba2-130m', 'zamba2-1.2b', 'h2o-danube-3-4b'):
        assert shape_supported(a, 'long_500k')


def test_exact_assigned_hyperparams():
    """Configs must match the assignment table exactly."""
    t = {
        'h2o-danube-3-4b': (24, 3840, 32, 8, 10240, 32000),
        'minitron-4b': (32, 3072, 24, 8, 9216, 256000),
        'nemotron-4-340b': (96, 18432, 96, 8, 73728, 256000),
        'internvl2-26b': (48, 6144, 48, 8, 16384, 92553),
        'llama4-maverick-400b-a17b': (48, 5120, 40, 8, 8192, 202048),
        'llama4-scout-17b-a16e': (48, 5120, 40, 8, 8192, 202048),
        'qwen3-1.7b': (28, 2048, 16, 8, 6144, 151936),
        'whisper-medium': (24, 1024, 16, 16, 4096, 51865),
    }
    for a, (L, d, H, KH, ff, V) in t.items():
        c = get_config(a)
        assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
                c.vocab_size) == (L, d, H, KH, ff, V), a
    z = get_config('zamba2-1.2b')
    assert (z.n_layers, z.d_model, z.n_heads, z.n_kv_heads, z.d_ff,
            z.vocab_size, z.ssm_state) == (38, 2048, 32, 32, 8192, 32000, 64)
    m = get_config('mamba2-130m')
    assert (m.n_layers, m.d_model, m.vocab_size, m.ssm_state) == \
        (24, 768, 50280, 128)
    assert get_config('llama4-maverick-400b-a17b').n_experts == 128
    assert get_config('llama4-scout-17b-a16e').n_experts == 16
