"""Public model facade: build once from a ModelConfig, use everywhere."""
from __future__ import annotations

import jax

from repro.models import common as cm
from repro.models import decode as dec
from repro.models import transformer as tfm
from repro.models.config import ModelConfig


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- parameters --------------------------------------------------------
    def init(self, key):
        """Concrete parameter values (CPU-feasible configs only)."""
        values, _ = cm.unbox(tfm.init_params(key, self.cfg))
        return values

    def param_axes(self):
        """Static logical-axes tree (no compute)."""
        with cm.abstract_init():
            _, axes = cm.unbox(tfm.init_params(jax.random.PRNGKey(0), self.cfg))
        return axes

    def param_shapes(self):
        """ShapeDtypeStruct tree (no compute)."""
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    def n_params(self) -> int:
        import math
        return sum(math.prod(s.shape) for s in jax.tree.leaves(self.param_shapes()))

    # -- forward ------------------------------------------------------------
    def loss(self, params, batch):
        return tfm.loss_fn(params, batch, self.cfg)

    def logits(self, params, batch):
        return tfm.forward_logits(params, batch, self.cfg)

    # -- serving -------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, length: int = 0):
        return dec.init_cache(self.cfg, batch, max_len, length)

    def decode_step(self, params, cache, tokens):
        return dec.decode_step(params, cache, tokens, self.cfg)

    def prefill(self, params, cache, tokens):
        return dec.prefill(params, cache, tokens, self.cfg)


def build_model(cfg: ModelConfig):
    if cfg.family == 'granite':
        from repro.models import granite
        return granite.Model(cfg)
    return Model(cfg)
